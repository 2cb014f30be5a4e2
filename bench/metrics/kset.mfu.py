"""A whole kset pass's share of the chip's bf16 peak: the pass's FLOPs
(`counts`) over the traced run's `kset_ms` times the peak."""


def read(ctx, out, trace):
    ms = out.e2e.get("kset_ms")
    if not ms:
        return None
    return 100.0 * out.counts["pass_flops"] / (
        ms * 1e-3 * ctx.device["peaks"]["bf16_flops_per_s"])
