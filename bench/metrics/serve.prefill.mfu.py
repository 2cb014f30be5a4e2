"""The prefill step's share of the chip's bf16 peak: the forward FLOPs of
the real (unpadded) prompt tokens of every wave (`counts.prefill_flops`)
over the engine's `serve.engine.prefill_seconds` times the peak."""


def read(ctx, out, trace):
    spent = out.counts.get("prefill_seconds", 0.0)
    if spent <= 0:
        return None
    return 100.0 * out.counts["prefill_flops"] / (
        spent * ctx.device["peaks"]["bf16_flops_per_s"])
