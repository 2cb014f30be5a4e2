"""Flash-attention kernels' share of their roofline in the kset window.

FLOPs over the causal, windowed pairs only, and bytes with K/V at the
model's own KV heads (`counts.attention`), so a kernel that reads K/V once
per group cannot read over 100%. Device time of the Pallas custom calls
inside the `kset_attention` programs. None where there is no such kernel.
"""
import counts

PROGRAM = "jit_kset_attention"


def read(ctx, out, trace):
    spent = trace.kernel_seconds(PROGRAM)
    if spent <= 0:
        return None
    least = sum(counts.least_seconds(c["flops"], c["bytes"],
                                     ctx.device["peaks"]) * c["count"]
                for c in out.counts["calls"] if c["kind"] == "attention")
    if least <= 0:
        return None
    return 100.0 * least * out.counts["passes"] / spent
