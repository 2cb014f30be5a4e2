"""Matmul kernels' share of their roofline in the kset window.

The least time of every matmul call the traced window made (the larger of
its FLOPs over the bf16 peak and its least bytes over HBM bandwidth, from
`counts.matmul`), over the device time of the Pallas custom calls inside the
`kset_matmul` programs. None where the trace holds no such kernel.
"""
import counts

PROGRAM = "jit_kset_matmul"


def read(ctx, out, trace):
    spent = trace.kernel_seconds(PROGRAM)
    if spent <= 0:
        return None
    least = sum(counts.least_seconds(c["flops"], c["bytes"],
                                     ctx.device["peaks"]) * c["count"]
                for c in out.counts["calls"] if c["kind"] == "matmul")
    return 100.0 * least * out.counts["passes"] / spent
