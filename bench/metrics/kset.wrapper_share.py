"""The kernel wrappers' share of the kset programs' device time: inside the
runs of the `jit_kset_*` programs in the traced window, the busy device
time outside the named Pallas kernels (`%matmul.N`, `%flash_attention.N`:
each `pallas_call`'s name) over the whole busy time. What is left is the
pads, slices, copies and casts `kernels/*.py` put around each kernel. None
where the trace holds no such run or no named kernel."""
import progtrace

PROGRAM = "jit_kset_"


def read(ctx, out, trace):
    pt = progtrace.for_outcome(out)
    runs = pt.runs(PROGRAM) if pt is not None else []
    within = [(r.start, r.end) for r in runs]
    busy = pt.busy_ns(within) if runs else 0.0
    kernels = pt.kernel_ns(within) if runs else 0.0
    if busy <= 0 or kernels <= 0:
        return None
    return 100.0 * (busy - kernels) / busy
