"""The decode step's device time: the busy union of the device's ops inside
the runs of the program `jit_serve_decode` in the traced window, over the
number of those runs. Host time and idle are left out, unlike the engine's
`serve.engine.step_seconds`. None where the trace holds no such run."""
import progtrace

PROGRAM = "jit_serve_decode"


def read(ctx, out, trace):
    pt = progtrace.for_outcome(out)
    runs = pt.runs(PROGRAM) if pt is not None else []
    if not runs:
        return None
    return 1e-6 * pt.busy_ns([(r.start, r.end) for r in runs]) / len(runs)
