"""The device's idle time per decode step: over the engine's `serve.step`
spans in the traced window (each from the token upload to the end of the
step's bookkeeping; together they tile the decode loop), the device's idle
time inside them over their number. The time the chip waits on the host:
the sampler's device-to-host read, the per-slot loop, the upload and the
dispatch. None where the trace holds no such span or no device op."""
import progtrace


def read(ctx, out, trace):
    pt = progtrace.for_outcome(out)
    steps = pt.named("serve.step") if pt is not None else []
    if not steps or not pt.has_device:
        return None
    return 1e-6 * pt.idle_ns([(s.start, s.end) for s in steps]) / len(steps)
