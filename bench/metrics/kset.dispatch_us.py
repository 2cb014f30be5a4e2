"""Host CPU time per kernel call in the kset window: the mean thread CPU
time inside the bench's own `bench.call.*` spans, each around one jitted
`ops.tuned_*` dispatch (with no sync inside). CPU time, not wall time: while
the device is busy a dispatch waits for a free slot in its queue, and that
wait is the device's time, not the host's."""


def read(ctx, out, trace):
    spans = ctx.spans.cpu_seconds("bench.call.")
    if not spans:
        return None
    return 1e6 * sum(spans) / len(spans)
