"""The decode step's share of its roofline: over every step of the window,
the least time of the rows still producing tokens (the larger of FLOPs over
the bf16 peak and least bytes over HBM bandwidth: the stored weights once,
each row's cached K/V; `counts.decode_step`) over the engine's
`serve.engine.step_seconds`."""


def read(ctx, out, trace):
    spent = out.counts.get("step_seconds", 0.0)
    if spent <= 0:
        return None
    return 100.0 * out.counts["decode_least_s"] / spent
