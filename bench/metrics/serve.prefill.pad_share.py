"""The padding's share of the rows the prefill computes: over the engine's
`serve.prefill` spans in the traced window, the sum of their
`padded_tokens` over the sum of `rows` x `width` (each wave is left-padded
to its longest prompt). None where the trace holds no such span."""
import progtrace


def read(ctx, out, trace):
    pt = progtrace.for_outcome(out)
    spans = pt.named("serve.prefill") if pt is not None else []
    rows = sum(s.attrs["rows"] * s.attrs["width"] for s in spans)
    if rows <= 0:
        return None
    return 100.0 * sum(s.attrs["padded_tokens"] for s in spans) / rows
