"""The device's idle share of the traced serving window: 1 - (union of device
op intervals / window)."""


def read(ctx, out, trace):
    share = trace.idle_share
    return None if share is None else 100.0 * share
