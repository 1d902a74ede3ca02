"""Parallel layer: share of the traced window during which a collective
instruction is in flight on chip 0 and no other instruction runs
there: the communication that compute does not hide."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_exposed_s"] / trace["window_s"]
