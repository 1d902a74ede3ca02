"""Parallel layer (``core/mesh.py``, ``core/sharding.py``): share of the
traced window during which a collective instruction (all-reduce and
kin) is in flight on chip 0.  Left out on one chip."""


def read(ctx):
    trace = ctx.get("trace")
    if not trace or ctx.get("chips", 1) < 2:
        return None
    return 100.0 * trace["collective_s"] / trace["window_s"]
