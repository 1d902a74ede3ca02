"""Serve scheduler: mean number of requests waiting for a slot, from
the per-iteration samples of ``serve/queue_depth`` over the window."""

from benchmark.lib import stats


def read(ctx):
    a, b = ctx["snap1"], ctx["snap0"]
    n = stats.delta(a, b, "serve/queue_depth/count")
    if n <= 0:
        return None
    return stats.delta(a, b, "serve/queue_depth/total_s") / n
