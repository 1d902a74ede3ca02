"""Serve scheduler (``serving/scheduler.py``): mean share of decode
slots in use, from the per-iteration samples of the program's
``serve/slot_occupancy`` timer between the window's two instants."""

from benchmark.lib import stats


def read(ctx):
    a, b = ctx["snap1"], ctx["snap0"]
    n = stats.delta(a, b, "serve/slot_occupancy/count")
    if n <= 0:
        return None
    return 100.0 * stats.delta(a, b, "serve/slot_occupancy/total_s") / n
