"""Serve engine (``serving/engine.py``): wall time of one decode step,
``serve/decode`` span total over the window / (dispatches x burst).
The engine fetches the tokens to the host after every dispatch, so the
span is device-synced."""

from benchmark.lib import stats


def read(ctx):
    a, b = ctx["snap1"], ctx["snap0"]
    n = stats.delta(a, b, "serve/decode/count")
    if n <= 0:
        return None
    return 1e3 * stats.delta(a, b, "serve/decode/total_s") / (n * ctx["decode_burst"])
