"""Serve engine: share of the window spent inside ``serve/prefill``
spans (prefill dispatches, during which no slot decodes)."""

from benchmark.lib import stats


def read(ctx):
    a, b = ctx["snap1"], ctx["snap0"]
    if "serve/prefill/total_s" not in a:
        return None
    return 100.0 * stats.delta(a, b, "serve/prefill/total_s") / ctx["window_s"]
