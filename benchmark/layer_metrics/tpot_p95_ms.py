"""Serve engine: the tail of the gap between tokens, p95 over the
requests completed in the window of ``Completion.tpot_s`` (each
request's mean gap).  Recorded, not judged (see ``ttft_p95_ms``)."""

from benchmark.lib import stats


def read(ctx):
    return stats.percentile(ctx.get("tpot_ms") or [], 95)
