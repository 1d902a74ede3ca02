"""Serve scheduler: the tail of time to first token, p95 over the
requests completed in the window of ``Completion.ttft_s`` + (due
instant -> return of ``LMServer.submit``).  Recorded, not judged: with
a hundred-odd requests in a window a p95 has fewer than ten samples
beyond it and swings by tens of percent from seed to seed (PERF.md)."""

from benchmark.lib import stats


def read(ctx):
    return stats.percentile(ctx.get("ttft_ms") or [], 95)
