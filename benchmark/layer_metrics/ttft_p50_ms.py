"""Serve scheduler: median time to first token over the requests
completed in the window (``Completion.ttft_s`` + due instant -> return
of ``LMServer.submit``).  Recorded, not judged: in the closed loop a
request arrives at a random phase of a four-step decode burst (120 ms),
and the median moves between 56 and 75 ms from seed to seed (PERF.md)."""

from benchmark.lib import stats


def read(ctx):
    return stats.percentile(ctx.get("ttft_ms") or [], 50)
