"""Serve entry (``serving/server.py``): what a request waits outside
the scheduler's own clocks.  Per request: client latency (due ->
completion noticed) - ``Completion.ttft_s`` - ``Completion.tpot_s`` x
(tokens - 1); p95 over the requests completed in the window."""

from benchmark.lib import stats


def read(ctx):
    return stats.percentile(ctx.get("intake_ms") or [], 95)
