"""Input layer (``data/pipeline.py``): wall of placing one host batch on the
mesh, in ms (``DevicePrefetcher``'s ``shard_batch`` call, host to device).

Source: the program's ``pipeline/shard`` timer, as ``TelemetryHook`` writes
its interval mean (``shard_s``) into ``metrics.jsonl`` at log cadence;
averaged over the log intervals that lie wholly inside the untraced
window, weighted by their steps (one batch per step).  None where the
rows carry no such key (a program from before PR 23).
"""


def read(ctx):
    rows = [r for r in ctx.get("window_rows") or [] if "shard_s" in r]
    steps = sum(r["interval_steps"] for r in rows)
    if not steps:
        return None
    return 1e3 * sum(r["shard_s"] * r["interval_steps"] for r in rows) / steps
