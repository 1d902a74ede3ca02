"""Models and ops (``parallel/moe.py``): the share of the step's expert
assignments that fell on the experts this chip holds, in %.

As the program reports it: ``moe_held_share`` on the ``metrics.jsonl``
rows ``TelemetryHook`` writes (averaged over the expert layers and over
the steps of each log interval), here the mean over the log intervals
that lie wholly inside the window, weighted by their steps.  With 8 of
256 experts held and even routing it is 3.125; it is what the grouped
products' rows, and with them ``moe_experts_device_ms``, scale with.
None for a program that does not report it (every expert held).
"""


def read(ctx):
    rows = [r for r in ctx.get("window_rows") or [] if "moe_held_share" in r]
    if not rows:
        return None
    steps = sum(r["interval_steps"] for r in rows)
    return 100.0 * sum(r["moe_held_share"] * r["interval_steps"] for r in rows) / steps
