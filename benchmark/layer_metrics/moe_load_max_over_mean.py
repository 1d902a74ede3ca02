"""Models and ops (``parallel/moe.py``): how uneven the routing was.

The fullest expert's assignments over the mean, as the program reports
it: ``moe_load_max_over_mean`` on the ``metrics.jsonl`` rows
``TelemetryHook`` writes (averaged over layers and over the steps of
each log interval), here the mean over the log intervals that lie wholly
inside the window, weighted by their steps.  1 is an even split; 64
would be every token to one expert.  None for a program that does not
report it.
"""


def read(ctx):
    rows = [r for r in ctx.get("window_rows") or [] if "moe_load_max_over_mean" in r]
    if not rows:
        return None
    steps = sum(r["interval_steps"] for r in rows)
    return sum(r["moe_load_max_over_mean"] * r["interval_steps"] for r in rows) / steps
