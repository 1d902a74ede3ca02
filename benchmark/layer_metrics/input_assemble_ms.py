"""Input layer (``data/pipeline.py``): host work to produce one batch, in ms
(the dataset's gather, decode, augment; with N pool workers it is work per
batch, not wall).

Source: the program's ``pipeline/assemble`` timer, as ``TelemetryHook`` writes
its interval mean (``assemble_s``) into ``metrics.jsonl`` at log cadence;
averaged over the log intervals that lie wholly inside the untraced
window, weighted by their steps (one batch per step).  None where the
rows carry no such key (a program from before PR 23).
"""


def read(ctx):
    rows = [r for r in ctx.get("window_rows") or [] if "assemble_s" in r]
    steps = sum(r["interval_steps"] for r in rows)
    if not steps:
        return None
    return 1e3 * sum(r["assemble_s"] * r["interval_steps"] for r in rows) / steps
