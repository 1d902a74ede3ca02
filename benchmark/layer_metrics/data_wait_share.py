"""Input layer (``data/pipeline.py``): share of the window the training
loop spent blocked in ``next(batch)``.

Source: the program's ``train/data_wait`` timer, as ``TelemetryHook``
writes its per-step mean (``data_wait_s``) into ``metrics.jsonl`` at log
cadence; summed over the log intervals that lie wholly inside the
window and divided by those intervals' wall time.
"""


def read(ctx):
    rows = ctx.get("window_rows")
    if not rows:
        return None
    waited = sum(r["data_wait_s"] * r["interval_steps"] for r in rows)
    wall = sum(r["interval_steps"] / r["steps_per_sec"] for r in rows)
    return 100.0 * waited / wall
