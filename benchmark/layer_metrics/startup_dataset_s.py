"""Start-up (``harness/startup.py``): ``build_dataset`` on ``fit``'s
thread, while the AOT thread lowers the step.  The program's
``startup/dataset_s`` gauge; None for a program that writes no such
gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/dataset_s")
    return None if value is None else float(value)
