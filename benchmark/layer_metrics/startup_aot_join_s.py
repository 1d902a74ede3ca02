"""Start-up (``harness/startup.py``): the part of the AOT thread's
compile that the loop's first iteration waited for.  The program's
``startup/aot_join_s`` gauge; None for a program that writes no such
gauge, 0.0 (a value, not a gap) where the thread had finished."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/aot_join_s")
    return None if value is None else float(value)
