"""Start-up (``harness/startup.py``): the AOT thread's tracing and
lowering of the train step, the part of a warm start no cache shortens;
it overlaps ``fit``'s own phases.  The program's ``startup/aot_lower_s``
gauge; None for a program that writes no such gauge, 0.0 where nothing
was lowered ahead of time."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/aot_lower_s")
    return None if value is None else float(value)
