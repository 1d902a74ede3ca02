"""Start-up (``harness/startup.py``): the AOT thread's whole
``lower().compile()`` of the train step (lowering, then the compile or
the read of the persistent cache); it overlaps ``fit``'s own phases.
The program's ``startup/aot_compile_s`` gauge; None for a program that
writes no such gauge, 0.0 where nothing was compiled ahead of time."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/aot_compile_s")
    return None if value is None else float(value)
