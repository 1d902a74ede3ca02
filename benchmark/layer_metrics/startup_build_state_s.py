"""Start-up (``harness/startup.py``): placing the compile cache, the
mesh and ``build_state`` (``model.init``'s trace, its compile or cache
read, dispatch of the parameter draw, placement), as host time on
``fit``'s thread.  The program's ``startup/build_state_s`` gauge; None
for a program that writes no such gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/build_state_s")
    return None if value is None else float(value)
