"""Start-up (``harness/startup.py``): the loop's first iteration (the
wait for the first batch, the lowering that prices the step, the wait
for the AOT thread, the first dispatch and its hook walk).  The
program's ``startup/first_chunk_s`` gauge; None for a program that
writes no such gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/first_chunk_s")
    return None if value is None else float(value)
