"""Start-up (``harness/startup.py``): the process's start (the kernel's
record, where ``setup_s`` starts too) to ``fit``'s entry: interpreter,
imports, the device client, the benchmark's own cell loading.  The
program's ``startup/process_to_fit_s`` gauge; None for a program that
writes no such gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/process_to_fit_s")
    return None if value is None else float(value)
