"""Start-up (``harness/startup.py``): ``fit``'s entry to the end of the
first hook walk that fetched a loss row, the first instant a step is
known to have finished on the device; with ``startup_process_to_fit_s``
it adds up to ``setup_s`` where the warm-up ends on a log step.  The
program's ``startup/first_loss_row_s`` gauge; None for a program that
writes no such gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/first_loss_row_s")
    return None if value is None else float(value)
