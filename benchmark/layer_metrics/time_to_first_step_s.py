"""Start-up (``harness/startup.py``): ``fit`` entry to the first
completed step, the program's ``startup/time_to_first_step_s`` gauge."""


def read(ctx):
    value = ctx.get("counters", {}).get("startup/time_to_first_step_s")
    return None if not value else float(value)
