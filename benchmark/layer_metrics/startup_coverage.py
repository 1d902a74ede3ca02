"""Start-up (``harness/startup.py``): the share of ``fit`` entry to the
first completed chunk (``startup/time_to_first_step_s``) that the
program's exclusive start-up phases cover, in %: the start-up layer's
``scope_coverage``.  The rest is ``startup/unattributed_s``.  None for a
program that writes no phases, or before a first step."""

PHASES = (
    "startup/build_state_s",
    "startup/build_step_s",
    "startup/restore_s",
    "startup/dataset_s",
    "startup/pipeline_open_s",
    "startup/first_chunk_s",
)


def read(ctx):
    counters = ctx.get("counters", {})
    total = counters.get("startup/time_to_first_step_s")
    if not total or any(key not in counters for key in PHASES):
        return None
    return 100.0 * sum(counters[key] for key in PHASES) / total
