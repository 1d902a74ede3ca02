"""Models and ops: device time of the backward pass per step (instructions
whose ``op_name`` holds ``transpose(``; a forward recomputed under remat
counts here).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map (``fit``'s ``step_scopes_p0.json``) and classed by
``benchmark/lib/scoped_trace.py``.  None without a trace or a map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.ms_per_step(ctx, "bwd")
