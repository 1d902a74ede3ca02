"""Models and ops (``ops/attention.py``): device time per step under the
``attention_core`` scope, forward and backward together; the q/k/v/out
projections are outside it.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map (``fit``'s ``step_scopes_p0.json``) and classed by
``benchmark/lib/scoped_trace.py``.  None without a trace or a map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.ms_per_step(ctx, "attention_core")
