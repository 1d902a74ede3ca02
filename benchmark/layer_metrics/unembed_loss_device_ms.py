"""Models and ops (``ops/losses.py``): device time per step under the
``unembed_loss`` scope (head projection + cross entropy where fused, the
cross entropy alone on the plain path), forward and backward together.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map (``fit``'s ``step_scopes_p0.json``) and classed by
``benchmark/lib/scoped_trace.py``.  None without a trace or a map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.ms_per_step(ctx, "unembed_loss")
