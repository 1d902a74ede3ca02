"""Models and ops (``models/mixers.py::KDAMixer``): device time per step
under the ``linear_attn`` scope, forward and backward together: the
whole KDA mixer (projections, short convolutions, the decay and the
gates, the chunk-wise core, the output norm and projection).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "linear_attn")
