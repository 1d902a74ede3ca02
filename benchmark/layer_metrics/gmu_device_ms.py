"""Models and ops (``models/mixers.py::GatedMemoryUnit``): device time per
step under the ``gmu`` scope, forward and backward together: the gated
memory unit (its input projection, the gate over an earlier layer's scan
output, its output projection), inside ``ssm``.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "gmu")
