"""Models and ops (``ops/ssm.py::chunked_ssd``): device time per step
under the ``ssd_core`` scope, forward and backward together: Mamba-2's
state-space dual scan alone (one ``C B^T`` a chunk under each head's
mask of decays, the state carried over the chunks, the read and the
``D`` skip), inside ``ssm``.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "ssd_core")
