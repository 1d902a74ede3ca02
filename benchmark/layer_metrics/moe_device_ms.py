"""Models and ops (``parallel/moe.py::topk_moe_ffn``): device time per
step under the ``moe`` scope, forward and backward together:
the whole expert layer (router, sort, gathers, grouped products, gate, weighted sum back).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "moe")
