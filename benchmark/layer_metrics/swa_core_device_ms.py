"""Models and ops (``ops/attention.py``): device time per step under the
``swa_core`` scope, forward and backward together: the attention core of
the layers that run under a sliding window, inside ``attention_core``
(which holds the full-span layers' cores beside it).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "swa_core")
