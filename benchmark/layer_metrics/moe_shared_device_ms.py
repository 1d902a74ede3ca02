"""Models and ops (``models/transformer_lm.py::TopKExpertsFFN``): device
time per step under the ``moe_shared`` scope, forward and backward
together: the shared expert that every token goes through beside its
routed experts, inside ``moe``.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "moe_shared")
