"""Models and ops (``ops/selective_scan.py::selective_scan``): device time
per step under the ``sscan_core`` scope, forward and backward together:
Mamba-1's selective scan alone (a decay for every channel and state, the
state carried token by token, the read and the ``D`` skip, and the
relayout of its operands), inside ``ssm``.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "sscan_core")
