"""Models and ops (``models/mixers.py::Mamba2Mixer``): device time per
step under the ``ssm`` scope, forward and backward together: the whole
Mamba-2 state-space mixer (the input projection, the short convolution
over ``x``, ``B`` and ``C``, ``dt``, the chunk-wise scan, the gate, the
norm and the output projection).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "ssm")
