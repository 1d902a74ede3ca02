"""Models and ops (``ops/linear_attention.py::chunked_gdn``): device time
per step under the ``gdn_core`` scope, forward and backward together: the
chunk-wise gated delta rule alone (one decay a head: masked products,
the triangular inverse, the state carried over the chunks, the read),
inside ``linear_attn``.

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map by ``benchmark/lib/named_scopes.py``.  None without
a trace or a map, or for a program without the scope.
"""

from benchmark.lib import named_scopes


def read(ctx):
    return named_scopes.ms_per_step(ctx, "gdn_core")
