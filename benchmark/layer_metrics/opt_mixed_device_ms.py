"""Step program: device time per step of the fused kernels that hold
instructions of the ``optimizer`` scope beside forward or backward ones.

XLA fuses across the scopes (a weight gradient's kernel may carry the
gradient norm's partial sum and the momentum update), and a fused
kernel's time goes whole to the class of the one ``op_name`` XLA kept
for it.  This is how much of the step is in such kernels: the part of
the optimizer's cost that ``opt_device_ms`` cannot see (or, where XLA
kept the optimizer's name, the model's work inside ``opt_device_ms``).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map (``fit``'s ``step_scopes_p0.json``, its ``fused``
table) by ``benchmark/lib/scoped_trace.py``.  None without a trace or a
map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.ms_per_step(ctx, "optimizer_mixed")
