"""Step program (``core/train_loop.py``): device time per step of everything
after the gradients (the ``optimizer`` scope: gradient norm, clipping,
``tx.update``, ``apply_updates``, EMA).

Chip 0's self time per traced step: the profiler trace joined with the
program's scope map (``fit``'s ``step_scopes_p0.json``) and classed by
``benchmark/lib/scoped_trace.py``.  None without a trace or a map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.ms_per_step(ctx, "optimizer")
