"""Step program: share of the step module's device self time that the scope
map names ``fwd``, ``bwd`` or ``optimizer``.  A stale or missing map shows
here, as low coverage, not as a wrong split.

From the profiler trace joined with the program's scope map (``fit``'s
``step_scopes_p0.json``) by ``benchmark/lib/scoped_trace.py``.  None
without a trace or a map.
"""

from benchmark.lib import scoped_trace


def read(ctx):
    return scoped_trace.coverage_percent(ctx)
