"""Device time of the step program under any ``jax.named_scope``.

``scoped_trace.py`` fills a fixed tuple of scopes (``SCOPES``); a reader
for a scope that a later PR adds to the program (``moe``,
``moe_experts``, ``moe_dispatch``: PR 25) cannot ask it.  This module
takes the scope's name as an argument.  It is built from what
``scoped_trace`` and ``trace_reduce`` export, under the same rules: chip
0's self time, only while a module of the program's scope map runs, the
trace clipped at the marker, a scope matched as a whole element of the
instruction's ``op_name`` (forward and backward together; a fused kernel
counts under the one ``op_name`` XLA kept for it).  A run's trace is read
once, by the first reader that asks, into seconds by ``op_name``
(``ctx["named_scopes"]``); a scope's time is the sum over the names it is
an element of.  Folding this back into ``scoped_trace.py`` is a later
``benchmark`` issue's (PERF.md section 7).

Where the run has no trace, or the program wrote no scope map (as a
program from before PR 23 does not), every reading is None.
"""

from __future__ import annotations

import logging
import time
from typing import Optional

import numpy as np

from benchmark.lib import cells, run_files, scoped_trace, trace_reduce
from benchmark.lib.window_hook import MARKER_IDLE_S, SYNC_MARKER

log = logging.getLogger("benchmark")


def seconds_by_op_name(
    planes: dict, modules: dict, *, start_s: Optional[float] = None
) -> Optional[dict]:
    """Chip 0's self time in seconds by ``op_name`` (``""`` for an
    instruction the map does not hold), over the runs of the modules of
    ``modules`` (the scope map's ``modules``); None when none ran."""
    devices = planes["devices"]
    if start_s is not None:
        devices = trace_reduce.clip_devices(devices, start_s)
    ops = trace_reduce.OPS_LINE
    busy = [i for i, lines in devices.items() if ops in lines and len(lines[ops][1])]
    if not busy:
        return None
    lines = devices[min(busy)]
    if trace_reduce.MODULES_LINE not in lines:
        return None
    names, spans = lines[ops]
    m_names, m_spans = lines[trace_reduce.MODULES_LINE]
    runs = sorted(
        (s, e, scoped_trace.module_key(n))
        for n, (s, e) in zip(m_names, m_spans)
        if scoped_trace.module_key(n) in modules
    )
    if not runs:
        return None
    at = np.searchsorted([r[0] for r in runs], spans[:, 0], side="right") - 1
    seconds: dict = {}
    for name, start, i, secs in zip(names, spans[:, 0], at, trace_reduce.self_times(spans)):
        if i < 0 or start > runs[i][1]:
            continue
        op_name = modules[runs[i][2]].get(trace_reduce.op_name(name), "")
        seconds[op_name] = seconds.get(op_name, 0.0) + float(secs)
    return seconds


def read_run(xplane: str, scopes_file: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    modules = cells.read_json(scopes_file).get("modules") or {}
    planes = trace_reduce.read_planes(ProfileData.from_file(xplane), {SYNC_MARKER})
    start = trace_reduce.marker_start_s(planes, SYNC_MARKER)
    clip = None if start is None else start + MARKER_IDLE_S / 2
    return seconds_by_op_name(planes, modules, start_s=clip)


def table(ctx: dict) -> Optional[dict]:
    """:func:`seconds_by_op_name` of this run (``run_files``), read once
    and kept in ``ctx``."""
    if "named_scopes" not in ctx:
        work = run_files.work_dir()
        xplane = run_files.xplane_path(work)
        scopes_file = run_files.step_scopes_path(work)
        found = None
        if xplane is not None and scopes_file is not None:
            t0 = time.perf_counter()
            found = read_run(xplane, scopes_file)
            log.info("named scopes: read %s in %.2f s", xplane, time.perf_counter() - t0)
        ctx["named_scopes"] = found
    return ctx["named_scopes"]


def scope_seconds(seconds: dict, scope: str) -> float:
    return sum(s for op_name, s in seconds.items() if scoped_trace.in_scope(op_name, scope))


def ms_per_step(ctx: dict, scope: str) -> Optional[float]:
    """Chip 0's self time per traced step, in ms, under ``scope``; None
    without a trace or a map, or where no instruction of the program is
    under ``scope`` (a program that does not have it)."""
    steps = (ctx.get("trace") or {}).get("steps")
    found = table(ctx) if steps else None
    if found is None or not any(scoped_trace.in_scope(n, scope) for n in found):
        return None
    return 1e3 * scope_seconds(found, scope) / steps
