"""Device time of the step program, by where in the program it came from.

The trace names each device event after its HLO instruction and carries
no scope (``enable_hlo_proto=False``, and the ``XLA Ops`` events hold no
``metadata=``).  The program writes the other half: ``fit`` leaves
``step_scopes_p0.json`` (instruction name -> ``op_name``, per HLO
module) beside its span export.  This module joins the two and classes
chip 0's self time.  The rules are the benchmark's, so that no later PR
changes its own yardstick:

- an instruction counts only while a module of the map runs (its event
  starts inside an ``XLA Modules`` event of that module's name); what
  runs outside is ``outside_s``;
- ``optimizer`` if ``optimizer`` is an element of the ``op_name``'s
  path; else ``bwd`` if it holds ``transpose(`` (a forward recomputed
  under remat counts here: it runs during the backward pass); else
  ``fwd`` if it holds ``jvp(``; else ``other`` (no entry in the map:
  XLA's own copies and slices; or an entry under no transform: the
  per-step key derivation);
- a named scope (``attention_core``, ``unembed_loss``) is matched as a
  whole element, bare or inside a transform's brackets
  (``.../attn/attention_core/...``, ``transpose(jvp(unembed_loss))``),
  forward and backward together.

XLA fuses across these scopes, and a fused kernel's time cannot be
split: it goes to the class of the one ``op_name`` XLA kept for the
fusion.  Where the map's ``fused`` table says that a fusion holds both
``optimizer`` instructions and forward or backward ones, its time is
also counted as ``optimizer_mixed``: the optimizer's cost that hides
in kernels of another class (or the model's cost inside a kernel
classed ``optimizer``).  A forward instruction inside a backward kernel
is no such mixture: XLA recomputes cheap forward pieces there, and the
rules above count recomputed forward as backward anyway.

``outside_s`` plus the four classes is chip 0's busy time
(``trace_reduce``'s ``busy_s_chip0``) exactly: both are the self times
of the same events.  The trace is clipped at the marker as
``runners/train_fit.py`` clips it.  A run is read once, by the first reader
that asks.
"""

from __future__ import annotations

import logging
import re
import time
from typing import Optional

import numpy as np

from benchmark.lib import cells, run_files, trace_reduce
from benchmark.lib.window_hook import MARKER_IDLE_S, SYNC_MARKER

log = logging.getLogger("benchmark")

CLASSES = ("fwd", "bwd", "optimizer", "other")
SCOPES = ("attention_core", "unembed_loss")


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a whole element of the path ``op_name``."""
    return re.search(rf"(?:^|[/(]){re.escape(scope)}(?:[/)]|$)", op_name) is not None


def classify(op_name: Optional[str]) -> str:
    if not op_name:
        return "other"
    if in_scope(op_name, "optimizer"):
        return "optimizer"
    if "transpose(" in op_name:
        return "bwd"
    if "jvp(" in op_name:
        return "fwd"
    return "other"


def module_key(event_name: str) -> str:
    """``jit_one_step(970020429550526235)`` -> ``jit_one_step``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def mixed_fusions(fused: Optional[dict]) -> set:
    """The fusions of one module that hold ``optimizer`` instructions
    beside forward or backward ones, from the scope map's ``fused``
    table of that module (None or empty: none known)."""
    if not fused:
        return set()
    classes = [classify(name) for name in fused["names"]]
    out = set()
    for fusion, held in fused["inside"].items():
        kinds = {classes[i] for i in held}
        if "optimizer" in kinds and kinds & {"fwd", "bwd"}:
            out.add(fusion)
    return out


def _keys_by_instruction(scopes: dict, fused: Optional[dict]) -> dict:
    """For one module of the map: instruction -> the keys of ``seconds``
    its time is added to (its class; the named scopes it is under;
    ``optimizer_mixed``)."""
    mixed = mixed_fusions(fused)
    return {
        instruction: [classify(op_name)]
        + [scope for scope in SCOPES if in_scope(op_name, scope)]
        + (["optimizer_mixed"] if instruction in mixed else [])
        for instruction, op_name in scopes.items()
    }


def reduce_scoped(
    planes: dict,
    modules: dict,
    *,
    start_s: Optional[float] = None,
    fused: Optional[dict] = None,
) -> Optional[dict]:
    """Chip 0's self time in seconds by class, by named scope and in
    optimizer-mixed kernels (``seconds``), or None when no module of
    ``modules`` (the scope map's ``modules``) ran on the chip.
    ``fused`` is the map's table of the same name."""
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
        (s, e, module_key(n)) for n, (s, e) in zip(m_names, m_spans) if module_key(n) in modules
    )
    if not runs:
        return None
    keys = {k: _keys_by_instruction(modules[k], (fused or {}).get(k)) for k in modules}
    # The run of a mapped module each event starts in, or none.
    at = np.searchsorted([r[0] for r in runs], spans[:, 0], side="right") - 1
    seconds = dict.fromkeys(CLASSES + SCOPES + ("optimizer_mixed",), 0.0)
    outside = 0.0
    for name, start, i, secs in zip(names, spans[:, 0], at, trace_reduce.self_times(spans)):
        if i < 0 or start > runs[i][1]:
            outside += float(secs)
            continue
        for key in keys[runs[i][2]].get(trace_reduce.op_name(name), ("other",)):
            seconds[key] += float(secs)
    module_s = sum(seconds[c] for c in CLASSES)
    return {
        "seconds": seconds,
        "module_s": module_s,
        "outside_s": outside,
        "busy_s_chip0": module_s + outside,
        "module_runs": len(runs),
    }


def read_run(xplane: str, scopes_file: str) -> Optional[dict]:
    from jax.profiler import ProfileData

    found = cells.read_json(scopes_file)
    modules = found.get("modules") or {}
    planes = trace_reduce.read_planes(ProfileData.from_file(xplane), {SYNC_MARKER})
    start = trace_reduce.marker_start_s(planes, SYNC_MARKER)
    clip = None if start is None else start + MARKER_IDLE_S / 2
    return reduce_scoped(planes, modules, start_s=clip, fused=found.get("fused"))


def summary(ctx: dict) -> Optional[dict]:
    """:func:`reduce_scoped` of this run's trace and scope map
    (``run_files``), or None when the run has no trace or the program
    wrote no map (as a program from before PR 23 does not).  Read once
    and kept in the run's ``ctx`` for the other readers."""
    if "scoped_trace" not in ctx:
        work = run_files.work_dir()
        xplane = run_files.xplane_path(work)
        scopes_file = run_files.step_scopes_path(work)
        found = None
        if xplane is not None and scopes_file is not None:
            t0 = time.perf_counter()
            found = read_run(xplane, scopes_file)
            log.info("scoped trace: read %s in %.2f s", xplane, time.perf_counter() - t0)
        ctx["scoped_trace"] = found
    return ctx["scoped_trace"]


def ms_per_step(ctx: dict, key: str) -> Optional[float]:
    """Chip 0's self time per traced step, in ms, of ``key``: a class
    (``fwd``, ``bwd``, ``optimizer``, ``other``), a named scope
    (``attention_core``, ``unembed_loss``; forward and backward
    together) or ``optimizer_mixed`` (the fused kernels that hold
    optimizer instructions beside forward or backward ones)."""
    steps = (ctx.get("trace") or {}).get("steps")
    found = summary(ctx) if steps else None
    return None if found is None else 1e3 * found["seconds"][key] / steps


def coverage_percent(ctx: dict) -> Optional[float]:
    """Share of the mapped modules' self time that fell in ``fwd``,
    ``bwd`` or ``optimizer``."""
    found = summary(ctx) if (ctx.get("trace") or {}).get("steps") else None
    if found is None or not found["module_s"]:
        return None
    return 100.0 * (1.0 - found["seconds"]["other"] / found["module_s"])
