"""The scope map of a compiled step: instruction name -> ``op_name``.

A device trace names every event after the HLO instruction it ran
(``%fusion.12 = ...``) and carries nothing of where the instruction came
from.  The compiled program does: each instruction's
``metadata={op_name="jit(one_step)/transpose(jvp(Transformer))/block_3/
attention_core/..."}`` holds flax's module path, the transform
(``jvp(`` forward, ``transpose(`` backward) and this repo's own
``jax.named_scope`` names (``optimizer``, ``unembed_loss``,
``attention_core``).  ``fit`` writes the join of the two beside its span
export (``step_scopes_p<i>.json``, under ``cfg.trace_export``), and
whoever reads the trace classes device time by it
(``benchmark/lib/scoped_trace.py``).

File format (``version`` 1)::

    {"version": 1,
     "modules": {"<HLO module name, as the trace's XLA Modules line shows
                  it without the (id)>": {"<instruction>": "<op_name>"}},
     "fused": {"<module>": {"names": ["<op_name>", ...],
                            "inside": {"<fusion>": [<index into names>, ...]}}}}

Only instructions the core runs under their own name are listed: those
of the entry computation, of ``while`` bodies and conditions, of
conditional branches and called computations.  The inside of a fusion
never shows in a trace (the fusion instruction does, with a
``metadata`` of its own: that of the one instruction the fusion grew
from), nor do the reducers that ``to_apply=`` names.  Instructions
without metadata (XLA's own copies and slices) are left out.

XLA fuses across this repo's scopes (a weight gradient's kernel may hold
the gradient norm's partial sum and the momentum update), and a fused
kernel's time cannot be split.  So ``fused`` lists, for every fusion
whose inside holds an ``op_name`` other than its own, the distinct
``op_name`` values inside it: a reader can tell a pure kernel from a
mixed one, whatever name XLA kept for the fusion.

Text parsing only: importing this module needs no jax.
"""

from __future__ import annotations

import logging
import os
import re
import time
from typing import Callable, Iterable, Optional

from distributed_tensorflow_models_tpu.telemetry import trace as tracelib

log = logging.getLogger("dtm")

SCOPES_VERSION = 1

_MODULE = re.compile(r"^HloModule\s+([^\s,]+)")
# ``%name (params) -> shape {`` / ``ENTRY %name (...) -> ... {``.
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+\(.*->.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s")
_OP_NAME = re.compile(r'\bop_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bkind=k\w+,\s*calls=%?([\w.\-]+)")
_APPLIED = re.compile(r"\bto_apply=%?([\w.\-]+)")
# A parameter runs nothing; its ``op_name`` is the argument's name.
_PARAMETER = re.compile(r"\sparameter\(\d+\)")


def step_scopes_path(workdir: str, process_index: int) -> str:
    """The per-process scope-map path, beside ``trace_p<i>.json``."""
    return os.path.join(workdir, f"step_scopes_p{process_index}.json")


def parse_hlo(text: str) -> tuple[Optional[str], dict[str, str], dict]:
    """``(module name, {instruction: op_name}, fused)`` of one compiled
    program's text (``Compiled.as_text()``); ``fused`` is ``{"names":
    [...], "inside": {fusion: [indices]}}`` as the module docstring has
    it."""
    module = None
    by_computation: dict[str, dict[str, str]] = {}
    fusions: dict[str, str] = {}  # fusion instruction -> fused computation
    inside: set[str] = set()  # fused computations and reducers
    current: Optional[dict] = None
    for line in text.splitlines():
        if current is None:
            if module is None:
                m = _MODULE.match(line)
                if m:
                    module = m.group(1)
                    continue
            m = _COMPUTATION.match(line)
            if m:
                current = by_computation.setdefault(m.group(1), {})
            continue
        if line.startswith("}"):
            current = None
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        fused = _FUSED.search(line)
        if fused:
            fusions[m.group(1)] = fused.group(1)
            inside.add(fused.group(1))
        inside.update(_APPLIED.findall(line))
        scope = _OP_NAME.search(line)
        if scope and not _PARAMETER.search(line):
            current[m.group(1)] = scope.group(1)
    scopes: dict[str, str] = {}
    for name, instructions in by_computation.items():
        if name not in inside:
            scopes.update(instructions)
    names: dict[str, int] = {}
    mixed: dict[str, list[int]] = {}
    for fusion, computation in fusions.items():
        if fusion not in scopes:
            continue
        held = set(by_computation.get(computation, {}).values())
        if held - {scopes[fusion]}:
            mixed[fusion] = sorted(
                names.setdefault(n, len(names)) for n in held
            )
    return module, scopes, {"names": list(names), "inside": mixed}


def write_step_scopes(
    path: str, executables: Callable[[], Iterable]
) -> Optional[dict]:
    """Write the scope map of the compiled programs ``executables()``
    returns (as ``lower().compile()`` gives them;
    ``InstrumentedStep.executables``) to ``path``; returns what it
    cost (``{"modules", "instructions", "hlo_bytes", "file_bytes",
    "seconds"}``) or None when no program gave its text.  Never raises:
    an executable read from the cache that holds no HLO, or a full disk,
    is logged once and the run goes on."""
    t0 = time.perf_counter()
    modules: dict[str, dict[str, str]] = {}
    fused: dict[str, dict] = {}
    hlo_bytes = 0
    try:
        for exe in executables():
            text = exe.as_text()
            if not text:
                continue
            hlo_bytes += len(text)
            module, scopes, inside = parse_hlo(text)
            # Two programs of one module name (two batch signatures of
            # one jit) cannot be told apart in a trace, and their
            # instruction names collide: the first, the main one, stays.
            if module and scopes and module not in modules:
                modules[module], fused[module] = scopes, inside
        if not modules:
            log.warning(
                "step scopes: no compiled program gave its HLO text; "
                "writing no %s", os.path.basename(path),
            )
            return None
        tracelib._atomic_json(
            path,
            {"version": SCOPES_VERSION, "modules": modules, "fused": fused},
        )
        file_bytes = os.path.getsize(path)
    except Exception:  # noqa: BLE001 — reporting must never mask training
        log.warning("step scopes: could not write %s", path, exc_info=True)
        return None
    cost = {
        "modules": len(modules),
        "instructions": sum(len(m) for m in modules.values()),
        "hlo_bytes": hlo_bytes,
        "file_bytes": file_bytes,
        "seconds": time.perf_counter() - t0,
    }
    log.info("step scopes: wrote %s (%s)", path, cost)
    return cost
