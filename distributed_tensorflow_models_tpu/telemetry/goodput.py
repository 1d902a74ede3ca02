"""Goodput accounting: where did the wall time go?

Production training stacks live or die on this number: the fraction of
wall time actually spent computing versus waiting on input, checkpoints,
or the compiler.  The report splits total wall time into exactly four
categories — ``compute`` is the residual, so the fractions sum to 1.0 by
construction:

    compute     = total - data_stall - checkpoint - compile
    data_stall  = train/data_wait        (loop blocked in next(batch))
    checkpoint  = checkpoint/{save,restore,wait,fence}
    compile     = train/compile          (explicit XLA compile events)

The report also carries a ``startup`` section: the start-up timeline
(``registry.STARTUP_*``; ``harness/startup.py::Timeline``, stamped by
``fit``).  ``process_to_fit_s`` and then the exclusive phases, which
add up to ``time_to_first_step_s`` but for ``unattributed_s``; the AOT
thread's ``aot_lower_s`` and ``aot_compile_s``, which overlap them;
``first_loss_row_s``; the persistent cache's ``compile_requests`` and
``cache_hits`` up to the first chunk; ``modules_at_fit`` with
``cloud_logging_imported``.  All of it is reported alongside — never
added into — the four exclusive fractions above, which still sum to
exactly 1.0.

MFU is wall-clock-inclusive (FLOPs retired per second of *total* time over
peak), i.e. it already prices in every stall — the honest end-to-end
number.
"""

from __future__ import annotations

import json
import os
from typing import Optional

from distributed_tensorflow_models_tpu.telemetry import registry as reglib

# Peak dense bf16 FLOPs/sec per chip, by jax ``device_kind`` prefix — the
# one table.  Source: Google Cloud TPU documentation,
# per-chip specifications of each generation (v4 275, v5e 197, v5p 459,
# v6e 918 TFLOP/s bf16).  A v5e chip reports ``device_kind`` "TPU v5 lite".
PEAK_BF16_FLOPS = (
    ("TPU v6", 918e12),
    ("TPU v5 lite", 197e12),
    ("TPU v5e", 197e12),
    ("TPU v5p", 459e12),
    ("TPU v5", 459e12),
    ("TPU v4", 275e12),
)


def peak_flops(kind: Optional[str]) -> Optional[float]:
    """Peak bf16 FLOPs/sec for a jax ``device_kind``.  None on the CPU
    (``"cpu"`` or no device: MFU then reports 0.0 rather than a made-up
    number).  An accelerator that is not in the table raises — an MFU
    silently reading 0.0 on a chip is a wrong number, not a missing one;
    ``DTM_PEAK_FLOPS`` supplies the peak for an unlisted accelerator."""
    env = os.environ.get("DTM_PEAK_FLOPS")
    if env:
        return float(env)
    if not kind or kind.lower() == "cpu":
        return None
    for prefix, peak in PEAK_BF16_FLOPS:
        if kind.startswith(prefix):
            return peak
    raise ValueError(
        f"no peak FLOP/s known for device_kind {kind!r}: add it to "
        "telemetry.goodput.PEAK_BF16_FLOPS (with its source) or set "
        "DTM_PEAK_FLOPS"
    )


def device_kind() -> Optional[str]:
    """The local backend's device kind, or None if jax is unavailable or
    not yet initialized (telemetry must never be the thing that crashes)."""
    try:
        import jax

        return jax.devices()[0].device_kind
    except Exception:  # noqa: BLE001 — report generation must not raise
        return None


def device_count() -> int:
    """Global participating-device count (1 when jax is unavailable).
    The MFU denominator must scale by this: cost analysis is of the
    *global* SPMD program, so the peak must be the whole mesh's."""
    try:
        import jax

        return max(len(jax.devices()), 1)
    except Exception:  # noqa: BLE001
        return 1


def goodput_report(
    registry: reglib.MetricsRegistry,
    total_s: float,
    steps: int,
    kind: Optional[str] = None,
    n_devices: Optional[int] = None,
) -> dict:
    """Build the ``telemetry.json`` payload from a registry snapshot.

    ``total_s`` is the run's full wall time (fit entry to report time);
    ``steps`` the steps executed by this invocation.  If attributed time
    exceeds ``total_s`` (clock skew between span endpoints), the total is
    raised to the attributed sum so no fraction goes negative and the four
    still sum to 1.0.
    """
    snap = registry.snapshot()

    def total(name: str) -> float:
        return snap.get(f"{name}/total_s", 0.0)

    data_stall = total(reglib.DATA_WAIT)
    checkpoint = (
        total(reglib.CKPT_SAVE)
        + total(reglib.CKPT_RESTORE)
        + total(reglib.CKPT_WAIT)
        + total(reglib.CKPT_FENCE)
    )
    compile_s = total(reglib.COMPILE)
    attributed = data_stall + checkpoint + compile_s
    total_s = max(float(total_s), attributed, 1e-9)
    compute = total_s - attributed

    kind = kind if kind is not None else device_kind()
    n_devices = n_devices if n_devices is not None else device_count()
    peak = peak_flops(kind)
    flops_per_step = snap.get(reglib.FLOPS_PER_STEP, 0.0)
    # Retired-FLOPs counter (signature-exact under mixed batch shapes);
    # gauge × steps is the fallback for registries populated without
    # per-step accumulation.  Both are GLOBAL-program FLOPs, so the peak
    # is the whole mesh's: per-chip peak × device count.
    flops_total = snap.get(reglib.FLOPS_TOTAL, 0.0) or (
        flops_per_step * steps
    )
    mfu = (
        flops_total / (total_s * peak * n_devices)
        if peak and flops_total
        else 0.0
    )
    return {
        "total_s": round(total_s, 6),
        "steps": int(steps),
        "steps_per_sec": round(steps / total_s, 6),
        "seconds": {
            "compute": round(compute, 6),
            "data_stall": round(data_stall, 6),
            "checkpoint": round(checkpoint, 6),
            "compile": round(compile_s, 6),
        },
        "fractions": {
            "compute": compute / total_s,
            "data_stall": data_stall / total_s,
            "checkpoint": checkpoint / total_s,
            "compile": compile_s / total_s,
        },
        "compile_events": int(snap.get(f"{reglib.COMPILE}/count", 0.0)),
        # The start-up timeline, reported beside the exclusive four-way
        # split, never summed into it: every start-up gauge and counter
        # under its name less the prefix.
        "startup": {
            key.split("/", 1)[1]: snap.get(key, 0.0)
            for key in (*reglib.STARTUP_GAUGES, *reglib.STARTUP_COUNTERS)
        },
        "flops_per_step": flops_per_step,
        "flops_total": flops_total,
        "device_kind": kind,
        "n_devices": n_devices,
        "peak_bf16_flops": peak,  # per chip
        "mfu": round(mfu, 6),
        # The raw snapshot rides along: every timer's p50/p95/max for the
        # stall post-mortem (which pipeline stage, how bad at the tail).
        "metrics": snap,
    }


def write_report(path: str, report: dict) -> None:
    """Atomic (tmp + rename) JSON dump — a reader tailing the workdir
    never sees a half-written report."""
    tmp = f"{path}.tmp"
    with open(tmp, "w") as f:
        json.dump(report, f, indent=1)
    os.replace(tmp, path)
