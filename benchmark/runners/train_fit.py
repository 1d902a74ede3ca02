"""Runner ``train_fit``: a training job through ``harness/train.py::fit``.

The traffic file gives the job (``fit.overrides`` on top of the
configuration's own, warm-up steps, the steps to trace); the runner
hands ``fit`` a fresh work directory, a data-parallel mesh over the
cell's chips and the timing hook (``lib/window_hook.py``), and reads
what ``fit`` leaves behind: ``metrics.jsonl`` (loss rows and
``TelemetryHook``'s interval readings), ``telemetry.json`` and, in a
traced run, the program's own span export.

Checkpointing is set so that no save falls inside the window; ``fit``'s
final save cannot be switched off by configuration and lands after
``t1``.
"""

from __future__ import annotations

import json
import math
import os
import time

from benchmark.lib import cells, device, stats, trace_reduce
from benchmark.lib.result import RunOptions, RunResult
from benchmark.lib.window_hook import MARKER_IDLE_S, SYNC_MARKER, make_window_hook

# No save and no stop by step count inside a run.
_NEVER_STEPS = 1_000_000_000
_NEVER_SECS = 1.0e9


def build_config(cell, opts: RunOptions):
    """The program's ``ExperimentConfig`` for this cell: the named
    program config, the configuration file's overrides, the traffic
    file's, and what the benchmark itself needs (seed, no save inside
    the window, span export in a traced run)."""
    from distributed_tensorflow_models_tpu.harness.config import get_config

    job = cell.traffic["fit"]
    overrides = cells.deep_merge(
        cell.config.get("overrides", {}), job.get("overrides", {})
    )
    per_chip = job.get("per_chip_batch")
    if per_chip is not None and "global_batch_size" not in overrides:
        overrides["global_batch_size"] = int(per_chip) * len(opts.devices)
    overrides.update(
        seed=opts.seed,
        train_steps=_NEVER_STEPS,
        checkpoint_every_secs=_NEVER_SECS,
        checkpoint_every_steps=None,
        trace_export=bool(opts.trace),
    )
    return get_config(cell.config["program_config"], **overrides)


def _rows(workdir: str) -> list:
    path = os.path.join(workdir, "metrics.jsonl")
    if not os.path.isfile(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _program_spans(workdir: str) -> list:
    """The program's ``Tracer`` export as ``(name, start_s, end_s)`` on
    the wall clock."""
    path = os.path.join(workdir, "trace_p0.json")
    if not os.path.isfile(path):
        return []
    events = cells.read_json(path)["traceEvents"]
    return [
        (e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
        for e in events
        if e.get("ph") == "X"
    ]


def _replica_checksums(state) -> dict:
    """The smallest parameter leaf, summed on every device that holds it."""
    import jax
    import numpy as np

    leaf = min(jax.tree.leaves(state.params), key=lambda x: x.size)
    sums = [
        float(np.asarray(shard.data, np.float64).sum())
        for shard in leaf.addressable_shards
    ]
    return {
        "devices": len(leaf.sharding.device_set),
        "replicated": bool(leaf.sharding.is_fully_replicated),
        "sums": sums,
    }


def run(cell, opts: RunOptions) -> RunResult:
    import jax

    from distributed_tensorflow_models_tpu.core import mesh as meshlib
    from distributed_tensorflow_models_tpu.harness import train as trainlib

    job = cell.traffic["fit"]
    trace_steps = int(job["trace_steps"])
    cfg = build_config(cell, opts)
    items_per_step = cfg.global_batch_size * (
        cfg.num_steps if cfg.task == "lm" else 1
    )
    mesh = meshlib.data_parallel_mesh(opts.devices)
    trace_dir = os.path.join(opts.workdir, "profile")
    seen: dict = {}

    def on_open(state):
        seen["compiles_t0"] = opts.compiles.total()
        seen["setup_s"] = opts.since_start()

    def on_close(state):
        seen["compiles_t1"] = opts.compiles.total()
        seen["replicas"] = _replica_checksums(state)
        seen["temp_bytes"] = device.program_temp_bytes(opts.devices[0].client)

    hook = make_window_hook(
        warmup_steps=int(job["warmup_steps"]),
        seconds=opts.seconds,
        check_every=max(1, int(cfg.steps_per_loop)),
        trace_steps=trace_steps if opts.trace else 0,
        settle_steps=int(job["settle_steps"]),
        trace_dir=trace_dir,
        on_open=on_open,
        on_close=on_close,
    )
    fit_dir = os.path.join(opts.workdir, "fit")
    t_fit = time.perf_counter()
    result = trainlib.fit(cfg, fit_dir, mesh=mesh, extra_hooks=[hook])
    t_fit_done = time.perf_counter()

    rows = _rows(fit_dir)
    counters = cells.read_json(os.path.join(fit_dir, "telemetry.json"))["metrics"]
    losses = [(int(r["step"]), float(r["loss"])) for r in rows if "loss" in r]
    after_warmup = [v for s, v in losses if s >= hook.step0]
    replicas = seen.get("replicas", {})
    steps_run = int(result.state.step)
    expected_steps = hook.trace_step1 if opts.trace and trace_steps > 0 else hook.step1
    checks = {
        "loss_rows_finite": bool(losses) and all(math.isfinite(v) for _, v in losses),
        "loss_fell": bool(after_warmup) and bool(losses) and after_warmup[-1] < losses[0][1],
        "steps_counted_equal_steps_run": steps_run == expected_steps and hook.steps > 0,
        "no_restart_or_rollback": (
            counters.get("train/restarts", 0) == 0
            and counters.get("train/rollbacks", 0) == 0
            and not result.preempted
        ),
        "no_compile_in_window": seen["compiles_t1"] == seen["compiles_t0"],
        "replicas_agree": (
            replicas.get("devices") == len(opts.devices)
            and replicas.get("replicated", False)
            and len(set(replicas.get("sums", [None]))) == 1
        ),
    }
    items_per_s = stats.rate(hook.steps * items_per_step, hook.t0, hook.t1)

    # Interval readings of TelemetryHook whose whole interval lies in
    # the window: rows at log cadence, each covering the steps since
    # the row before.
    window_rows = []
    prev_step = None
    for r in rows:
        step = int(r["step"])
        if prev_step is not None and prev_step >= hook.step0 and step <= hook.step1:
            window_rows.append({**r, "interval_steps": step - prev_step})
        prev_step = step

    trace = None
    if opts.trace and trace_steps > 0:
        trace = trace_reduce.reduce_trace(
            trace_dir,
            marker=SYNC_MARKER,
            marker_stamp_s=hook.marker_wall,
            program_spans=_program_spans(fit_dir),
            clip_after_marker_s=MARKER_IDLE_S / 2,
        )
        if trace is not None:
            trace["steps"] = hook.trace_step1 - hook.trace_step0
            trace["host_window_s"] = hook.trace_t1 - hook.trace_t0
            checks["device_ran_in_trace"] = trace["busy_s"] > 0
            if len(opts.devices) > 1:
                checks["all_reduce_on_device"] = trace["collective_ops"] > 0
        else:
            checks["device_ran_in_trace"] = False

    flops_step = float(counters.get("train/flops_per_step", 0.0))
    ctx = {
        "chips": len(opts.devices),
        "device_kind": opts.devices[0].device_kind,
        "window_s": hook.window_s,
        "steps": hook.steps,
        "items_per_step": items_per_step,
        "items_per_s": items_per_s,
        "window_rows": window_rows,
        "counters": counters,
        "compiles_in_window": seen["compiles_t1"] - seen["compiles_t0"],
        "config": cell.config,
        "trace": trace,
        "program_temp_bytes": seen.get("temp_bytes", 0),
    }
    notes = {
        "steps_in_window": hook.steps,
        "window_s": hook.window_s,
        "items_per_step": items_per_step,
        "loss_first": losses[0][1] if losses else None,
        "loss_last": losses[-1][1] if losses else None,
        "program_flops_per_item": flops_step / items_per_step if flops_step else None,
        "time_to_first_step_s": counters.get("startup/time_to_first_step_s"),
        "replica_checksums": replicas.get("sums"),
        # After the window: the traced sub-window (if any), fit's final
        # save and teardown; then the benchmark's own reading of the trace.
        "after_window_in_fit_s": t_fit_done - t_fit - (hook.t1 - t_fit),
        "reduce_s": time.perf_counter() - t_fit_done,
    }
    if trace is not None:
        notes["traced_items_per_s"] = (
            trace["steps"] * items_per_step / trace["host_window_s"]
        )
        # The host clock around block_until_ready against the device's
        # own clock, per step of the traced sub-window.
        notes["host_step_ms_in_trace"] = 1e3 * trace["host_window_s"] / trace["steps"]
        notes["trace_window_step_ms"] = 1e3 * trace["window_s"] / trace["steps"]
    return RunResult(
        checks=checks,
        attempted=hook.steps,
        failed=0 if checks["loss_rows_finite"] else hook.steps,
        end_to_end={
            # ``train_images_per_s`` or ``train_tokens_per_s``: the
            # configuration's file says which items the model consumes.
            f"train_{cell.config['items']}_per_s": items_per_s,
            "setup_s": seen["setup_s"],
        },
        ctx=ctx,
        notes=notes,
    )
