"""Telemetry subsystem: registry semantics, hot-loop overhead guard,
instrumented-step compile accounting, pipeline instrumentation, the
TelemetryHook injection/aggregation, the goodput report, and the
end-to-end smoke run whose artifacts the schema lint validates."""

import json
import math
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu import telemetry
from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.harness import (
    config as configlib,
    hooks as hooklib,
    train as trainlib,
)

SCHEMA_LINT = os.path.join(
    os.path.dirname(__file__), "..", "scripts", "check_metrics_schema.py"
)


# --------------------------------------------------------------------------
# Registry
# --------------------------------------------------------------------------


def test_counter_gauge_timer_snapshot():
    reg = telemetry.MetricsRegistry()
    reg.counter("events").inc()
    reg.counter("events").inc(2.5)
    reg.gauge("depth").set(3)
    t = reg.timer("lap")
    for v in (0.1, 0.2, 0.3, 0.4):
        t.record(v)
    snap = reg.snapshot()
    assert snap["events"] == 3.5
    assert snap["depth"] == 3.0
    assert snap["lap/count"] == 4
    assert snap["lap/total_s"] == pytest.approx(1.0)
    assert snap["lap/mean_s"] == pytest.approx(0.25)
    assert snap["lap/max_s"] == pytest.approx(0.4)
    assert snap["lap/p50_s"] == pytest.approx(0.3)  # nearest-rank
    assert snap["lap/p95_s"] == pytest.approx(0.4)
    assert snap["lap/p99_s"] == pytest.approx(0.4)


def test_timer_reservoir_ages_out_old_samples():
    t = telemetry.Timer()
    for _ in range(telemetry.Timer.RESERVOIR):
        t.record(100.0)  # warmup-era outliers
    for _ in range(telemetry.Timer.RESERVOIR):
        t.record(0.001)  # steady state overwrites the ring
    (p95,) = t.percentiles(0.95)
    assert p95 == pytest.approx(0.001)  # outliers aged out of p95...
    assert t.max == 100.0  # ...but the all-time max survives


def test_span_records_on_error_too():
    reg = telemetry.MetricsRegistry()
    with pytest.raises(ValueError):
        with reg.span("work"):
            raise ValueError("boom")
    assert reg.snapshot()["work/count"] == 1


def test_registries_are_isolated():
    a, b = telemetry.MetricsRegistry(), telemetry.MetricsRegistry()
    a.counter("x").inc()
    assert "x" not in b.snapshot()
    assert telemetry.get_registry() is telemetry.get_registry()


# --------------------------------------------------------------------------
# Overhead guard (tier-1 CI): per-step telemetry cost on a hot loop
# --------------------------------------------------------------------------


def test_hot_loop_overhead_under_5us_per_step():
    """The full per-step recording set (one timer record, one counter inc,
    one gauge set) plus a snapshot every 100 steps — the real cadence —
    must amortize under 5 µs/step on CPU, or telemetry would tax the very
    step time it measures.  Tracing rides the SAME budget: the loop runs
    with a live tracer at the default ring size and adds the per-step
    trace events fit's hot path produces — the data-wait span's trace
    mirror (what ``registry.span`` emits beyond the timer record already
    counted here) and the per-chunk ``train/chunk`` complete event — so
    the flight recorder cannot quietly re-tax the step path.  Since PR 23
    the set also holds what that PR added to the loop's thread, as
    ``record_since`` records it: the hook walk (``train/hooks``) and the
    batch's placement (``pipeline/shard`` with ``pipeline/bytes``).  A
    loop this hot has sub-millisecond walks and batches, which land in
    the timers and not in the ring (from a millisecond up each adds a
    ring event, under 0.1% of it).  ``pipeline/assemble`` is the
    producer thread's, like ``pipeline/producer_wait``."""
    reg = telemetry.MetricsRegistry()
    reg.trace = telemetry.Tracer(
        capacity=configlib.ExperimentConfig.trace_ring_events
    )
    t = reg.timer(telemetry.STEP_TIME)
    c = reg.counter("steps")
    g = reg.gauge(telemetry.HOST_QUEUE_DEPTH)
    placed = reg.counter(telemetry.PIPELINE_BYTES)
    # Populate a realistic snapshot surface first.
    for name in (telemetry.DATA_WAIT, telemetry.DISPATCH,
                 telemetry.PREFETCH_FILL, telemetry.CKPT_SAVE):
        reg.timer(name).record(0.01)
    N = 20_000
    best = float("inf")
    for _ in range(5):  # best-of-5 shields against CI scheduler noise
        t0 = time.perf_counter()
        for i in range(N):
            t.record(1e-4)
            c.inc()
            g.set(i & 7)
            # The span's trace-emission increment (its timer record is
            # the t.record above) + the per-chunk event, args included.
            reg.trace.complete(telemetry.DATA_WAIT, 1e-4)
            reg.trace.complete(
                "train/chunk", 1e-4, args={"start": i, "k": 1}
            )
            now = time.perf_counter()
            reg.record_since(telemetry.HOOKS, now)
            reg.record_since(telemetry.SHARD, now, {"bytes": 1 << 20})
            placed.inc(1 << 20)
            if i % 100 == 0:
                reg.snapshot()
        best = min(best, (time.perf_counter() - t0) / N)
    # Every site really traced (plus a work record the scheduler held up
    # past the millisecond, now and then).
    assert 0 <= reg.trace.emitted - 5 * 2 * N < 10
    assert reg.timer(telemetry.SHARD).count == 5 * N
    assert best < 5e-6, f"telemetry hot-loop cost {best*1e6:.2f} µs/step"


# --------------------------------------------------------------------------
# InstrumentedStep: compile events + FLOPs
# --------------------------------------------------------------------------


def test_instrumented_step_counts_compiles_and_flops():
    reg = telemetry.MetricsRegistry()
    jitted = jax.jit(
        lambda s, b, r: (s + b["x"].sum(), {"loss": b["x"].sum()})
    )
    istep = train_loop.InstrumentedStep(jitted, registry=reg)
    s = jnp.float32(0.0)
    rng = jax.random.key(0)
    for _ in range(3):
        s, m = istep(s, {"x": jnp.ones((64, 64))}, rng)
    snap = reg.snapshot()
    assert snap[f"{telemetry.COMPILE}/count"] == 1  # same signature: cached
    # First call compiled (recorded as a compile event, not a dispatch);
    # the two cache hits are dispatches.
    assert snap[f"{telemetry.DISPATCH}/count"] == 2
    assert snap[f"{telemetry.COMPILE}/total_s"] > 0
    # XLA cost analysis is available on CPU: the FLOPs gauge must be live.
    assert snap[telemetry.FLOPS_PER_STEP] > 0
    assert istep.flops_per_step == snap[telemetry.FLOPS_PER_STEP]

    # New batch signature -> a recorded recompile event.
    s2, _ = istep(jnp.float32(0.0), {"x": jnp.ones((32, 32))}, rng)
    assert reg.snapshot()[f"{telemetry.COMPILE}/count"] == 2
    assert float(s2) == pytest.approx(32 * 32)


def test_instrumented_step_flops_total_weights_mixed_signatures():
    """A ragged (smaller) batch must add *its own* program's FLOPs to the
    retired-FLOPs counter, not re-price the whole run (the MFU numerator
    is the counter, never gauge x steps)."""
    reg = telemetry.MetricsRegistry()
    jitted = jax.jit(lambda s, b, r: (s, {"loss": (b["x"] @ b["x"]).sum()}))
    istep = train_loop.InstrumentedStep(jitted, registry=reg)
    full = {"x": jnp.ones((64, 64))}
    ragged = {"x": jnp.ones((16, 16))}
    istep(0.0, full, None)
    f_full = reg.snapshot()[telemetry.FLOPS_TOTAL]
    assert f_full > 0
    istep(0.0, full, None)
    assert reg.snapshot()[telemetry.FLOPS_TOTAL] == pytest.approx(2 * f_full)
    istep(0.0, ragged, None)
    f_ragged = reg.snapshot()[telemetry.FLOPS_TOTAL] - 2 * f_full
    assert 0 < f_ragged < f_full  # priced at the small program's cost
    istep(0.0, full, None)  # back to the full program: full price again
    assert reg.snapshot()[telemetry.FLOPS_TOTAL] == pytest.approx(
        3 * f_full + f_ragged
    )


def test_instrumented_step_falls_back_on_plain_callable():
    """A non-jitted step (no .lower, no compile cache) must still run;
    FLOPs/compile accounting degrades to nothing, dispatch still ticks."""
    reg = telemetry.MetricsRegistry()
    istep = train_loop.InstrumentedStep(
        lambda s, b, r: (s + 1, {"loss": 0.0}), registry=reg
    )
    s, _ = istep(0, {"x": np.ones((2,))}, None)
    s, _ = istep(s, {"x": np.ones((2,))}, None)
    assert s == 2
    snap = reg.snapshot()
    assert snap[f"{telemetry.DISPATCH}/count"] == 2
    assert snap.get(f"{telemetry.COMPILE}/count", 0.0) == 0


def test_instrumented_step_tolerates_resharded_state(mesh8):
    """The TP-resume regression guard: a state resharded between calls
    (as checkpoint restore + place_state produces) must run through the
    wrapper — plain-jit resharding semantics, with the recompile showing
    up as a second compile event."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    reg = telemetry.MetricsRegistry()
    jitted = jax.jit(lambda s, b, r: (s * 1.0 + b["x"].sum(), {}))
    istep = train_loop.InstrumentedStep(jitted, registry=reg)
    batch = {"x": jnp.ones((8,))}
    s = jax.device_put(
        jnp.zeros((8, 4)), NamedSharding(mesh8, P("data", None))
    )
    s, _ = istep(s, batch, None)
    # Re-lay the carry out differently (replicated), as a restore would.
    s = jax.device_put(np.asarray(s), NamedSharding(mesh8, P()))
    s, _ = istep(s, batch, None)
    assert reg.snapshot()[f"{telemetry.COMPILE}/count"] == 2


# --------------------------------------------------------------------------
# Pipeline instrumentation
# --------------------------------------------------------------------------


def test_pipeline_records_waits_and_depths(mesh8):
    from distributed_tensorflow_models_tpu.data import datasets, pipeline

    reg = telemetry.MetricsRegistry()
    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    y = np.arange(64, dtype=np.int32)
    ds = datasets.ArrayDataset({"image": x, "label": y}, 8, seed=0)
    host = pipeline.HostPipeline(ds, prefetch=2, registry=reg)
    pre = pipeline.DevicePrefetcher(host, mesh8, depth=2, registry=reg)
    for _ in range(3):
        next(pre)
    snap = reg.snapshot()
    # Prefetcher pulled >= depth + consumed batches from upstream.
    assert snap[f"{telemetry.PREFETCH_FILL}/count"] >= 3
    # The producer thread recorded put waits and the queue depth gauge.
    assert snap[f"{telemetry.PRODUCER_WAIT}/count"] >= 1
    assert telemetry.HOST_QUEUE_DEPTH in snap
    host.stop()


# --------------------------------------------------------------------------
# TelemetryHook
# --------------------------------------------------------------------------


class _FakeState:
    step = jnp.asarray(0)


def test_telemetry_hook_injects_at_cadence_only():
    reg = telemetry.MetricsRegistry()
    h = hooklib.TelemetryHook(reg, every_steps=2)
    h.begin(_FakeState())
    reg.timer(telemetry.STEP_TIME).record(0.02)
    reg.timer(telemetry.DATA_WAIT).record(0.01)
    metrics = {"loss": 1.0}
    h.after_step(_FakeState(), metrics, 1)
    assert "data_wait_s" not in metrics  # off-cadence: untouched
    h.after_step(_FakeState(), metrics, 2)
    for key in ("data_wait_s", "step_time_s", "mfu", "steps_per_sec",
                "stall_fraction", "compile_count", "checkpoint_s"):
        assert key in metrics, key
    assert metrics["step_time_s"] == pytest.approx(0.02)
    assert metrics["data_wait_s"] == pytest.approx(0.01 / 2)


def test_telemetry_hook_interval_deltas_reset():
    """Second firing must report the new interval, not cumulative sums."""
    reg = telemetry.MetricsRegistry()
    h = hooklib.TelemetryHook(reg, every_steps=1)
    h.begin(_FakeState())
    reg.timer(telemetry.STEP_TIME).record(0.5)
    m1 = {}
    h.after_step(_FakeState(), m1, 1)
    reg.timer(telemetry.STEP_TIME).record(0.1)
    m2 = {}
    h.after_step(_FakeState(), m2, 2)
    assert m1["step_time_s"] == pytest.approx(0.5)
    assert m2["step_time_s"] == pytest.approx(0.1)


def test_telemetry_hook_multihost_aggregation(monkeypatch):
    """Chief-side cross-host view: allgathered steps/sec + stall fraction
    (process_allgather monkeypatched — no real cluster in CI)."""
    from jax.experimental import multihost_utils

    def fake_allgather(arr):
        return np.stack([arr, arr * 3.0])  # "other host" is 3x

    monkeypatch.setattr(
        multihost_utils, "process_allgather", fake_allgather
    )
    reg = telemetry.MetricsRegistry()
    h = hooklib.TelemetryHook(reg, every_steps=1, process_count=2)
    h.begin(_FakeState())
    reg.timer(telemetry.DATA_WAIT).record(0.001)
    metrics = {}
    h.after_step(_FakeState(), metrics, 1)
    assert metrics["hosts/steps_per_sec_mean"] == pytest.approx(
        2.0 * metrics["hosts/steps_per_sec_min"]
    )
    assert metrics["hosts/stall_fraction_max"] == pytest.approx(
        3.0 * metrics["stall_fraction"], rel=1e-5
    )


# --------------------------------------------------------------------------
# Goodput report
# --------------------------------------------------------------------------


def test_goodput_report_fractions_sum_to_one(tmp_path):
    reg = telemetry.MetricsRegistry()
    reg.timer(telemetry.DATA_WAIT).record(0.2)
    reg.timer(telemetry.CKPT_SAVE).record(0.05)
    reg.timer(telemetry.CKPT_WAIT).record(0.05)
    reg.timer(telemetry.COMPILE).record(0.3)
    rep = telemetry.goodput_report(reg, total_s=1.0, steps=10, kind="CPU")
    f = rep["fractions"]
    assert sum(f.values()) == pytest.approx(1.0)
    assert f["data_stall"] == pytest.approx(0.2)
    assert f["checkpoint"] == pytest.approx(0.1)
    assert f["compile"] == pytest.approx(0.3)
    assert f["compute"] == pytest.approx(0.4)
    assert rep["steps"] == 10 and rep["compile_events"] == 1
    assert rep["mfu"] == 0.0  # no peak table entry for CPU

    path = str(tmp_path / "telemetry.json")
    telemetry.write_report(path, rep)
    assert json.load(open(path))["fractions"]["compute"] == pytest.approx(0.4)


def test_goodput_report_clamps_overattribution():
    """Attributed > total (span clock skew) must not yield negative
    compute or fractions summing past 1."""
    reg = telemetry.MetricsRegistry()
    reg.timer(telemetry.DATA_WAIT).record(2.0)
    rep = telemetry.goodput_report(reg, total_s=1.0, steps=1, kind=None)
    assert rep["fractions"]["compute"] == 0.0
    assert sum(rep["fractions"].values()) == pytest.approx(1.0)


def test_mfu_scales_by_device_count():
    """The FLOPs numerator is the GLOBAL program's cost, so MFU must
    divide by per-chip peak x mesh size — not report >100% on any
    multi-chip mesh."""
    reg = telemetry.MetricsRegistry()
    reg.counter(telemetry.FLOPS_TOTAL).inc(197e12)  # one chip-second of v5e
    rep1 = telemetry.goodput_report(
        reg, total_s=1.0, steps=1, kind="TPU v5e", n_devices=1
    )
    rep4 = telemetry.goodput_report(
        reg, total_s=1.0, steps=1, kind="TPU v5e", n_devices=4
    )
    assert rep1["mfu"] == pytest.approx(1.0)
    assert rep4["mfu"] == pytest.approx(0.25)
    assert rep4["n_devices"] == 4


def test_peak_flops_lookup(monkeypatch):
    assert telemetry.peak_flops("TPU v5e") == 197e12
    assert telemetry.peak_flops("TPU v4 lite") == 275e12
    assert telemetry.peak_flops("TPU v5 lite") == 197e12  # a v5e's kind
    assert telemetry.peak_flops("cpu") is None
    assert telemetry.peak_flops(None) is None
    # An accelerator the table does not list is an error, not mfu 0.0.
    with pytest.raises(ValueError, match="TPU v9"):
        telemetry.peak_flops("TPU v9")
    monkeypatch.setenv("DTM_PEAK_FLOPS", "1e12")
    assert telemetry.peak_flops("anything") == 1e12


# --------------------------------------------------------------------------
# End-to-end smoke (the ISSUE acceptance run) + schema lint wiring
# --------------------------------------------------------------------------


@pytest.mark.slow
def test_smoke_train_produces_telemetry_artifacts(mesh8, tmp_path):
    """LeNet ~50 CPU steps: telemetry.json fractions sum to ~1.0, and
    metrics.jsonl carries data_wait_s / step_time_s / mfu at the logging
    cadence; the schema lint passes with --require-telemetry."""
    cfg = configlib.get_config(
        "lenet_mnist",
        train_steps=50,
        global_batch_size=32,
        log_every_steps=10,
        checkpoint_every_secs=10_000.0,
        trace_export=True,
    )
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)

    report = json.load(open(tmp_path / "telemetry.json"))
    f = report["fractions"]
    assert set(f) == {"compute", "data_stall", "checkpoint", "compile"}
    assert sum(f.values()) == pytest.approx(1.0, abs=1e-6)
    assert all(v >= 0 for v in f.values())
    assert report["steps"] == 50
    assert report["compile_events"] >= 1
    assert report["seconds"]["compile"] > 0
    assert report["seconds"]["checkpoint"] > 0  # CheckpointHook.end saved
    assert report["flops_per_step"] > 0  # XLA cost analysis on CPU
    assert math.isfinite(report["steps_per_sec"])
    # The start-up timeline's schema, pinned: the whole set, in order
    # (the coverage lint below also checks that its phases add up).
    assert list(report["startup"]) == [
        "process_to_fit_s", "build_state_s", "build_step_s", "restore_s",
        "dataset_s", "pipeline_open_s", "first_chunk_s", "aot_join_s",
        "first_data_wait_s", "unattributed_s", "time_to_first_step_s",
        "first_loss_row_s", "aot_lower_s", "aot_compile_s",
        "modules_at_fit", "cloud_logging_imported",
        "compile_requests", "cache_hits",
    ]

    rows = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    telem_rows = [r for r in rows if "data_wait_s" in r]
    assert [r["step"] for r in telem_rows] == [10, 20, 30, 40, 50]
    for r in telem_rows:
        for key in ("data_wait_s", "step_time_s", "mfu", "steps_per_sec",
                    "stall_fraction", "compile_count"):
            assert key in r, key
        assert r["step_time_s"] > 0
        assert r["loss"] > 0  # device metrics share the row

    # The CI lint is the same script an operator runs by hand.
    proc = subprocess.run(
        [sys.executable, SCHEMA_LINT, str(tmp_path / "metrics.jsonl"),
         "--require-telemetry"],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stderr

    # Declared-vs-emitted coverage: every key constant in the telemetry
    # registry must show up in this run's snapshot, except the
    # explicitly feature/topology-gated ones (no chaos, no fleet
    # supervisor, no sharded workers, no restore, no watchdog, no
    # serving traffic here).  serve/ is NOT a blanket hole in coverage:
    # test_serving runs --declared-coverage --only-prefix serve/
    # against a served-traffic serving_stats report, so together the
    # two checks tile the whole registry.
    registry_py = os.path.join(
        os.path.dirname(SCHEMA_LINT), "..",
        "distributed_tensorflow_models_tpu", "telemetry", "registry.py",
    )
    proc = subprocess.run(
        [sys.executable, SCHEMA_LINT, str(tmp_path / "telemetry.json"),
         "--declared-coverage", registry_py,
         "--allow-missing", "chaos/",
         "--allow-missing", "fleet/",
         "--allow-missing", "checkpoint/restore",
         "--allow-missing", "pipeline/reassembly_wait",
         "--allow-missing", "pipeline/worker_busy",
         "--allow-missing", "train/watchdog_last_progress_s",
         "--allow-missing", "serve/"],
        capture_output=True,
        text=True,
    )
    assert proc.returncode == 0, proc.stderr + proc.stdout

    # Event tracing (default ring) leaves its accounting in the report
    # and — with trace_export on — a Perfetto-loadable per-process
    # trace; a CLEAN exit leaves no flight-recorder dump.
    snap = report["metrics"]
    assert snap["trace/events"] > 0
    assert snap["trace/dropped"] >= 0
    trace = json.load(open(tmp_path / "trace_p0.json"))
    names = {e["name"] for e in trace["traceEvents"]}
    for expected in ("fit/entry", "fit/end", "train/chunk",
                     "train/compile", "checkpoint/save"):
        assert expected in names, expected
    assert not os.path.exists(tmp_path / "flight_recorder_p0.json")


def _schema_lint():
    from importlib import util as importutil

    spec = importutil.spec_from_file_location("check_metrics_schema",
                                             SCHEMA_LINT)
    mod = importutil.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_schema_lint_catches_violations(tmp_path):
    mod = _schema_lint()

    good = [json.dumps({"step": 1, "time": 1.0, "loss": 0.5}),
            json.dumps({"step": 2, "time": 2.0, "loss": 0.4,
                        "data_wait_s": 0.0, "step_time_s": 0.01,
                        "mfu": 0.0})]
    errors, rows, trows = mod.check_lines(good)
    assert not errors and rows == 2 and trows == 1

    bad = [
        "not json",
        json.dumps({"time": 1.0}),  # missing step
        json.dumps({"step": 5, "time": 1.0}),
        json.dumps({"step": 3, "time": 1.0}),  # step regression
        json.dumps({"step": 6, "time": 1.0, "tag": "oops"}),  # non-number
        json.dumps({"step": 7, "time": 1.0, "mfu": 0.1}),  # partial telem
    ]
    # Default: the regression is tolerated (recoverable_fit restarts
    # legitimately rewind the step); --strict-monotonic flags it.
    errors, _, _ = mod.check_lines(bad)
    assert len(errors) == 4
    errors, _, _ = mod.check_lines(bad, strict_monotonic=True)
    assert len(errors) == 5
    # CLI exit codes: 1 on violations, 0 on a clean file.
    p = tmp_path / "bad.jsonl"
    p.write_text("\n".join(bad) + "\n")
    assert mod.main([str(p)]) == 1
    p2 = tmp_path / "good.jsonl"
    p2.write_text("\n".join(good) + "\n")
    assert mod.main([str(p2), "--require-telemetry"]) == 0


_MOE_ROW = {"step": 1, "time": 1.0, "moe_load_max_over_mean": 2.0, "moe_aux_loss": 1.0, "moe_z_loss": 0.5}


@pytest.mark.parametrize(
    "more,errors",
    [
        ({"moe_held_share": 0.03, "moe_held_slabs": 1.25}, 0),
        ({"moe_held_share": 0.03}, 0),  # a row from before the slabs were reported
        ({"moe_held_slabs": 1.0}, 1),  # never without the share
        ({"moe_held_share": 0.03, "moe_held_slabs": 0.5}, 1),  # a layer walks one slab at least
    ],
    ids=["both", "share_alone", "slabs_alone", "under_one_slab"],
)
def test_schema_lint_holds_the_held_experts_keys_together(more, errors):
    found, _, _ = _schema_lint().check_lines([json.dumps({**_MOE_ROW, **more})])
    assert len(found) == errors, found
