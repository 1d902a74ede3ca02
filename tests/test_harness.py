"""Harness tests: configs, hooks, checkpoint round-trip, fit with
auto-resume (the reference's recovery semantics, SURVEY.md §5.3-5.4), and
eval drivers."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.harness import (
    checkpoint as ckptlib,
    config as configlib,
    evaluate as evallib,
    hooks as hooklib,
    train as trainlib,
)
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.ops import optim


# --------------------------------------------------------------------------
# Configs
# --------------------------------------------------------------------------


def test_config_registry_complete():
    names = configlib.list_configs()
    # The BASELINE.json config list, one entry each [B:6-12].
    for required in (
        "lenet_mnist",
        "resnet32_cifar10",
        "inception_v3_imagenet",
        "resnet50_imagenet",
        "ptb_small",
        "ptb_medium",
        "ptb_large",
    ):
        assert required in names


def test_config_optimizers_build():
    for name in configlib.list_configs():
        cfg = configlib.get_config(name)
        tx = cfg.optimizer.make()
        params = {"w": jnp.ones((3,))}
        opt_state = tx.init(params)
        updates, _ = tx.update({"w": jnp.ones((3,))}, opt_state, params)
        assert jnp.all(jnp.isfinite(updates["w"]))


def test_config_overrides():
    cfg = configlib.get_config("lenet_mnist", train_steps=7, seed=3)
    assert cfg.train_steps == 7 and cfg.seed == 3
    with pytest.raises(KeyError):
        configlib.get_config("nope")


# --------------------------------------------------------------------------
# Hooks
# --------------------------------------------------------------------------


class _FakeState:
    step = jnp.asarray(0)


def test_stop_at_step_hook():
    hooks = [hooklib.StopAtStepHook(5)]
    assert hooklib.run_hooks_after_step(hooks, _FakeState(), {}, 4)
    assert not hooklib.run_hooks_after_step(hooks, _FakeState(), {}, 5)


def test_nan_guard_hook():
    h = hooklib.NanGuardHook(every_steps=2)
    h.after_step(_FakeState(), {"loss": jnp.asarray(1.0)}, 2)
    h.after_step(_FakeState(), {"loss": jnp.asarray(float("nan"))}, 3)  # off-cadence
    with pytest.raises(FloatingPointError):
        h.after_step(_FakeState(), {"loss": jnp.asarray(float("nan"))}, 4)


def test_nan_guard_catches_mid_chunk_nan_with_exact_step():
    """Fused-chunk NaN detection: the guard fires at a chunk-boundary walk
    but scans the whole stacked chunk, attributing the NaN to its exact
    mid-chunk step."""
    stacked = {
        "loss": jnp.asarray([1.0, float("nan"), 2.0, 3.0]),
    }
    h = hooklib.NanGuardHook(every_steps=4)
    row = hooklib.LazyMetricRow(stacked, index=3, chunk_start_step=5)
    with pytest.raises(FloatingPointError, match="at step 6"):
        h.after_step(_FakeState(), row, 8)
    # A clean chunk passes.
    clean = hooklib.LazyMetricRow(
        {"loss": jnp.asarray([1.0, 2.0, 3.0, 4.0])}, 3, 5
    )
    h.after_step(_FakeState(), clean, 8)


def test_lazy_metric_row_semantics():
    """Row access indexes the stacked leaf; writes land in the overlay
    (TelemetryHook's injection contract); iteration sees both."""
    stacked = {"loss": jnp.asarray([1.0, 2.0, 3.0]), "acc": jnp.asarray([0.1, 0.2, 0.3])}
    row = hooklib.LazyMetricRow(stacked, index=1, chunk_start_step=10)
    assert float(row["loss"]) == 2.0
    assert float(row["acc"]) == pytest.approx(0.2)
    row.update({"steps_per_sec": 42.0, "loss": 9.0})  # overlay shadows
    assert row["steps_per_sec"] == 42.0
    assert float(row["loss"]) == 9.0
    assert set(row) == {"loss", "acc", "steps_per_sec"}
    assert len(row) == 3
    assert {k: float(v) for k, v in row.items()}["acc"] == pytest.approx(0.2)


def test_wants_step_gating():
    """Built-in hooks declare their active steps; the default stays
    conservative (every step) so arbitrary user hooks keep per-step
    semantics under the fused loop."""
    assert hooklib.Hook().wants_step(1)
    assert hooklib.StopAtStepHook(5).wants_step(5)
    assert not hooklib.StopAtStepHook(5).wants_step(4)
    ng = hooklib.NanGuardHook(every_steps=10)
    assert ng.wants_step(10) and not ng.wants_step(9)
    fault = hooklib.FaultInjectionHook(7)
    assert fault.wants_step(7) and not fault.wants_step(6)
    ck = hooklib.CheckpointHook(lambda s, st: None, every_secs=1e9)
    assert not ck.wants_step(3)  # clock nowhere near due
    ck2 = hooklib.CheckpointHook(
        lambda s, st: None, every_secs=None, every_steps=4
    )
    assert ck2.wants_step(8) and not ck2.wants_step(7)


def test_run_hooks_after_chunk_walks_only_wanted_steps():
    """The chunk walk skips steps no hook wants and counts full walks into
    train/hook_walks; StopRequested stops the walk after its step."""
    from distributed_tensorflow_models_tpu import telemetry

    seen = []

    class Every4(hooklib.Hook):
        def wants_step(self, step):
            return step % 4 == 0

        def after_step(self, state, metrics, step):
            seen.append((step, float(metrics["loss"])))

    reg = telemetry.MetricsRegistry()
    stacked = {"loss": jnp.arange(8, dtype=jnp.float32)}
    ok = hooklib.run_hooks_after_chunk(
        [Every4(), hooklib.StopAtStepHook(100)],
        _FakeState(), stacked, start_step=0, length=8, registry=reg,
    )
    assert ok
    assert seen == [(4, 3.0), (8, 7.0)]  # rows 3 and 7 of the chunk
    assert reg.snapshot()[f"{telemetry.HOOK_WALKS}"] == 2.0

    # Stop at a mid-chunk step: later rows are not walked (the unfused
    # loop breaks immediately after the stop step too).
    seen.clear()
    reg2 = telemetry.MetricsRegistry()
    ok = hooklib.run_hooks_after_chunk(
        [Every4(), hooklib.StopAtStepHook(4)],
        _FakeState(), stacked, start_step=0, length=8, registry=reg2,
    )
    assert not ok
    assert seen == [(4, 3.0)]


def test_metric_writer_hook(tmp_path):
    h = hooklib.MetricWriterHook(str(tmp_path), every_steps=2)
    h.after_step(_FakeState(), {"loss": jnp.asarray(2.0)}, 1)  # skipped
    h.after_step(_FakeState(), {"loss": jnp.asarray(1.5)}, 2)
    h.after_step(_FakeState(), {"loss": jnp.asarray(1.0)}, 4)
    rows = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert [r["step"] for r in rows] == [2, 4]
    assert rows[0]["loss"] == 1.5


def test_checkpoint_hook_cadence():
    saves = []
    h = hooklib.CheckpointHook(
        lambda s, step: saves.append(step), every_secs=None, every_steps=3
    )
    for step in range(1, 8):
        h.after_step(_FakeState(), {}, step)
    state = _FakeState()
    state.step = jnp.asarray(7)
    h.end(state)
    assert saves == [3, 6, 7]


def test_step_counter_hook():
    h = hooklib.StepCounterHook(every_steps=2, batch_size=32)
    state = _FakeState()
    h.begin(state)
    h.after_step(state, {}, 1)
    h.after_step(state, {}, 2)
    assert h.last_steps_per_sec is not None and h.last_steps_per_sec > 0


def test_logging_hook_skips_array_valued_metrics(caplog):
    """float() on an array metric raises TypeError; the logging path must
    skip it (mirroring SummaryWriter.scalars) instead of killing training."""
    import logging as _logging

    h = hooklib.LoggingHook(every_steps=1)
    metrics = {
        "loss": jnp.asarray(1.5),
        "per_class": jnp.ones((4,)),  # non-scalar: must be skipped
        "junk": object(),
    }
    with caplog.at_level(_logging.INFO, logger="dtm"):
        h.after_step(_FakeState(), metrics, 1)
    assert "loss=1.5000" in caplog.text
    assert "per_class" not in caplog.text


def test_metric_writer_keeps_handle_open_and_appends(tmp_path):
    """The satellite fix: one persistent line-buffered handle, one write
    per row — rows are on disk immediately (no reopen per write), and a
    reopened hook appends rather than truncates."""
    h = hooklib.MetricWriterHook(str(tmp_path), every_steps=1)
    h.after_step(_FakeState(), {"loss": jnp.asarray(1.0)}, 1)
    # Visible to a concurrent tail before any close/flush call.
    assert len((tmp_path / "metrics.jsonl").read_text().splitlines()) == 1
    f_first = h._f
    h.after_step(_FakeState(), {"loss": jnp.asarray(0.5)}, 2)
    assert h._f is f_first  # no reopen between writes
    h.end(_FakeState())
    assert h._f.closed

    h2 = hooklib.MetricWriterHook(str(tmp_path), every_steps=1)
    h2.after_step(_FakeState(), {"loss": jnp.asarray(0.25)}, 3)
    h2.end(_FakeState())
    rows = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert [r["step"] for r in rows] == [1, 2, 3]


def test_run_hooks_after_step_runs_all_despite_stop():
    """Ordering + StopRequested semantics: every hook sees the stop step's
    metrics; later hooks are not starved by an earlier hook's stop."""
    calls = []

    class Recorder(hooklib.Hook):
        def __init__(self, name, stop=False):
            self._name, self._stop = name, stop

        def after_step(self, state, metrics, step):
            calls.append(self._name)
            if self._stop:
                raise hooklib.StopRequested

    hooks = [Recorder("a", stop=True), Recorder("b"), Recorder("c", stop=True)]
    assert hooklib.run_hooks_after_step(hooks, _FakeState(), {}, 1) is False
    assert calls == ["a", "b", "c"]


def test_hook_abort_dispatch():
    """Hook.abort defaults to end(); an override severs that link — the
    failure path must call abort, never end, on overriding hooks."""
    events = []

    class EndOnly(hooklib.Hook):
        def end(self, state):
            events.append("end_only.end")

    class Overridden(hooklib.Hook):
        def end(self, state):
            events.append("overridden.end")

        def abort(self, state):
            events.append("overridden.abort")

    EndOnly().abort(None)
    Overridden().abort(None)
    assert events == ["end_only.end", "overridden.abort"]


def test_checkpoint_hook_abort_skips_collective_save_multihost(monkeypatch):
    """With process_count > 1 a crash-time save is a collective this lone
    failing process must NOT enter (peers are blocked in the next step's
    all-reduce); single-process the crash save preserves progress."""
    saves = []
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    h = hooklib.CheckpointHook(
        lambda s, step: saves.append(step), every_secs=None
    )
    h.abort(_FakeState())
    assert saves == []  # skipped: no one-process collective entry

    monkeypatch.setattr(jax, "process_count", lambda: 1)
    h1 = hooklib.CheckpointHook(
        lambda s, step: saves.append(step), every_secs=None
    )
    h1.abort(_FakeState())
    assert saves == [0]  # single-process crash-time save runs


# --------------------------------------------------------------------------
# Checkpointing
# --------------------------------------------------------------------------


def _tiny_state(ema=False, carry=False):
    model = get_model("lenet", num_classes=4)
    tx = optim.tf_momentum(0.1, 0.9)
    return TrainState.create(
        model,
        tx,
        jax.random.key(0),
        jnp.zeros((2, 28, 28, 1)),
        ema_decay=0.99 if ema else None,
        carry={"h": jnp.ones((2, 3))} if carry else None,
    )


def test_checkpoint_roundtrip(tmp_path):
    state = _tiny_state(ema=True, carry=True)
    state = state.replace(step=jnp.asarray(12, jnp.int32))
    mgr = ckptlib.CheckpointManager(str(tmp_path), keep=2)
    assert mgr.save(state, {"dataset": {"epoch": 1, "batch_idx": 7}})
    mgr.wait()

    template = _tiny_state(ema=True, carry=True)
    restored, data = mgr.restore(template)
    mgr.close()
    assert int(restored.step) == 12
    assert data == {"dataset": {"epoch": 1, "batch_idx": 7}}
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        state.params,
        restored.params,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
        state.ema_params,
        restored.ema_params,
    )
    np.testing.assert_allclose(restored.carry["h"], np.ones((2, 3)))


def test_checkpoint_keep_k(tmp_path):
    state = _tiny_state()
    mgr = ckptlib.CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3):
        mgr.save(state.replace(step=jnp.asarray(s, jnp.int32)))
    mgr.wait()
    assert mgr.latest_step() == 3
    with pytest.raises(Exception):
        mgr.restore(_tiny_state(), step=1)  # evicted by keep=2
    mgr.close()


def test_restore_or_init_fresh(tmp_path):
    template = _tiny_state()
    mgr = ckptlib.CheckpointManager(str(tmp_path), keep=1)
    state, data, restored = ckptlib.restore_or_init(mgr, template)
    assert not restored and state is template and data == {}
    mgr.close()


# --------------------------------------------------------------------------
# fit / eval end-to-end on the fake mesh
# --------------------------------------------------------------------------


def _small_cfg(**kw):
    base = dict(
        train_steps=6,
        global_batch_size=32,
        log_every_steps=2,
        checkpoint_every_secs=10_000.0,
    )
    base.update(kw)
    return configlib.get_config("lenet_mnist", **base)


def test_fit_runs_and_checkpoints(mesh8, tmp_path):
    cfg = _small_cfg()
    result = trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    assert result.steps_run == 6
    assert int(result.state.step) == 6
    assert np.isfinite(result.final_metrics["loss"])
    assert os.path.exists(tmp_path / "metrics.jsonl")
    # CheckpointHook.end saved the final state.
    mgr = ckptlib.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 6
    mgr.close()


def test_fit_auto_resume(mesh8, tmp_path):
    """Kill/restart semantics: a second fit picks up at the saved step and
    the input pipeline position, finishing the remaining steps only."""
    cfg = _small_cfg(train_steps=4)
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)

    cfg2 = _small_cfg(train_steps=8)
    result = trainlib.fit(cfg2, str(tmp_path), mesh=mesh8)
    assert result.steps_run == 4  # only the remaining 4
    assert int(result.state.step) == 8

    # And a third invocation with nothing to do runs zero steps.
    result3 = trainlib.fit(cfg2, str(tmp_path), mesh=mesh8)
    assert result3.steps_run == 0
    assert int(result3.state.step) == 8


@pytest.mark.slow
def test_fused_loop_host_overhead_drops_k_fold(mesh8, tmp_path):
    """Tier-1 micro-guard for the fused multi-step dispatch: at
    steps_per_loop=K the host overhead per step — jitted dispatches and
    full hook walks — must drop ≥K-fold vs the unfused loop.  Counts come
    from the run's own telemetry snapshot (telemetry.json), the same
    instrument a production run reads."""
    K = 8
    cfg = _small_cfg(train_steps=16, log_every_steps=8)

    def run(workdir, **kw):
        trainlib.fit(cfg.replace(**kw), workdir, mesh=mesh8)
        with open(os.path.join(workdir, "telemetry.json")) as f:
            snap = json.load(f)["metrics"]
        dispatches = snap.get("train/dispatch/count", 0.0) + snap.get(
            "train/compile/count", 0.0
        )
        return dispatches, snap.get("train/hook_walks", 0.0)

    d1, w1 = run(str(tmp_path / "unfused"))
    dk, wk = run(str(tmp_path / "fused"), steps_per_loop=K)
    assert d1 == 16.0 and w1 == 16.0  # one dispatch + one walk per step
    assert dk * K <= d1, (dk, d1)
    assert wk * K <= w1, (wk, w1)


def _pipeline_threads():
    import threading

    return [
        t.name
        for t in threading.enumerate()
        if t.is_alive()
        and t.name.startswith(("host-pipeline", "data-worker"))
    ]


def test_fit_leaves_no_pipeline_threads(mesh8, tmp_path):
    """Tier-1 thread-leak guard: after fit() returns — normal end AND the
    abort path — every host-pipeline / data-worker-* thread is joined.
    Run with a worker pool so the guard covers dispatcher + workers +
    reassembly, not just the single serial producer."""
    cfg = _small_cfg(train_steps=2, data_workers=2)
    trainlib.fit(cfg, str(tmp_path / "ok"), mesh=mesh8)
    assert _pipeline_threads() == []

    class Poison(hooklib.Hook):
        def after_step(self, state, metrics, step):
            if step == 1:
                raise FloatingPointError("injected abort")

    with pytest.raises(FloatingPointError):
        trainlib.fit(
            cfg,
            str(tmp_path / "abort"),
            mesh=mesh8,
            extra_hooks=[Poison()],
        )
    assert _pipeline_threads() == []


def test_recoverable_fit_survives_injected_fault(mesh8, tmp_path):
    """_RecoverableSession semantics (TF monitored_session.py:1261-1274):
    a preemption-class failure mid-training restarts from the latest
    checkpoint and completes, losing no checkpointed progress."""

    class Preempted(ConnectionError):
        pass

    cfg = _small_cfg(train_steps=8)
    fault = hooklib.FaultInjectionHook(5, lambda: Preempted("chip lost"))
    result = trainlib.recoverable_fit(
        cfg,
        str(tmp_path),
        mesh=mesh8,
        max_restarts=2,
        backoff_base_s=0.0,  # keep the test immediate (backoff pinned
        # separately in tests/test_resilience.py)
        extra_hooks=[fault],
    )
    assert int(result.state.step) == 8
    # The retry resumed from the crash-time save (step 5), not from zero.
    assert result.steps_run == 3
    mgr = ckptlib.CheckpointManager(str(tmp_path))
    assert mgr.latest_step() == 8
    mgr.close()


def test_recoverable_fit_gives_up_after_max_restarts(mesh8, tmp_path):
    class Preempted(ConnectionError):
        pass

    class AlwaysFault(hooklib.Hook):
        def after_step(self, state, metrics, step):
            raise Preempted("flaky every attempt")

    cfg = _small_cfg(train_steps=8)
    with pytest.raises(Preempted):
        trainlib.recoverable_fit(
            cfg,
            str(tmp_path),
            mesh=mesh8,
            max_restarts=2,
            backoff_base_s=0.0,
            extra_hooks=[AlwaysFault()],
        )


def test_is_transient_error_filters_deterministic_xla_failures():
    """ADVICE r1: XLA raises JaxRuntimeError for both preemption-class and
    deterministic failures; only the former is worth restore-and-retry."""
    import jax

    Err = jax.errors.JaxRuntimeError
    assert trainlib.is_transient_error(ConnectionError("peer gone"))
    assert trainlib.is_transient_error(
        Err("UNAVAILABLE: connection reset by peer")
    )
    assert trainlib.is_transient_error(Err("ABORTED: coordination heartbeat"))
    # Unknown message shapes default to transient: a retry is bounded, a
    # dead multi-host run is not.
    assert trainlib.is_transient_error(
        Err("INTERNAL: failed to communicate with peer task 3")
    )
    assert not trainlib.is_transient_error(
        Err("INVALID_ARGUMENT: donated buffer was reused")
    )
    assert not trainlib.is_transient_error(
        Err("RESOURCE_EXHAUSTED: out of memory allocating 16.0G")
    )
    # A program the chip's compiler refuses is refused again on every
    # retry, whatever status code it is wrapped in.
    assert not trainlib.is_transient_error(
        Err("UNAVAILABLE: TPU backend setup/compile error (Unavailable)")
    )
    assert not trainlib.is_transient_error(
        Err("INTERNAL: Mosaic failed to compile TPU kernel: bad slice")
    )


def test_recoverable_fit_propagates_deterministic_jax_errors(mesh8, tmp_path):
    """A deterministic XLA failure must fail fast, not burn max_restarts
    restore-retrain cycles (ADVICE r1)."""
    import jax

    attempts = []

    class Poison(hooklib.Hook):
        def after_step(self, state, metrics, step):
            if step == 2:
                attempts.append(1)
                raise jax.errors.JaxRuntimeError(
                    "RESOURCE_EXHAUSTED: out of memory"
                )

    cfg = _small_cfg(train_steps=4)
    with pytest.raises(jax.errors.JaxRuntimeError):
        trainlib.recoverable_fit(
            cfg, str(tmp_path), mesh=mesh8, max_restarts=3,
            extra_hooks=[Poison()],
        )
    assert len(attempts) == 1  # no retries


def test_recoverable_fit_does_not_catch_nan_guard(mesh8, tmp_path):
    """A NaN trip is deterministic, not a preemption — restarting would
    crash-loop, so it must propagate (SURVEY.md §5.5 NanTensorHook role)."""
    cfg = _small_cfg(train_steps=4)

    class Poison(hooklib.Hook):
        def after_step(self, state, metrics, step):
            if step == 2:
                # What NanGuardHook raises on a non-finite loss.
                raise FloatingPointError("loss is nan at step 2")

    with pytest.raises(FloatingPointError):
        trainlib.recoverable_fit(
            cfg, str(tmp_path), mesh=mesh8, extra_hooks=[Poison()]
        )


@pytest.mark.slow
def test_fit_then_eval_classification(mesh8, tmp_path):
    cfg = _small_cfg(train_steps=20)
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    res = evallib.evaluate_classification(
        cfg, str(tmp_path), mesh=mesh8, max_batches=4
    )
    assert res.step == 20
    assert 0.0 <= res.metrics["top1"] <= 1.0
    assert res.metrics["top5"] >= res.metrics["top1"]
    assert res.metrics["top1"] > 0.15  # better than chance after 20 steps


def test_fit_lm_and_eval(mesh8, tmp_path):
    cfg = configlib.get_config(
        "ptb_small",
        train_steps=4,
        global_batch_size=16,
        num_steps=8,
        vocab_size=64,
        model_kwargs={"config": "small", "hidden_size": 16, "vocab_size": 64},
        log_every_steps=2,
        checkpoint_every_secs=10_000.0,
    )
    result = trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    assert int(result.state.step) == 4
    assert np.isfinite(result.final_metrics["loss"])
    res = evallib.evaluate_lm(cfg, str(tmp_path), mesh=mesh8, max_batches=3)
    assert res.metrics["perplexity"] > 1.0
    assert np.isfinite(res.metrics["perplexity"])


@pytest.mark.slow
def test_async_vs_sync_ab_experiment(mesh8):
    """The reference's flagship A/B ([B:10], SURVEY.md §2.4) as a harness
    call: same init + batch stream through both modes."""
    from distributed_tensorflow_models_tpu.harness import experiment

    cfg = _small_cfg(train_steps=12)
    res = experiment.async_vs_sync(
        cfg, 12, num_workers=2, mesh=mesh8
    )
    assert len(res.sync_losses) == 12 and len(res.async_losses) == 12
    assert np.isfinite(res.sync_losses).all()
    assert np.isfinite(res.async_losses).all()
    # Both modes learn on the easy synthetic stream (per-event losses are
    # noisy — stale-parameter forwards — so compare half-means).
    assert np.mean(res.sync_losses[-4:]) < np.mean(res.sync_losses[:4])
    assert np.mean(res.async_losses[-4:]) < np.mean(res.async_losses[:4])
    # Round-robin with 2 workers: steady-state staleness 1.
    assert res.mean_staleness > 0
    j = res.to_json()
    assert set(j) == {"sync", "async"}
    assert j["async"]["mean_staleness"] > 0


def test_cli_ab_subcommand(mesh8, capsys):
    from distributed_tensorflow_models_tpu.harness import cli

    rc = cli.main(
        [
            "ab",
            "--config",
            "lenet_mnist",
            "--steps",
            "4",
            "--async-workers",
            "2",
            "--batch-size",
            "32",
        ]
    )
    assert rc == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert "sync" in out and "async" in out


def test_zaremba_schedule():
    sched = optim.zaremba_decay(1.0, steps_per_epoch=10, hold_epochs=4,
                                decay_rate=0.5)
    # Constant through the first 4 epochs (steps 0..39).
    assert float(sched(0)) == 1.0
    assert float(sched(39)) == 1.0
    # Then halves each epoch: epoch 4 -> 0.5, epoch 5 -> 0.25 ...
    assert float(sched(40)) == pytest.approx(0.5)
    assert float(sched(49)) == pytest.approx(0.5)
    assert float(sched(50)) == pytest.approx(0.25)


def test_final_step_metrics_written(mesh8, tmp_path):
    """The stop step's metrics must land in metrics.jsonl even though
    StopAtStepHook fires on that same step."""
    cfg = _small_cfg(train_steps=4, log_every_steps=2)
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    rows = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert rows[-1]["step"] == 4


def test_device_prefetcher_state_tracks_consumed(mesh8):
    """Checkpoointed dataset position reflects consumed batches, not the
    prefetch buffer's read-ahead."""
    from distributed_tensorflow_models_tpu.data import datasets, pipeline

    x = np.arange(64, dtype=np.float32).reshape(64, 1)
    y = np.arange(64, dtype=np.int32)
    ds = datasets.ArrayDataset({"image": x, "label": y}, 8, seed=1)
    pre = pipeline.DevicePrefetcher(ds, mesh8, depth=2)
    consumed = [np.asarray(next(pre)["label"]) for _ in range(3)]
    state = pre.get_state()
    assert state == {"epoch": 0, "batch_idx": 3}

    ds2 = datasets.ArrayDataset({"image": x, "label": y}, 8, seed=1)
    ds2.set_state(state)
    nxt = np.asarray(next(pre)["label"])  # 4th batch from original
    resumed = next(iter(ds2))["label"]
    np.testing.assert_array_equal(resumed, nxt)
    assert not any(np.array_equal(resumed, c) for c in consumed)


def test_cli_list_and_train(tmp_path, capsys):
    from distributed_tensorflow_models_tpu.harness import cli

    assert cli.main(["list"]) == 0
    out = capsys.readouterr().out
    assert "lenet_mnist" in out


def test_checkpoint_per_process_dataset_sidecar(tmp_path):
    """Multi-host dataset state: each process saves/restores its OWN
    iterator position via per-step sidecars (exact resume for the
    file-sharded ImageNet stream, where positions differ per process)."""
    import os

    state = _tiny_state()
    state = state.replace(step=jnp.asarray(3, jnp.int32))
    # Simulated process 1 of 2 (injectable so no real cluster is needed;
    # orbax itself runs single-process here).
    mgr = ckptlib.CheckpointManager(
        str(tmp_path), keep=2, process_index=1, process_count=2
    )
    assert mgr.save(state, {"dataset": {"records": 41}})
    mgr.wait()
    assert os.path.exists(
        os.path.join(str(tmp_path), "checkpoints/dataset_states/3/p1.json")
    )

    _, data = mgr.restore(_tiny_state())
    assert data == {"dataset": {"records": 41}}

    # A process without a sidecar falls back to the orbax (primary) JSON.
    mgr0 = ckptlib.CheckpointManager(
        str(tmp_path), keep=2, process_index=0, process_count=2
    )
    _, data0 = mgr0.restore(_tiny_state())
    assert data0 == {"dataset": {"records": 41}}
    mgr.close()
    mgr0.close()


def test_checkpoint_sidecar_pruned_with_keep_k(tmp_path):
    import os

    mgr = ckptlib.CheckpointManager(
        str(tmp_path), keep=1, process_index=0, process_count=2
    )
    for step in (1, 2):
        state = _tiny_state().replace(step=jnp.asarray(step, jnp.int32))
        assert mgr.save(state, {"pos": step}, force=True)
        mgr.wait()
    base = os.path.join(str(tmp_path), "checkpoints/dataset_states")
    assert sorted(os.listdir(base)) == ["2"]
    mgr.close()


def test_checkpoint_sidecar_topology_mismatch_falls_back(tmp_path):
    """A sidecar from an N-process run must not be restored as exact when
    resuming with a different process count."""
    import json
    import os

    state = _tiny_state().replace(step=jnp.asarray(5, jnp.int32))
    mgr4 = ckptlib.CheckpointManager(
        str(tmp_path), process_index=1, process_count=4
    )
    assert mgr4.save(state, {"pos": "primary"})
    mgr4.wait()
    # Make the sidecar's payload distinct from the orbax primary copy so
    # the assertion discriminates which path restore() actually took.
    sidecar = os.path.join(
        str(tmp_path), "checkpoints/dataset_states/5/p1.json"
    )
    with open(sidecar, "w") as f:
        json.dump({"nproc": 4, "state": {"pos": "sidecar"}}, f)
    # Same pid, different topology: must fall back to the primary JSON.
    mgr2 = ckptlib.CheckpointManager(
        str(tmp_path), process_index=1, process_count=2
    )
    _, data = mgr2.restore(_tiny_state())
    assert data == {"pos": "primary"}
    # Matching topology: the sidecar is exact and wins.
    mgr4b = ckptlib.CheckpointManager(
        str(tmp_path), process_index=1, process_count=4
    )
    _, data4 = mgr4b.restore(_tiny_state())
    assert data4 == {"pos": "sidecar"}
    # Legacy bare-dict sidecar (no topology stamp): same format, restored.
    with open(sidecar, "w") as f:
        json.dump({"pos": "legacy"}, f)
    mgr4c = ckptlib.CheckpointManager(
        str(tmp_path), process_index=1, process_count=4
    )
    _, datal = mgr4c.restore(_tiny_state())
    assert datal == {"pos": "legacy"}
    for m in (mgr4, mgr2, mgr4b, mgr4c):
        m.close()


def test_inception_harness_state_traces_train_step():
    """build_state inits with train=False; the train step applies with
    train=True.  Every parameter the train path uses (incl. the aux head)
    must exist in that state — pinned at trace level so the full 299x299
    model costs no FLOPs here.  Regression: aux params used to be created
    only under train=True init, crashing inception training."""
    import numpy as np

    from distributed_tensorflow_models_tpu.core import train_loop
    from distributed_tensorflow_models_tpu.harness.config import get_config

    cfg = get_config("inception_v3_imagenet", global_batch_size=2)
    mesh = trainlib.mesh_from_config(cfg)
    state = trainlib.build_state(cfg, mesh)
    loss_fn = train_loop.classification_loss_fn(
        state.apply_fn,
        label_smoothing=cfg.label_smoothing,
        weight_decay=cfg.weight_decay,
        aux_loss_weight=cfg.aux_loss_weight,
    )
    step_fn = train_loop.make_train_step_fn(loss_fn)
    batch = {
        "image": np.zeros((2, 299, 299, 3), np.float32),
        "label": np.zeros((2,), np.int32),
    }
    out_state, metrics = jax.eval_shape(
        step_fn, state, batch, jax.random.key(0)
    )
    assert metrics["loss"].shape == ()
    # Aux head params must be in the state (declared at eval-mode init).
    assert "AuxHead" in state.params
