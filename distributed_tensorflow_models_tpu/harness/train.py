"""Generic training driver: restore-or-init, hook orchestration, auto-resume.

This is the worker ``main()`` of every reference driver collapsed into one
function (SURVEY.md §3.1): where the reference builds a ClusterSpec/Server,
wraps graph construction in ``replica_device_setter``, and loops
``mon_sess.run(train_op)`` under MonitoredTrainingSession's hooks, this
driver builds the mesh, places the state, compiles the step, and loops over
the host pipeline — identical capabilities, one SPMD program.

Fault recovery (SURVEY.md §5.3): the reference wraps sessions in
``_RecoverableSession`` which recreates a session after preemption and
restarts from the last checkpoint (TF monitored_session.py:1261-1274).  On
TPU the process dies with its slice, so the equivalent is *auto-resume*:
rerunning the same command restores the latest checkpoint — including the
input-pipeline position — and continues.  ``fit`` is therefore idempotent
under kill/restart, which the integration test exercises.
"""

from __future__ import annotations

import dataclasses
import logging
import os
import time
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from distributed_tensorflow_models_tpu import resilience, telemetry
from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.data import datasets as datalib
from distributed_tensorflow_models_tpu.data import pipeline as pipelib
from distributed_tensorflow_models_tpu.harness import checkpoint as ckptlib
from distributed_tensorflow_models_tpu.harness import hooks as hooklib
from distributed_tensorflow_models_tpu.harness import startup as startuplib
from distributed_tensorflow_models_tpu.harness.config import (
    PREEMPT_POLL_STEPS_DEFAULT,
    ExperimentConfig,
)
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.telemetry import scopes as scopelib

log = logging.getLogger("dtm")


def build_dataset(cfg: ExperimentConfig, split: str = "train"):
    """Dataset factory keyed by config (the L3 wiring of each driver).

    Multi-host: each process builds a dataset yielding only its
    ``global_batch/process_count`` slice (SURVEY.md §3.4 — each reference
    worker reads its own shard stream); ``shard_batch`` assembles the
    process-local slices into the global device array.
    """
    pid, nproc = jax.process_index(), jax.process_count()
    proc = dict(process_index=pid, process_count=nproc)
    if cfg.dataset == "mnist":
        return datalib.mnist_dataset(
            cfg.global_batch_size, split, cfg.seed, **proc
        )
    if cfg.dataset == "cifar10":
        return datalib.cifar10_dataset(
            cfg.global_batch_size, split, cfg.seed, **proc
        )
    if cfg.dataset == "imagenet_synthetic":
        return datalib.synthetic_imagenet_dataset(
            cfg.global_batch_size, cfg.image_size, cfg.seed, **proc
        )
    if cfg.dataset == "imagenet":
        import glob
        import os

        pattern = os.path.join(
            datalib.DATA_DIR,
            "imagenet",
            "train-*" if split == "train" else "validation-*",
        )
        paths = sorted(glob.glob(pattern))
        if not paths:
            log.warning(
                "no ImageNet shards under %s; using synthetic data", pattern
            )
            return datalib.synthetic_imagenet_dataset(
                cfg.global_batch_size, cfg.image_size, cfg.seed, **proc
            )
        return datalib.ImageNetTFRecordDataset(
            paths,
            cfg.global_batch_size,
            train=split == "train",
            image_size=cfg.image_size,
            seed=cfg.seed,
            label_offset=1,
            **proc,
        )
    if cfg.dataset == "ptb":
        return datalib.ptb_dataset(
            cfg.global_batch_size,
            cfg.num_steps,
            split,
            cfg.vocab_size,
            **proc,
        )
    raise ValueError(f"unknown dataset {cfg.dataset!r}")


def mesh_from_config(cfg: ExperimentConfig):
    """The one place a config becomes a mesh — every driver (fit, the eval
    loops, the A/B experiment) must agree on axis sizes or a config trained
    on a seq/pipe/expert mesh would be evaluated on a different topology."""
    return meshlib.create_mesh(
        meshlib.MeshSpec(
            data=cfg.mesh_data,
            model=cfg.mesh_model,
            seq=cfg.mesh_seq,
            pipe=cfg.mesh_pipe,
            expert=cfg.mesh_expert,
        )
    )


def _mesh_model_kwargs(cfg: ExperimentConfig, mesh) -> dict:
    """Mesh-dependent model kwargs for attention models: the attention
    implementation and, when ``seq_impl``/``mesh_expert`` are configured,
    the sequence-parallel attention fn and the MoE mesh.  These change how
    the model *computes*, never what parameters it declares — so init can
    use the plain (mesh-free) model on a tiny sample while the training
    ``apply_fn`` comes from the mesh-aware instance."""
    if cfg.model != "transformer_lm":
        return {}
    if cfg.mesh_pipe > 1 and cfg.seq_impl:
        raise ValueError(
            "mesh_pipe and seq_impl cannot combine: the pipelined block "
            "stack schedules whole blocks per stage and does not route "
            "through the sequence-parallel attention_fn"
        )
    if cfg.mesh_pipe > 1 and cfg.mesh_model > 1:
        raise ValueError(
            "mesh_pipe and mesh_model cannot combine: the tensor-parallel "
            "rule sets target per-block parameter names, which the "
            "pipelined stacked layout does not use — TP would silently "
            "fall back to replication"
        )
    if cfg.mesh_expert > 1 and cfg.model_kwargs.get("moe_router") == "topk":
        raise ValueError(
            "mesh_expert > 1 with moe_router='topk': exact top-k routing "
            "over an expert axis needs an all-to-all of uneven size, "
            "which is the cell olmoe_train_ep4's PR (PERF.md section 7)"
        )
    kwargs: dict = {"attn_impl": cfg.attn_impl}
    if cfg.seq_impl:
        from distributed_tensorflow_models_tpu.parallel import ring as ringlib

        # A sliding window moves INTO the sequence-parallel closure (ring
        # and ulysses mask in global coordinates); _init_model_kwargs
        # drops it from the model so the attention_fn guard doesn't trip
        # and the window isn't double-applied.
        window = cfg.model_kwargs.get("attn_window")
        if cfg.seq_impl == "ring":
            # attn_impl maps onto the ring inner step: auto picks the
            # Pallas chunk kernel + LSE merge on TPU; reference/blockwise
            # use the XLA streaming fold (parallel/ring.py).
            ring_impl = "auto" if cfg.attn_impl == "auto" else "fold"
            kwargs["attention_fn"] = lambda q, k, v, causal=True: (
                ringlib.ring_attention(
                    q, k, v, mesh, causal=causal, impl=ring_impl,
                    window=window,
                )
            )
        elif cfg.seq_impl == "ulysses":
            kwargs["attention_fn"] = lambda q, k, v, causal=True: (
                ringlib.ulysses_attention(
                    q, k, v, mesh, causal=causal, impl=cfg.attn_impl,
                    window=window,
                )
            )
        else:
            raise ValueError(f"unknown seq_impl {cfg.seq_impl!r}")
    if cfg.model_kwargs.get("num_experts", 0) > 0:
        kwargs["moe_mesh"] = mesh
    if cfg.mesh_pipe > 1:
        kwargs["pipe_mesh"] = mesh
    return kwargs


def _init_model_kwargs(cfg: ExperimentConfig) -> dict:
    """Kwargs for the mesh-free *init* model.  Must declare the identical
    parameter structure the mesh-aware apply model uses — the pipelined
    block stack changes the layout (stacked per-layer params), so that
    switch is the one mesh-dependent kwarg also applied at init."""
    kwargs = dict(cfg.model_kwargs)
    if cfg.model == "transformer_lm" and cfg.mesh_pipe > 1:
        kwargs.setdefault("pipelined", True)
    if cfg.seq_impl:
        # Under sequence parallelism the window lives in the
        # attention_fn closure (_mesh_model_kwargs); the model must not
        # also apply it.  Params don't depend on attn_window, so the
        # init/apply parameter structures stay identical.
        kwargs.pop("attn_window", None)
    return kwargs


def build_state(cfg: ExperimentConfig, mesh) -> TrainState:
    model = get_model(cfg.model, **_init_model_kwargs(cfg))
    tx = cfg.optimizer.make()
    if cfg.task == "lm":
        sample = jnp.zeros(
            (2, cfg.num_steps), jnp.int32
        )
        carry = (
            model.initial_carry(cfg.global_batch_size)
            if hasattr(model, "initial_carry")
            else None
        )
        state = TrainState.create(
            model,
            tx,
            jax.random.key(cfg.seed),
            sample,
            ema_decay=cfg.ema_decay,
            carry=carry,
        )
        mesh_kwargs = _mesh_model_kwargs(cfg, mesh)
        if mesh_kwargs:
            # Dict-merge (not **,**) so an explicit model_kwargs entry for
            # the same key overrides the config-derived default instead of
            # raising a duplicate-kwarg TypeError.
            mesh_model = get_model(
                cfg.model, **{**mesh_kwargs, **_init_model_kwargs(cfg)}
            )
            state = state.replace(apply_fn=mesh_model.apply)
    else:
        sample = jnp.zeros(
            (2, cfg.image_size, cfg.image_size, 3 if cfg.image_size > 28 else 1),
            jnp.float32,
        )
        if cfg.model == "lenet":
            sample = jnp.zeros((2, 28, 28, 1), jnp.float32)
        state = TrainState.create(
            model, tx, jax.random.key(cfg.seed), sample, ema_decay=cfg.ema_decay
        )
    return place(cfg, state, mesh)


def place(cfg: ExperimentConfig, state: TrainState, mesh) -> TrainState:
    """Lay ``state`` out on ``mesh`` as ``cfg`` says (its tensor-parallel
    rule set).  The one call to ``place_state``: the fresh template, a
    restored or rolled-back state and the evaluators' all go through
    here, so none of them can come back replicated where the run
    shards."""
    from distributed_tensorflow_models_tpu.parallel import tensor as tensorlib

    return train_loop.place_state(
        state, mesh, tensorlib.get_rules(cfg.param_rules)
    )


# Models whose __call__ accepts return_hidden (the fused chunked
# unembed+xent contract).  One list, shared by every loss-building entry
# point (fit and the A/B experiment).
FUSED_UNEMBED_MODELS = ("transformer_lm", "ptb_lstm")


def build_lm_loss(cfg: ExperimentConfig, apply_fn):
    """The one place an LM config becomes a loss fn; validates the
    fused_unembed capability before tracing can produce an opaque
    TypeError."""
    if cfg.fused_unembed and cfg.model not in FUSED_UNEMBED_MODELS:
        raise ValueError(
            "fused_unembed requires a model with a return_hidden path "
            f"({', '.join(FUSED_UNEMBED_MODELS)})"
        )
    return train_loop.lm_loss_fn(apply_fn, fused_unembed=cfg.fused_unembed)


def build_loss(cfg: ExperimentConfig, state: TrainState):
    """The one place a config becomes a loss fn (shared by the single-step
    and fused multi-step builders so they can never diverge)."""
    if cfg.task == "lm":
        return build_lm_loss(cfg, state.apply_fn)
    return train_loop.classification_loss_fn(
        state.apply_fn,
        label_smoothing=cfg.label_smoothing,
        weight_decay=cfg.weight_decay,
        aux_loss_weight=cfg.aux_loss_weight,
    )


def _shardings(state: TrainState):
    """The layout ``state`` was placed in, leaf by leaf: what its step
    programs are compiled to hand back.  None on one device, where there
    is no layout to drift and naming one makes the chip's compiler a
    fifth slower (``gpt2m_train``'s step: 134 -> 161 s; PERF.md section
    6, PR 43)."""
    if state.step.sharding.num_devices == 1:
        return None
    return jax.tree.map(lambda x: x.sharding, state)


def build_step(cfg: ExperimentConfig, state: TrainState):
    """The step program for the placed ``state``."""
    return train_loop.make_train_step(
        build_loss(cfg, state), state_shardings=_shardings(state)
    )


def build_multi_step(cfg: ExperimentConfig, state: TrainState):
    """(fused K-step program, raw single step) for ``steps_per_loop > 1``.
    The raw step rides along for telemetry: per-step FLOPs must come from
    a single-step lowering (cost analysis sees a scan body once —
    InstrumentedMultiStep's docstring)."""
    loss_fn = build_loss(cfg, state)
    return (
        train_loop.make_multi_step(
            loss_fn, state_shardings=_shardings(state)
        ),
        train_loop.make_train_step_fn(loss_fn),
    )


def _chunk_len(
    step: int, cfg: ExperimentConfig, hooks: Sequence[hooklib.Hook] = ()
) -> int:
    """Length of the next fused chunk starting after ``step``: up to
    ``cfg.steps_per_loop``, shrunk so the chunk ends exactly at (a) the
    next ``log_every_steps`` boundary, (b) ``train_steps``, and (c) the
    FIRST step any hook ``wants_step`` — a chunk is one atomic device
    program, so the only way a hook can observe the exact state of the
    step it fires at (an early StopAtStepHook in ``extra_hooks``, a
    fault injection, a profiler window edge, a due checkpoint clock) is
    for the chunk to end there.  Every hook therefore fires at precisely
    the same steps, with the same state, as the unfused loop.  The cost
    model follows: hooks that keep the conservative per-step default
    ``wants_step`` degrade the loop to per-step dispatch — cadence-aware
    hooks (all built-ins) are what buy fusion.

    Multi-host note: the chunk length feeds the compiled scan program,
    so it must be identical on every process — ``wants_step`` of every
    hook present on more than one process is deterministic in ``step``
    (the chief-only writer hooks share the cadence the every-process
    TelemetryHook/NanGuardHook probe anyway), and ``extra_hooks`` that
    exist on a subset of processes must gate on step-deterministic
    cadences or the processes' programs desync."""
    k = min(cfg.steps_per_loop, cfg.train_steps - step)
    if cfg.log_every_steps and cfg.log_every_steps > 0:
        k = min(k, cfg.log_every_steps - step % cfg.log_every_steps)
    k = max(k, 1)
    for i in range(1, k):
        if any(h.wants_step(step + i) for h in hooks):
            return i
    return k


# Default for ``ExperimentConfig.preempt_poll_steps`` — how often (in
# steps) multi-host runs agree on the preemption flag: the flag is
# per-process (the runtime signals every host, but not at the same
# instant), and the emergency save is a collective, so processes must
# decide "preempted now" at the same step — the same reasoning as
# CheckpointHook's clock-broadcast poll.  Single-process runs read the
# flag directly at every chunk boundary.  Lower it (via the config) when
# poll_steps x step_time would overrun the fleet's preemption grace
# window.  (The value itself lives in config.py — THE one definition —
# so harness/startup.py's dominant-chunk mirror can never drift from
# this loop's fallback; the historical name is kept for callers.)
PREEMPT_POLL_STEPS = PREEMPT_POLL_STEPS_DEFAULT


class _PreemptPollHook(hooklib.Hook):
    """Boundary-alignment only: makes fused chunks end at the multi-host
    preemption-poll steps so every process runs the poll collective at
    the same step.  ``after_step`` does nothing — the loop itself polls."""

    def __init__(self, every_steps: int):
        self._every = every_steps

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        pass


@dataclasses.dataclass
class FitResult:
    state: TrainState
    final_metrics: dict
    steps_run: int
    # Resilience markers (README "Robustness"): ``preempted`` — the run
    # stopped early at a chunk boundary on a preemption notice
    # (SIGTERM/SIGINT), after a forced emergency checkpoint; rerunning
    # the same command resumes it, so callers must treat it as
    # *resumable*, not failed.  ``rollbacks``/``skipped_batches`` — the
    # nan_policy="rollback" activity of this run (also exported as the
    # train/rollbacks and train/skipped_batches counters).
    preempted: bool = False
    rollbacks: int = 0
    skipped_batches: int = 0


def fit(
    cfg: ExperimentConfig,
    workdir: str,
    *,
    extra_hooks: Sequence[hooklib.Hook] = (),
    mesh: Optional[object] = None,
    restarts: int = 0,
    listener: Optional[resilience.PreemptionListener] = None,
) -> FitResult:
    """Train ``cfg`` to ``cfg.train_steps``, resuming from ``workdir`` if a
    checkpoint exists.  Returns the final (host-fetched) state.

    With ``cfg.steps_per_loop > 1`` the loop drives *fused chunks*: K
    stacked batches per jitted ``lax.scan`` dispatch
    (``core/train_loop.py::make_multi_step``), per-step metric rows
    accumulated on device and handed to hooks lazily
    (``hooks.run_hooks_after_chunk`` — quiet steps are never walked and
    never force a device sync).  Chunks shrink to end exactly at
    ``log_every_steps`` boundaries and ``train_steps``, so hook cadences
    and the training trajectory are identical to the unfused loop.

    Telemetry: the run owns a fresh ``MetricsRegistry`` threaded through
    the pipeline, the instrumented step, the checkpoint manager, and a
    ``TelemetryHook``; on exit (success *and* failure) the chief writes
    ``<workdir>/telemetry.json`` — the goodput report splitting total wall
    time into compute / data-stall / checkpoint / compile.
    ``restarts`` seeds the ``train/restarts`` counter (``recoverable_fit``
    passes its attempt number so the final report carries the cumulative
    count).

    Resilience (README "Robustness"; mechanisms in ``resilience/``):

    - **Preemption grace** — SIGTERM (or a first SIGINT) sets a flag the
      loop polls at chunk boundaries; on it, a forced emergency
      checkpoint (state + dataset sidecars) is written, teardown runs
      cleanly, and the result carries ``preempted=True`` (resumable).
      Multi-host, the flag is allgathered every
      ``cfg.preempt_poll_steps`` steps so the collective save is entered
      by everyone or no one — keep poll_steps x step_time inside the
      fleet's preemption grace window.
    - **Divergence rollback** — ``cfg.nan_policy="rollback"`` turns the
      NaN guard's ``FloatingPointError`` into: restore the newest
      *finite* checkpoint, rebuild the input pipeline at its exact
      cursor, replay, and — when the replay reaches the offending chunk
      — advance the cursor exactly past its batches (skip counted in
      ``train/skipped_batches``), bounded by ``cfg.rollback_budget``.
    - **Watchdog** — ``cfg.watchdog_timeout_s`` starts a progress
      watchdog diagnosing silent stalls (hung collective / pipeline
      deadlock) instead of letting them look like slow steps.
    - **Chaos** — ``cfg.chaos`` (off by default) injects deterministic
      faults at these exact seams (``resilience/chaos.py``), including
      the cross-host kill/visibility-skew/straggler drills.
    - **Multi-host coordination** — every fleet-visible checkpoint
      decision (save skip/replace, restore-walk step pick,
      restore-vs-init, the rollback's any-host divergence verdict) is
      chief-decided via ``resilience/consensus.py`` so storage
      visibility skew cannot de-sync the fleet; under a fleet
      supervisor (``launch.py``) each process heartbeats
      (``resilience/heartbeat.py``) and the chief exports ``fleet/*``
      gauges.
    """
    if cfg.nan_policy not in ("abort", "rollback"):
        raise ValueError(
            f"nan_policy must be 'abort' or 'rollback', got {cfg.nan_policy!r}"
        )
    t_run0 = time.perf_counter()
    registry = telemetry.MetricsRegistry()
    registry.counter(telemetry.RESTARTS).inc(restarts)
    # Pre-create the other resilience counters (CKPT_FENCE precedent,
    # checkpoint.py): a run that never rolled back must say so with an
    # explicit zero in telemetry.json — absence is indistinguishable
    # from the emission path silently breaking, and the schema lint's
    # declared-coverage check rightly treats absence as a failure.
    registry.counter(telemetry.ROLLBACKS)
    registry.counter(telemetry.SKIPPED_BATCHES)
    # The attention op counts its route choices, and the fused LM head
    # its gradient-in-forward calls, in the process-global registry, at
    # trace time; the report gets what this run traced.
    traced0 = _trace_counts()
    # Structured event tracing + flight recorder (telemetry/trace.py,
    # README "Observability"): the run's tracer rides the registry, so
    # every component the registry already reaches (pipeline, step,
    # checkpoint, startup) records onto one wall-clock-stamped timeline.
    tracer = telemetry.Tracer(
        capacity=max(1, int(cfg.trace_ring_events or 0)),
        process_index=jax.process_index(),
        enabled=int(cfg.trace_ring_events or 0) > 0,
    )
    registry.trace = tracer
    # The start-up timeline (harness/startup.py, README "Observability"):
    # from here to the first loss row every ``startup.mark`` closes one
    # exclusive phase of this thread's time.
    startup = startuplib.Timeline(registry, t_run0)
    # Read by the flight-dump closure below at CALL time (a closure over
    # fit's local): dumps fired before the loop report the sentinel.
    step = -1

    def _dump_flight(reason: str) -> None:
        """Dump the ring + registry to ``flight_recorder_p<i>.json``.
        Called on every abnormal exit (rollback, preemption, crash, the
        chaos kill's pre-SIGKILL hook, and the signal watcher's
        at-arrival dump).  Best-effort: forensics must never be the
        thing that fails training."""
        if not cfg.flight_recorder or not tracer.enabled:
            return
        try:
            os.makedirs(workdir, exist_ok=True)
            tracer.dump_flight_record(
                telemetry.flight_record_path(workdir, tracer.process_index),
                reason,
                registry,
                extra={"step": step},
            )
        except Exception:  # noqa: BLE001
            log.exception("flight-record dump (%s) failed", reason)

    tracer.instant("fit/entry", {"config": cfg.name, "restarts": restarts})
    # Production compile cache, applied before build_state — whose
    # model.init is this run's first trace (README "Performance";
    # restart-MTTR: a relaunch deserializes instead of recompiling).
    startuplib.apply_compile_cache(cfg.xla_cache_dir)
    chaos = resilience.get_injector(cfg.chaos, seed=cfg.seed, scope=workdir)
    if chaos is not None:
        # (Re)wire the memoized injector to THIS run's forensics: fires
        # land on the timeline, and the kill fault dumps before SIGKILL.
        chaos.tracer = tracer
        chaos.flight_dump = _dump_flight
    if mesh is None:
        mesh = mesh_from_config(cfg)
    state = build_state(cfg, mesh)
    startup.mark(telemetry.STARTUP_BUILD_STATE)
    manager = ckptlib.CheckpointManager(
        workdir,
        keep=cfg.keep_checkpoints,
        registry=registry,
        # Chaos visibility-skew simulation: the hidden step vanishes
        # from this process's listings, never from reads — the manager's
        # chief-decides consensus is what keeps the fleet in agreement.
        step_filter=chaos.step_filter() if chaos is not None else None,
    )
    # Every fleet-visible decision (save skip/replace, restore-walk step
    # pick, restore-vs-init, any-host divergence below) goes through
    # this chief-decides broadcast; single-process it is an exact no-op.
    consensus = manager.consensus

    seq_dim = (
        1
        if cfg.task == "lm" and mesh.shape[meshlib.AxisNames.SEQ] > 1
        else None
    )
    steps_per_loop = max(1, int(cfg.steps_per_loop))

    raw_step = None
    aot = None
    try:
        # The checkpoint manager is live from here (and the AOT thread
        # shortly after): a step-build/restore/dataset failure must reap
        # both rather than leak them into the caller (recoverable_fit
        # may re-enter fit on the same workdir right away).
        #
        # The step program is built from the TEMPLATE state, before the
        # restore (cheap closure work — no tracing; the loss depends
        # only on apply_fn, which restore never changes), so the AOT
        # compiler can lower the very jit callable the loop will drive
        # *while* the restore reads the checkpoint — a relaunch overlaps
        # its two dominant serial costs (README "Performance").
        if steps_per_loop > 1:
            step_jit, raw_step = build_multi_step(cfg, state)
        else:
            step_jit = build_step(cfg, state)
        aot = _start_aot_compile(
            cfg, state, mesh, seq_dim, steps_per_loop, step_jit, registry
        )

        resilience.heartbeat.set_phase("restore")
        startup.mark(telemetry.STARTUP_BUILD_STEP)
        state, data_state, restored = ckptlib.restore_or_init(manager, state)
        if restored:
            state = place(cfg, state, mesh)
        if restored and manager.last_resize is not None:
            # Crossing a fleet resize is incident-grade: drop a flight
            # record on EVERY host so both sides of the crossing are
            # reconstructable from the recorder alone, and put the
            # resize facts on this host's timeline.
            tracer.instant("fit/resize_restore", dict(manager.last_resize))
            _dump_flight("resize_restore")
        # Startup restore wall (incl. the re-placement): one of the
        # restart-MTTR terms the goodput report's "startup" section
        # carries.
        startup.mark(telemetry.STARTUP_RESTORE)
        tracer.instant(
            "fit/restore_done",
            {"restored": restored, "step": int(state.step)},
        )
        # "compile" until the first chunk completes: the gap between
        # restore-done and first-step is where the (possibly AOT-hidden)
        # XLA compile lives, and a heartbeat frozen here says so.
        resilience.heartbeat.set_phase("compile")

        dataset = build_dataset(cfg, "train")
        if restored and data_state.get("dataset") and hasattr(
            dataset, "set_state"
        ):
            dataset.set_state(data_state["dataset"])
        if chaos is not None:
            dataset = chaos.wrap_dataset(dataset)
        startup.mark(telemetry.STARTUP_DATASET)
    except BaseException:
        _close_quietly(None, manager, aot)
        _dump_flight("setup_failure")
        _unwire_chaos_forensics(chaos)
        raise

    host = device_it = stacker = data_src = None

    def _open_pipeline() -> None:
        # One place builds the input stack so the rollback path can
        # rebuild it at a restored cursor bit-identically to fit entry.
        nonlocal host, device_it, stacker, data_src
        host = pipelib.HostPipeline(
            dataset,
            prefetch=4,
            num_workers=max(1, int(cfg.data_workers)),
            registry=registry,
        )
        device_it = pipelib.DevicePrefetcher(
            host, mesh, depth=2, seq_dim=seq_dim, registry=registry
        )
        if steps_per_loop > 1:
            # Fused multi-step dispatch: stack K sharded batches per chunk
            # and run them through one jitted lax.scan program — one
            # dispatch, one hook-gated walk set, one metrics transfer per
            # chunk.
            stacker = pipelib.BatchStacker(device_it)
            data_src = stacker
        else:
            stacker = None
            data_src = device_it

    own_listener = listener is None
    if own_listener:
        listener = resilience.PreemptionListener()
    fwatch: Optional[telemetry.FlightWatcher] = None

    def _final_dump(reason: str) -> None:
        """The terminal flight dump: stop the signal watcher FIRST so a
        starved watcher thread cannot resume later and overwrite this
        fuller record with its thinner at-arrival one (`signal_N` over
        `preempted`) — the watcher's value ends the moment the graceful
        path is known to run."""
        if fwatch is not None:
            fwatch.stop()
        _dump_flight(reason)

    try:
        # The pipeline threads start inside this block, and the rest
        # of the setup below it can fail for real reasons (a hook
        # constructor hitting an unwritable workdir, a bad fused-step
        # build) — any such failure must tear the pipeline and the
        # checkpoint manager down instead of leaking a producer
        # thread blocked forever on its full buffer.
        _open_pipeline()
        if steps_per_loop > 1:
            step_fn = train_loop.InstrumentedMultiStep(
                step_jit, raw_step, registry=registry, aot=aot
            )
        else:
            step_fn = train_loop.InstrumentedStep(
                step_jit, registry=registry, aot=aot
            )

        def save_fn(s, _step, *, force: bool = False):
            # Use the consuming stage's view of the dataset position — the
            # device prefetcher (or, chunked, the batch stacker in front of
            # it) lags the host pipeline by the prefetch depth and reflects
            # exactly the batches the train loop has consumed, so resume
            # never skips.
            prev_phase = resilience.heartbeat.set_phase("save")
            try:
                manager.save(s, {"dataset": data_src.get_state()}, force=force)
                if chaos is not None and chaos.should_tear(int(s.step)):
                    # Chaos torn-write injection damages only *durable*
                    # files — wait for the async save so the tear is the
                    # post-finalization corruption the restore hardening
                    # exists for.
                    manager.wait()
                    chaos.tear_checkpoint(manager.directory, int(s.step))
            finally:
                if prev_phase:
                    resilience.heartbeat.set_phase(prev_phase)

        # Writer hooks run on process 0 only (the reference's chief-writes-
        # summaries convention, TF monitored_session.py:566-609); the NaN guard
        # runs everywhere so all processes abort together (metrics are global,
        # identical on every process); the checkpoint hook runs everywhere —
        # orbax saves are collective.
        is_chief = jax.process_index() == 0
        chief_hooks: list[hooklib.Hook] = (
            [
                hooklib.StepCounterHook(
                    cfg.log_every_steps, cfg.global_batch_size
                ),
                hooklib.LoggingHook(cfg.log_every_steps, keys=("loss",)),
                hooklib.MetricWriterHook(workdir, cfg.log_every_steps),
                hooklib.TensorBoardHook(workdir, cfg.log_every_steps),
            ]
            if is_chief
            else []
        )
        # Preemption grace: flag-setting signal handlers for the life of the
        # run (released in the finally below).  ``recoverable_fit`` passes a
        # listener spanning its whole retry loop, so a notice received in one
        # attempt (or during a backoff sleep) is not forgotten by the next;
        # standalone fit owns its own.  Install is a no-op off the main
        # thread — such a caller simply never observes a preemption.
        listener_active = listener.install()
        if listener_active and cfg.flight_recorder and tracer.enabled:
            # At-arrival forensics: a SIGTERM'd host wedged in a dead
            # peer's collective never reaches its chunk-boundary poll
            # (or any graceful dump) before the supervisor's SIGKILL —
            # the watcher dumps the flight record the moment the signal
            # lands, off the wakeup fd, main thread blocked or not.
            fwatch = telemetry.FlightWatcher(_dump_flight)
            if not fwatch.install():
                fwatch = None

        chaos_hooks: list[hooklib.Hook] = []
        if chaos is not None:
            sigterm_hook = chaos.sigterm_hook()
            if sigterm_hook is not None:
                if listener_active:
                    chaos_hooks.append(sigterm_hook)
                else:
                    # Without the handler a raised SIGTERM is a hard kill —
                    # the drill would demonstrate an ungraceful death
                    # instead of proving the graceful path.
                    log.warning(
                        "chaos sigterm_at_step disabled: preemption listener "
                        "inactive (fit not on the main thread)"
                    )
            tear_hook = chaos.tear_hook(save_fn, final_step=cfg.train_steps)
            if tear_hook is not None:
                chaos_hooks.append(tear_hook)
            kill_hook = chaos.kill_hook()
            if kill_hook is not None:
                chaos_hooks.append(kill_hook)
            straggler_hook = chaos.straggler_hook()
            if straggler_hook is not None:
                chaos_hooks.append(straggler_hook)
        nproc = jax.process_count()
        # Fleet-health gauges (chief only): peers alive / step lag /
        # heartbeat age, read from the launcher's heartbeat directory —
        # plain file reads, present exactly when a supervisor started us
        # with heartbeats on (launch.py sets DTM_HEARTBEAT_DIR).
        hb_writer = resilience.heartbeat.active_writer()
        fleet_hooks: list[hooklib.Hook] = (
            [
                hooklib.FleetHook(
                    registry, hb_writer.directory, nproc,
                    cfg.log_every_steps,
                )
            ]
            if is_chief and nproc > 1 and hb_writer is not None
            else []
        )
        preempt_poll_steps = max(
            1, int(cfg.preempt_poll_steps or PREEMPT_POLL_STEPS)
        )
        all_hooks: list[hooklib.Hook] = [
            hooklib.StopAtStepHook(cfg.train_steps),
            # Before the chief writer hooks: TelemetryHook injects its derived
            # scalars (data_wait_s, step_time_s, mfu, ...) into the metrics
            # dict for the writers to record.  Runs on every process — its
            # multi-host aggregation is a collective.
            hooklib.TelemetryHook(registry, cfg.log_every_steps),
            *fleet_hooks,
            *chief_hooks,
            hooklib.NanGuardHook(cfg.log_every_steps),
            hooklib.CheckpointHook(
                save_fn,
                every_secs=cfg.checkpoint_every_secs,
                every_steps=cfg.checkpoint_every_steps,
            ),
            *chaos_hooks,
            *extra_hooks,
            # Multi-host only: align fused-chunk boundaries with the
            # preemption-poll steps (the poll is a collective).
            *(
                [_PreemptPollHook(preempt_poll_steps)] if nproc > 1 else []
            ),
        ]

        def _preempt_due(step: int) -> bool:
            if nproc == 1:
                return listener.preempted
            if step % preempt_poll_steps:
                return False
            from jax.experimental import multihost_utils

            import numpy as np

            flags = np.asarray(
                multihost_utils.process_allgather(
                    np.asarray(listener.preempted, np.int32)
                )
            )
            return bool(flags.max())

        rng = jax.random.key(cfg.seed + 1)
        metrics = {}
        steps_run = 0
        preempted = False
        rollbacks_done = 0
        skipped_total = 0
        # Rollback bookkeeping.  pending: [step, n] — when the (replayed)
        # loop reaches ``step``, discard the next ``n`` batches (the offending
        # chunk's).  executed: skips already performed, re-scheduled if a
        # later rollback rewinds behind them (their batches are back in the
        # stream).
        pending_skips: list[list[int]] = []
        executed_skips: list[tuple[int, int]] = []
        step = int(state.step)

    except BaseException:
        if fwatch is not None:
            fwatch.stop()
        if own_listener:
            listener.uninstall()  # no-op if install never ran
        _close_quietly(host, manager, aot)
        _dump_flight("setup_failure")
        _unwire_chaos_forensics(chaos)
        raise

    watchdog = None
    try:
        # Everything that can raise between handler install and the main
        # loop's finally runs guarded — a bad watchdog timeout, a hook's
        # begin() failing, or the anchor save hitting dead storage must
        # not leak the replaced signal handlers / watchdog thread into
        # the caller.
        if cfg.watchdog_timeout_s:
            watchdog = resilience.ProgressWatchdog(
                cfg.watchdog_timeout_s,
                registry=registry,
                abort=cfg.watchdog_abort,
            )
        for h in all_hooks:
            h.begin(state)
        if cfg.nan_policy == "rollback" and not restored:
            # Rollback needs a restore anchor even before the first
            # scheduled save: bank the initial state (once, cheap) so a
            # divergence in the first cadence window has somewhere to
            # rewind to.  Gated on ``not restored`` — not on
            # latest_step() — because the fresh-init fallback (torn
            # checkpoints present but nothing restorable) also needs the
            # anchor.  Explicitly fenced: saves are overlapped
            # (dispatch-only) on the step path, but the anchor must be
            # DURABLE before training can diverge past it — an async
            # anchor lost to a crash would leave the first cadence
            # window with nothing to rewind to.
            save_fn(state, step, force=True)
            manager.wait()
    except BaseException:
        if watchdog is not None:
            watchdog.stop()
        if fwatch is not None:
            fwatch.stop()
        if own_listener:
            listener.uninstall()
        # The pipeline threads and the checkpoint manager already exist at
        # this point — a setup failure must not leak them into the caller
        # (the producer would sit blocked on its full buffer forever).
        _close_quietly(host, manager, aot)
        _dump_flight("setup_failure")
        _unwire_chaos_forensics(chaos)
        raise

    # Sentinel for "no divergence seen here" in the any-host agreement
    # below (min-reduced, so it must exceed any real step while fitting
    # the consensus layer's int32 wire).
    _NO_BAD_STEP = 2**31 - 1

    def _check_chunk_finite(loss_rows, chunk_start: int, n: int) -> None:
        """Rollback mode guards EVERY chunk, not only the NaN guard's
        log-cadence walks: the skip ledger's exactness rests on detection
        landing in the offending chunk — cadence-delayed detection would
        attribute the divergence to (and skip) an innocent later chunk
        while the real poison replays on every rewind until the budget
        dies.  Cost: one small device→host read per chunk, paid only
        under ``nan_policy="rollback"``.  Raised BEFORE the hook walk, so
        the checkpoint hook can never persist the poisoned state.

        Multi-host the verdict is **fleet-agreed** (one allgather per
        chunk, rollback mode only): any host seeing a non-finite loss
        makes EVERY host raise, at the earliest step any host saw — so
        the fleet enters ``_rollback``'s collectives together with one
        shared skip ledger, instead of trusting that every host's
        readback of the (nominally global) loss classifies the same
        way."""
        bad_step = _NO_BAD_STEP
        bad_value = None
        if loss_rows is not None:
            import numpy as np

            arr = np.atleast_1d(np.asarray(loss_rows))[:n]
            bad = ~np.isfinite(arr)
            if bad.any():
                i = int(np.argmax(bad))
                bad_step = chunk_start + 1 + i
                bad_value = arr[i]
        if consensus.active:
            agreed = min(
                consensus.allgather_int(bad_step, label="chunk-finite")
            )
            if agreed < _NO_BAD_STEP:
                tracer.instant(
                    "train/divergence",
                    {"step": agreed, "local": agreed == bad_step},
                )
                raise FloatingPointError(
                    f"loss is {bad_value if agreed == bad_step else 'non-finite on a peer'}"
                    f" at step {agreed} (fleet-agreed divergence)"
                )
        elif bad_step < _NO_BAD_STEP:
            tracer.instant(
                "train/divergence", {"step": bad_step, "local": True}
            )
            raise FloatingPointError(
                f"loss is {bad_value} at step {bad_step}"
            )

    def _discard_batches(n: int) -> int:
        """Advance the consuming stage exactly ``n`` batches (the rollback
        skip).  Pulled through the normal stages so the resume-exact state
        rides along and the next checkpoint names the post-skip cursor."""
        done = 0
        with registry.span(telemetry.DATA_WAIT):
            if stacker is not None:
                try:
                    _, done = stacker.next_chunk(n)
                except StopIteration:
                    pass
            else:
                for _ in range(n):
                    try:
                        next(device_it)
                    except StopIteration:
                        break
                    done += 1
        return done

    def _rollback(offender_start: int, offender_len: int) -> bool:
        """Restore the newest finite checkpoint and schedule the exact
        skip of the offending chunk (steps ``offender_start+1 ..
        offender_start+offender_len``).  False = no usable restore point
        (caller re-raises the divergence error)."""
        nonlocal state, step
        try:
            host.stop(raise_pending=False)
        except Exception:  # noqa: BLE001 — teardown must not mask recovery
            log.exception("pipeline teardown during rollback failed")
        manager.wait()
        try:
            # The hardened walk-back (torn/unrestorable candidates
            # skipped) plus a finiteness gate: a clock-due save can land
            # at a walk the NaN guard's cadence skipped — after
            # divergence began — and restoring it would replay the poison.
            restored_state, restored_data = manager.restore_newest_valid(
                state,
                accept=train_loop.state_is_finite,
                accept_name="non-finite parameters",
            )
        except FileNotFoundError as e:  # incl. NoValidCheckpointError
            log.error("rollback: no finite checkpoint to restore (%s)", e)
            return False
        state = place(cfg, restored_state, mesh)
        step = int(state.step)
        # Delete the abandoned timeline's checkpoints (anything newer
        # than the restore point): they hold post-divergence state that
        # must never be auto-resumed, and leaving them would shadow the
        # replay's own saves at the same steps (save() skips existing
        # steps by design).
        for stale in manager.all_steps():
            if stale > step:
                log.warning(
                    "rollback: deleting post-divergence checkpoint at "
                    "step %d", stale,
                )
                manager.delete(stale)
        if restored_data.get("dataset") and hasattr(dataset, "set_state"):
            dataset.set_state(restored_data["dataset"])
        _open_pipeline()
        # Re-schedule every skip the rewind re-exposed, plus the new
        # offender; dedup by step, keeping the widest span.
        wanted = {s: n for s, n in executed_skips if s >= step}
        for s, n in pending_skips:
            wanted[s] = max(wanted.get(s, 0), n)
        if offender_start >= step:
            wanted[offender_start] = max(
                wanted.get(offender_start, 0), offender_len
            )
        else:  # only reachable via exotic extra_hooks save ordering
            log.warning(
                "rollback: restored step %d is past the offending chunk "
                "at %d; nothing to skip", step, offender_start,
            )
        pending_skips[:] = sorted([s, n] for s, n in wanted.items())
        log.warning(
            "rollback: restored step %d; will skip the offending chunk "
            "(steps %d..%d) on replay",
            step, offender_start + 1, offender_start + offender_len,
        )
        # The rollback's span on the timeline runs from the divergence
        # instant (train/divergence) through the restore spans to this
        # marker — fleet_report reads the pair as the rollback window.
        tracer.instant(
            "train/rollback",
            {
                "restored_step": step,
                "offender_start": offender_start,
                "offender_len": offender_len,
            },
        )
        if watchdog is not None:
            watchdog.beat(step)
        return True

    try:
        # First beat carries the (possibly restored) entry step, so the
        # supervisor and peers see "looping, at step N" before the first
        # chunk — which may take a full XLA compile — completes.
        resilience.heartbeat.beat(step)
        startup.mark(telemetry.STARTUP_PIPELINE_OPEN)
        while step < cfg.train_steps:
            if _preempt_due(step):
                log.warning(
                    "preemption: writing emergency checkpoint at step %d "
                    "and exiting (resumable — rerun the same command)",
                    step,
                )
                tracer.instant("train/preempted", {"step": step})
                save_fn(state, step, force=True)
                # Explicit durability fence: the process is about to
                # exit on the preemption notice — the overlapped
                # (dispatch-only) save contract does not cover "the
                # supervisor may SIGKILL us the moment we return".
                manager.wait()
                preempted = True
                # The preemption forensics record: the grace path ran,
                # the emergency save is durable — replaces the signal
                # watcher's at-arrival dump with the full story (the
                # watcher is stopped first so it cannot win the race).
                _final_dump("preempted")
                break
            while pending_skips and pending_skips[0][0] <= step:
                skip_at, n = pending_skips.pop(0)
                if skip_at < step:
                    # Defensive: the skip's boundary was overshot (should
                    # not happen — chunks are capped at pending skips
                    # below); skipping NOW would discard the wrong
                    # batches, so drop the entry rather than jam the
                    # queue or corrupt the stream.
                    log.warning(
                        "rollback: scheduled skip at step %d overshot "
                        "(loop is at %d); dropping it", skip_at, step,
                    )
                    continue
                done = _discard_batches(n)
                skipped_total += done
                registry.counter(telemetry.SKIPPED_BATCHES).inc(done)
                tracer.instant(
                    "train/skip_batches", {"step": step, "n": done}
                )
                executed_skips.append((step, done))
                log.warning(
                    "rollback: advanced the dataset cursor past %d "
                    "offending batch(es) at step %d", done, step,
                )
                # Refresh the rollback forensics now that the recovery's
                # final act (the exact skip) is on the timeline — the
                # dump written at rewind time predates it.
                _dump_flight("rollback")
            start = step
            t_iter = time.perf_counter()
            k = 0
            try:
                if stacker is None:
                    with registry.span(telemetry.DATA_WAIT):
                        batch = next(device_it)
                    k = 1
                    if chaos is not None:
                        batch = chaos.poison_batch(batch, start + 1, 1)
                    state, metrics = step_fn(state, batch, rng)
                    if cfg.nan_policy == "rollback":
                        _check_chunk_finite(metrics.get("loss"), start, 1)
                    registry.timer(telemetry.STEP_TIME).record(
                        time.perf_counter() - t_iter
                    )
                    step = start + 1
                    steps_run += 1
                    registry.counter(telemetry.HOOK_WALKS).inc()
                    t_hooks = time.perf_counter()
                    try:
                        ok = hooklib.run_hooks_after_step(
                            all_hooks, state, metrics, step
                        )
                    finally:
                        registry.record_since(telemetry.HOOKS, t_hooks)
                else:
                    k_req = _chunk_len(start, cfg, all_hooks)
                    if pending_skips and pending_skips[0][0] > start:
                        # A chunk is one atomic device program, so the
                        # only way to execute a scheduled skip at its
                        # exact step — replay chunk boundaries are not
                        # guaranteed to reproduce the original run's
                        # (clock-due hooks) — is to end the chunk there.
                        k_req = min(k_req, pending_skips[0][0] - start)
                    with registry.span(telemetry.DATA_WAIT):
                        chunk, k = stacker.next_chunk(k_req)
                    if chaos is not None:
                        chunk = chaos.poison_batch(chunk, start + 1, k)
                    state, rows = step_fn(state, chunk, rng)
                    if cfg.nan_policy == "rollback":
                        _check_chunk_finite(rows.get("loss"), start, k)
                    # Chunk wall ÷ K, recorded once per STEP (k records):
                    # the timer's count stays the step count and its total
                    # the loop wall, so TelemetryHook's per-record mean is
                    # not chunk-weighted when chunk lengths mix (a K=8
                    # chunk and its K=2 boundary tail would otherwise
                    # average 50/50) and step_time_s stays comparable
                    # across steps_per_loop values.  k sub-µs records per
                    # chunk — off the hot path.
                    per_step = (time.perf_counter() - t_iter) / k
                    step_timer = registry.timer(telemetry.STEP_TIME)
                    for _ in range(k):
                        step_timer.record(per_step)
                    step = start + k
                    steps_run += k
                    # The latest metrics row, lazily — FitResult
                    # materialises it only at return.  Passed as final_row
                    # so TelemetryHook's injected scalars land on THIS
                    # object when the last row is walked (final_metrics
                    # parity with the unfused loop).
                    metrics = hooklib.LazyMetricRow(rows, k - 1, start + 1)
                    t_hooks = time.perf_counter()
                    try:
                        ok = hooklib.run_hooks_after_chunk(
                            all_hooks, state, rows, start, k,
                            registry=registry, final_row=metrics,
                        )
                    finally:
                        registry.record_since(telemetry.HOOKS, t_hooks)
            except FloatingPointError:
                # The NaN guard's divergence signal.  Policy "abort"
                # (default) keeps the reference behavior: propagate.
                if cfg.nan_policy != "rollback" or k == 0:
                    raise
                if rollbacks_done >= cfg.rollback_budget:
                    log.error(
                        "rollback budget (%d) exhausted; aborting",
                        cfg.rollback_budget,
                    )
                    raise
                # _check_chunk_finite's verdict is fleet-agreed (one
                # allgather per chunk): any host's non-finite loss makes
                # EVERY host raise on the same chunk, so the fleet enters
                # this handler together and the rollback's collectives
                # stay matched.  Fleet-uniform by construction:
                # dtmlint: disable=collective-order
                if not _rollback(start, k):
                    raise
                # Counted only when a rewind actually happened, so the
                # counter equals restores performed even on exhaustion.
                rollbacks_done += 1
                registry.counter(telemetry.ROLLBACKS).inc()
                # Rollback forensics land even though the run survives:
                # the drill (or incident) is reconstructable from the
                # dump whether or not the replay later succeeds.
                _dump_flight("rollback")
                continue
            if tracer.enabled:
                # One complete event per chunk (train/data_wait +
                # train/dispatch + train/hooks + a remainder):
                # the step-progress series fleet_report's skew/straggler
                # attribution is computed from.
                tracer.complete(
                    "train/chunk",
                    time.perf_counter() - t_iter,
                    ts_mono=t_iter,
                    args={"start": start, "k": k},
                )
            if steps_run and registry.gauge(
                telemetry.STARTUP_FIRST_LOSS_ROW
            ).value == 0.0:
                # Start-up lasts until the first loss row; this test is
                # the loop's one check for it.
                if registry.gauge(telemetry.STARTUP_FIRST_STEP).value == 0.0:
                    # Relaunch-to-first-step MTTR, the number the
                    # cold-start work (compile cache + AOT-overlapped
                    # restore) exists to shrink: fit entry → first
                    # completed chunk.
                    startup.first_chunk_done()
                    resilience.heartbeat.set_phase("train")
                if cfg.log_every_steps and step % cfg.log_every_steps == 0:
                    # The walk above ran the log-cadence hooks (chunks
                    # end at their steps), which fetched the loss.
                    startup.first_loss_row()
            if watchdog is not None:
                watchdog.beat(step)
            resilience.heartbeat.beat(step)
            if not ok:
                break
    except BaseException as e:
        # Already failing: run abort hooks best-effort (single-process, the
        # CheckpointHook crash-save preserves progress when storage still
        # works; multi-host it skips its collective save — see Hook.abort)
        # but never let cleanup mask the original error or skip releasing
        # the pipeline threads / checkpoint manager — recoverable_fit may
        # re-enter fit on the same workdir right after this.
        tracer.instant(
            "fit/abort", {"step": step, "error": repr(e)[:200]}
        )
        for h in all_hooks:
            try:
                h.abort(state)
            except Exception:
                log.exception("hook %r abort() failed during error cleanup", h)
        _close_quietly(host, manager, aot)
        # A goodput report from a crashed run is exactly what the
        # post-mortem wants (was it stalling before it died?).  The
        # armed-but-unfired chaos count rides along: a crash drill whose
        # fault never injected should say so in its post-mortem too.
        if chaos is not None:
            chaos.export_unfired(registry)
        # Crash forensics: the flight record holds the last events (the
        # abort hooks' checkpoint spans included) and the trace export /
        # trace gauges land before the goodput report snapshots them.
        _final_dump("crash")
        _export_trace(workdir, registry, cfg, step_fn)
        _write_telemetry_report(
            workdir, registry, t_run0, steps_run, traced0
        )
        raise
    else:
        # One hook's end() failing (e.g. a writer's close hitting ENOSPC)
        # must not starve later hooks — CheckpointHook.end's final save
        # runs last — nor the telemetry report.  The first error still
        # propagates after cleanup: a failed final save is not a success.
        end_error: Optional[BaseException] = None
        try:
            for h in all_hooks:
                try:
                    h.end(state)
                except BaseException as e:  # noqa: BLE001
                    log.exception("hook %r end() failed", h)
                    if end_error is None:
                        end_error = e
        finally:
            _close_quietly(host, manager, aot)
        # After close: the report's checkpoint split includes the final
        # save's wait-until-durable time.  chaos/armed_unfired is set
        # first so the gauge lands in the report's registry snapshot.
        if chaos is not None:
            chaos.export_unfired(registry)
        tracer.instant(
            "fit/end", {"steps_run": steps_run, "preempted": preempted}
        )
        _export_trace(workdir, registry, cfg, step_fn)
        _write_telemetry_report(
            workdir, registry, t_run0, steps_run, traced0
        )
        if chaos is not None and not preempted:
            # A drill whose fault never injected must not exit 0 looking
            # like a passed drill (a preempted run legitimately leaves
            # later-positioned faults unfired).
            chaos.warn_unfired()
        if end_error is not None:
            raise end_error
    finally:
        # Both exits: release the signal handlers (the caller's SIGINT
        # behavior must come back — unless the listener is owned by
        # recoverable_fit, which spans restarts), the watchdog thread,
        # the flight watcher (wakeup fd restored, thread joined), and
        # the memoized injector's forensics wiring (the closure pins the
        # ring + registry; a stale hook fire must not dump into a
        # finished run).
        if watchdog is not None:
            watchdog.stop()
        if fwatch is not None:
            fwatch.stop()
        if own_listener:
            listener.uninstall()
        _unwire_chaos_forensics(chaos)

    host_metrics = {k: float(v) for k, v in metrics.items()}
    if preempted:
        log.warning(
            "run preempted at step %d after an emergency checkpoint; "
            "resumable by rerunning the same command", step,
        )
    return FitResult(
        state=state,
        final_metrics=host_metrics,
        steps_run=steps_run,
        preempted=preempted,
        rollbacks=rollbacks_done,
        skipped_batches=skipped_total,
    )


def _unwire_chaos_forensics(chaos) -> None:
    """Detach a (memoized, process-lifetime) injector from a finished
    run's tracer/flight-dump closure — fit re-wires them at every
    entry."""
    if chaos is not None:
        chaos.tracer = None
        chaos.flight_dump = None


def _export_trace(
    workdir: str, registry: telemetry.MetricsRegistry, cfg, step_fn=None
) -> None:
    """Per-process, best-effort: stamp the ``trace/*`` gauges (so the
    goodput report's snapshot says how far the ring reached and how much
    it dropped) and — under ``cfg.trace_export`` — write the
    Chrome-trace JSON ``scripts/fleet_report.py`` merges across hosts
    and, beside it, the scope map of the step programs the loop ran
    (``step_scopes_p<i>.json``, telemetry/scopes.py): what a reader of a
    device trace needs to name the trace's instructions.  Runs on BOTH
    exit paths, after the loop and before the telemetry report
    snapshots."""
    tracer = registry.trace
    if not tracer.enabled:
        return
    try:
        registry.gauge(telemetry.TRACE_EVENTS).set(float(tracer.emitted))
        registry.gauge(telemetry.TRACE_DROPPED).set(float(tracer.dropped))
        if cfg.trace_export:
            os.makedirs(workdir, exist_ok=True)
            if step_fn is not None:
                cost = scopelib.write_step_scopes(
                    scopelib.step_scopes_path(workdir, tracer.process_index),
                    step_fn.executables,
                )
                if cost is not None:
                    tracer.instant("fit/step_scopes", cost)
            tracer.dump_chrome(
                telemetry.chrome_trace_path(workdir, tracer.process_index)
            )
    except Exception:  # noqa: BLE001 — reporting must never mask training
        log.exception("trace export failed")


def _trace_counts() -> dict[str, float]:
    """What the ops count at trace time (``attention(impl="auto")``'s
    routes, ``chunked_kda``'s, ``KDAMixer``'s two placements,
    ``chunked_gdn``'s, ``chunked_ssd``'s and ``selective_scan``'s traced
    calls, the fused head's gradient-in-forward calls, what the recomputed
    halves keep, routing plans and cores' results among it), as the
    process-global registry holds it now."""
    shared = telemetry.get_registry()
    return {
        name: shared.counter(name).value
        for name in (
            telemetry.ATTN_ROUTE_FUSED,
            telemetry.ATTN_ROUTE_BLOCKWISE,
            telemetry.KDA_ROUTE_KERNEL,
            telemetry.KDA_ROUTE_PLAIN,
            telemetry.KDA_MIXER_FUSED,
            telemetry.KDA_MIXER_PLAIN,
            telemetry.GDN_ROUTE_PLAIN,
            telemetry.SSD_ROUTE_KERNEL, telemetry.SSD_ROUTE_PLAIN,
            telemetry.SSCAN_ROUTE_KERNEL, telemetry.SSCAN_ROUTE_PLAIN,
            telemetry.UNEMBED_GRAD_IN_FORWARD,
            telemetry.REMAT_PRODUCTS_KEPT, telemetry.REMAT_BYTES_KEPT,
            telemetry.MOE_PLAN_KEPT, telemetry.REMAT_CORES_KEPT,
        )
    }


def _write_telemetry_report(
    workdir: str, registry: telemetry.MetricsRegistry,
    t_run0: float, steps_run: int, traced0: dict[str, float],
) -> None:
    """Chief-only, best-effort ``telemetry.json`` goodput report."""
    if jax.process_index() != 0:
        return
    try:
        for name, count in _trace_counts().items():
            registry.counter(name).inc(count - traced0[name])
        report = telemetry.goodput_report(
            registry, total_s=time.perf_counter() - t_run0, steps=steps_run
        )
        telemetry.write_report(
            os.path.join(workdir, "telemetry.json"), report
        )
        frac = report["fractions"]
        log.info(
            "goodput: compute %.1f%%, data stall %.1f%%, checkpoint "
            "%.1f%%, compile %.1f%% over %.1fs (%d compile events, "
            "mfu %.4f)",
            100 * frac["compute"], 100 * frac["data_stall"],
            100 * frac["checkpoint"], 100 * frac["compile"],
            report["total_s"], report["compile_events"], report["mfu"],
        )
    except Exception:  # noqa: BLE001 — reporting must never mask training
        log.exception("failed to write telemetry.json")


def _start_aot_compile(
    cfg, template, mesh, seq_dim, steps_per_loop, jit_fn, registry
):
    """Kick off the background AOT compile of the train-step program (the
    restore that follows overlaps it).  Never raises — AOT is an
    optimization; any setup failure logs and returns None, leaving the
    jit path exactly as it was."""
    if not cfg.aot_compile:
        return None
    try:
        batch = startuplib.abstract_batch(cfg, mesh, seq_dim)
        if batch is None:
            log.info(
                "aot_compile: batch structure unknown for dataset %r; "
                "staying on the lazy jit path", cfg.dataset,
            )
            return None
        label = "train-step"
        if steps_per_loop > 1:
            k = startuplib.dominant_chunk_len(cfg, jax.process_count())
            batch = startuplib.stacked_batch(batch, k)
            label = f"{k}-step chunk"
        # The same rng fit's loop will pass — only its aval matters.
        rng = jax.random.key(cfg.seed + 1)
        return startuplib.AotTrainStep(
            jit_fn,
            (template, batch, rng),
            registry=registry,
            cache_dir=startuplib.configured_cache_dir(),
            label=label,
        ).start()
    except Exception:  # noqa: BLE001 — never the thing that fails training
        log.warning(
            "aot_compile setup failed; continuing on the jit path",
            exc_info=True,
        )
        return None


def _close_quietly(host, manager, aot=None) -> None:
    # ``host`` is None when teardown runs before (or because) the
    # pipeline build itself failed.
    try:
        if host is not None:
            host.stop()
    except Exception:
        log.exception("host pipeline stop failed")
    finally:
        try:
            manager.close()
        except Exception:
            log.exception("checkpoint manager close failed")
        if aot is not None:
            # Reap the compile thread (an XLA compile cannot be
            # cancelled; an aborted fit must not hand a live thread back
            # to the caller).  Bounded: a pathological compile leaves a
            # daemon thread behind with a warning rather than wedging
            # teardown.
            try:
                aot.join(timeout=120.0)
            except Exception:
                log.exception("aot compile thread join failed")


def default_recoverable_errors() -> tuple[type[BaseException], ...]:
    """Failure classes worth restarting on — *transient* ones only: device
    runtime errors (the analogue of the AbortedError/UnavailableError set
    ``_RecoverableSession`` retries on, TF monitored_session.py:1261-1274)
    and connection/timeout failures to peers or storage.  Deliberately NOT
    blanket ``OSError``: a PermissionError or FileNotFoundError from a bad
    workdir is deterministic and retrying it would crash-loop.

    ``JaxRuntimeError`` is in the set but — only when ``recoverable_fit``
    uses this default set implicitly — additionally message-filtered by
    :func:`is_transient_error`: XLA raises the same class for deterministic
    failures (compile errors, OOM, donation misuse), which must propagate
    immediately rather than burn ``max_restarts`` restore-retrain cycles.
    Passing any explicit ``recover_on`` (including this very tuple) disables
    the filter — an explicit set is taken at its word."""
    return (ConnectionError, TimeoutError, jax.errors.JaxRuntimeError)


# Deny-list: JaxRuntimeError messages that are deterministic failures —
# retrying replays the identical failure ``max_restarts`` times (ADVICE r1).
# Everything NOT matched here is treated as transient: a preemption/peer
# failure with an unrecognized message must still be retried (losing a
# multi-host run beats a bounded wasted retry), mirroring how TF's
# _RecoverableSession retried broadly on session-level errors
# (monitored_session.py:1261-1274).  Compile failures are deterministic:
# a program the chip's compiler refuses is refused again on every retry.
_DETERMINISTIC_MARKERS = (
    "out of memory",
    "resource_exhausted",
    "donated buffer",
    "invalid_argument",
    "unimplemented",
    "compile error",
    "compile permanent error",
    "failed to compile",
)


def is_transient_error(e: BaseException) -> bool:
    """True if ``e`` looks preemption-like and is worth a restore-and-retry.

    Non-JAX errors in the recoverable set (ConnectionError, TimeoutError)
    are transient by type.  JaxRuntimeError is transient *unless* its
    message matches a known-deterministic failure class (compile error,
    OOM, donation misuse, invalid argument) — those propagate immediately
    instead of burning restore-retrain cycles (ADVICE r1)."""
    if not isinstance(e, jax.errors.JaxRuntimeError):
        return True
    msg = str(e).lower()
    return not any(m in msg for m in _DETERMINISTIC_MARKERS)


# The deterministic-jitter restart schedule moved to
# ``resilience/backoff.py`` so the fleet supervisor
# (``launch.supervise_local``, which never imports jax/harness) can
# share it; re-exported here because this is its historical home and
# ``recoverable_fit``'s callers reach it as ``trainlib.restart_backoff``.
restart_backoff = resilience.restart_backoff


def recoverable_fit(
    cfg: ExperimentConfig,
    workdir: str,
    *,
    max_restarts: int = 3,
    recover_on: tuple[type[BaseException], ...] | None = None,
    backoff_base_s: float = 1.0,
    backoff_max_s: float = 60.0,
    **fit_kwargs,
) -> FitResult:
    """``fit`` wrapped in the reference's session-recovery loop.

    ``_RecoverableSession`` catches preemption-class errors, recreates the
    session, and resumes from the last checkpoint (TF monitored_session.py:
    1238,1261-1274; workers re-poll via session_manager.py:419).  Here the
    equivalent is simply calling ``fit`` again: restore-or-init picks up the
    latest checkpoint — parameters, optimizer state, EMA, step, and the
    input-pipeline position — so no progress is lost beyond the last save.
    Bounded by ``max_restarts`` to avoid crash-looping on deterministic
    failures (e.g. a NaN guard trip, which is *not* in the recoverable set),
    and spaced by :func:`restart_backoff` so a flapping fault is retried
    on a widening, jittered schedule instead of a hot crash-loop.

    A ``preempted`` result returns as-is (no restart): the process was
    told to die — the emergency checkpoint makes the *next invocation*
    the resume, not this one.  The attempt count is threaded into each
    ``fit`` as the ``train/restarts`` counter, so the final attempt's
    ``telemetry.json`` records how many restore-retrain cycles the run
    burned.
    """
    # The message filter guards only the *default* set, where JaxRuntimeError
    # is too broad a class; an explicit recover_on is taken at its word so
    # callers can opt into retrying message shapes the filter doesn't know.
    filter_messages = recover_on is None
    if recover_on is None:
        recover_on = default_recoverable_errors()
    # One listener spans ALL attempts (threaded into each fit): a
    # preemption notice received in attempt N — or during a backoff
    # sleep, which would otherwise run under the default (fatal) SIGTERM
    # handler — is still honored by attempt N+1, which emergency-saves
    # and returns preempted at its first boundary.
    listener = resilience.PreemptionListener()
    listener.install()
    attempt = 0
    try:
        while True:
            try:
                # steps_run counts the final (successful) attempt;
                # overall progress is state.step, which spans attempts
                # via checkpoints.
                return fit(
                    cfg, workdir, restarts=attempt, listener=listener,
                    **fit_kwargs,
                )
            except recover_on as e:
                if filter_messages and not is_transient_error(e):
                    raise
                attempt += 1
                if attempt > max_restarts:
                    raise
                delay = restart_backoff(
                    attempt,
                    base_s=backoff_base_s,
                    max_s=backoff_max_s,
                    seed=cfg.seed,
                )
                log.warning(
                    "fit failed (%s: %s); restart %d/%d from latest "
                    "checkpoint in %.2fs",
                    type(e).__name__,
                    e,
                    attempt,
                    max_restarts,
                    delay,
                )
                # Don't sleep out the grace period: skip the backoff
                # when a notice is already pending, and wake immediately
                # if one arrives mid-wait (listener.wait, not
                # time.sleep — PEP 475 would resume the sleep) so the
                # next attempt can emergency-save and exit resumable.
                if delay > 0 and not listener.preempted:
                    listener.wait(delay)
    finally:
        listener.uninstall()
