"""Training hooks: the reference's session-hook set, step-callback style.

The reference orchestrates its train loop through ``SessionRunHook``s
(SURVEY.md §2.2 F13; TF basic_session_run_hooks.py): StepCounterHook
(steps/sec), NanTensorHook, StopAtStepHook, LoggingTensorHook,
SummarySaverHook, CheckpointSaverHook.  Here the loop is a plain Python
``for`` over a compiled step, so hooks are simple objects with
``begin/after_step/end`` callbacks — same capabilities, same metric names
and cadences, no graph machinery.

Metric readback note: ``after_step`` receives the *device* metrics dict;
hooks that need host floats call ``float(...)`` themselves, and only on the
steps where they fire, so the hot loop never forces a sync on quiet steps.
"""

from __future__ import annotations

import json
import logging
import os
import time
from collections.abc import MutableMapping
from typing import Any, Iterator, Mapping, Optional, Sequence

import jax
import numpy as np

from distributed_tensorflow_models_tpu import telemetry

log = logging.getLogger("dtm")

Metrics = Mapping[str, Any]


class Hook:
    def begin(self, state) -> None: ...

    def wants_step(self, step: int) -> bool:
        """Does this hook need :meth:`after_step` called at ``step``?

        The fused multi-step loop (``fit`` with ``steps_per_loop > 1``)
        consults this to skip whole hook walks on steps where no hook
        would act — the host-overhead amortisation the fused dispatch
        exists for.  Returning ``True`` is always safe (the unfused loop
        never asks); the default keeps per-step semantics for arbitrary
        user hooks.  Must be cheap, side-effect-free, and — for hooks
        whose ``after_step`` performs a multi-host collective —
        deterministic in ``step`` so every process walks the same rows.
        """
        return True

    def after_step(self, state, metrics: Metrics, step: int) -> None: ...

    def end(self, state) -> None: ...

    def abort(self, state) -> None:
        """Cleanup on the *failure* path.  Defaults to :meth:`end`; hooks
        whose ``end`` performs a multi-host collective must override this —
        a single failing process entering a collective while its peers are
        blocked elsewhere turns a clean per-process error into a
        cluster-wide hang."""
        self.end(state)


class LazyMetricRow(MutableMapping):
    """One step's lazy view into a fused chunk's stacked on-device metrics.

    The fused multi-step program returns every metric as a ``[K]``-stacked
    device array; materialising K host dicts per chunk would reintroduce
    the per-step host cost the fusion removed.  This row adapter indexes a
    leaf only when a hook actually reads the key (the result is still a
    device scalar — only ``float()`` forces the device→host sync), so
    hooks that fire every N steps never sync the other N−1 rows.

    Writes (``TelemetryHook``'s derived-scalar injection) land in a
    host-side overlay that shadows the stacked leaves — the same
    dict-update contract the writer hooks rely on.

    Chunk-aware consumers (``NanGuardHook``) can reach the whole chunk via
    :meth:`stacked` plus :attr:`chunk_start_step`/:attr:`index` to
    attribute a mid-chunk event to its exact step.
    """

    def __init__(self, stacked: Mapping, index: int, chunk_start_step: int):
        self._stacked = stacked
        self._index = index
        self._start = chunk_start_step  # global step of row 0
        self._overlay: dict = {}

    @property
    def index(self) -> int:
        return self._index

    @property
    def chunk_start_step(self) -> int:
        return self._start

    def stacked(self, key: str):
        """The full ``[K]`` device array behind ``key`` (raises KeyError
        for overlay-only keys, which have no per-step history)."""
        return self._stacked[key]

    def __getitem__(self, key):
        if key in self._overlay:
            return self._overlay[key]
        return self._stacked[key][self._index]

    def __setitem__(self, key, value):
        self._overlay[key] = value

    def __delitem__(self, key):
        del self._overlay[key]

    def __iter__(self) -> Iterator[str]:
        yield from self._stacked
        for k in self._overlay:
            if k not in self._stacked:
                yield k

    def __len__(self) -> int:
        return len(set(self._stacked) | set(self._overlay))


class StopRequested(Exception):
    """Raised by hooks to end training (StopAtStepHook's mechanism)."""


class StopAtStepHook(Hook):
    """Stop after ``last_step`` (TF basic_session_run_hooks.py:393)."""

    def __init__(self, last_step: int):
        self._last = last_step

    def wants_step(self, step):
        return step >= self._last

    def after_step(self, state, metrics, step):
        if step >= self._last:
            raise StopRequested


class StepCounterHook(Hook):
    """steps/sec (and examples/sec) every ``every_steps`` — the reference's
    throughput meter (TF basic_session_run_hooks.py:674)."""

    def __init__(self, every_steps: int = 100, batch_size: Optional[int] = None):
        self._every = every_steps
        self._batch = batch_size
        self._t0 = None
        self._s0 = 0
        self.last_steps_per_sec: Optional[float] = None

    def begin(self, state):
        self._t0 = time.perf_counter()
        self._s0 = int(state.step)

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if step % self._every:
            return
        now = time.perf_counter()
        dt = now - self._t0
        if dt <= 0:
            return
        sps = (step - self._s0) / dt
        self.last_steps_per_sec = sps
        msg = f"step {step}: {sps:.2f} steps/sec"
        if self._batch:
            msg += f", {sps * self._batch:.1f} examples/sec"
        log.info(msg)
        self._t0, self._s0 = now, step


class NanGuardHook(Hook):
    """Abort on non-finite loss (NanTensorHook, TF
    basic_session_run_hooks.py:761).  Checks every ``every_steps`` to avoid
    forcing a device sync each step."""

    def __init__(self, every_steps: int = 100, key: str = "loss"):
        self._every = every_steps
        self._key = key

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if step % self._every:
            return
        if isinstance(metrics, LazyMetricRow):
            # Fused-chunk row: check EVERY row of the chunk up to this one
            # (one [K]-array readback — same sync cost as the scalar) so a
            # mid-chunk NaN is caught at the boundary walk and attributed
            # to its exact step, not the chunk end.
            arr = np.asarray(metrics.stacked(self._key))[
                : metrics.index + 1
            ]
            bad = ~np.isfinite(arr)
            if bad.any():
                i = int(np.argmax(bad))
                raise FloatingPointError(
                    f"{self._key} is {arr[i]} at step "
                    f"{metrics.chunk_start_step + i}"
                )
            return
        value = float(metrics[self._key])
        if not np.isfinite(value):
            raise FloatingPointError(
                f"{self._key} is {value} at step {step}"
            )


class LoggingHook(Hook):
    """Log scalar metrics every N steps (LoggingTensorHook :169)."""

    def __init__(self, every_steps: int = 100, keys: Optional[Sequence[str]] = None):
        self._every = every_steps
        self._keys = keys

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if step % self._every:
            return
        keys = self._keys or sorted(metrics)
        parts = []
        for k in keys:
            v = metrics.get(k)
            if v is None:
                continue
            try:
                parts.append(f"{k}={float(v):.4f}")
            except (TypeError, ValueError):
                # Array-valued metric (e.g. a per-class histogram): skip —
                # the same guard SummaryWriter.scalars applies.  Logging
                # must never be the thing that kills training.
                continue
        log.info("step %d: %s", step, ", ".join(parts))


class MetricWriterHook(Hook):
    """Append scalar metrics to ``<workdir>/metrics.jsonl`` every N steps —
    the SummarySaverHook role (TF monitored_session.py:585-590) with a
    dependency-free format (one JSON object per line, TensorBoard-convertible;
    schema documented in README "Observability" and linted by
    ``scripts/check_metrics_schema.py``).

    The file handle stays open across steps (line-buffered append) —
    reopening per write cost a path resolution + fd churn every cadence —
    and each row goes down as ONE ``write`` of the full line, so a
    concurrent ``tail -f`` never sees a torn line."""

    def __init__(self, workdir: str, every_steps: int = 100):
        self._path = os.path.join(workdir, "metrics.jsonl")
        self._every = every_steps
        os.makedirs(workdir, exist_ok=True)
        # buffering=1: text-mode line buffering — flushed to the OS at
        # each newline, i.e. exactly once per row.
        self._f = open(self._path, "a", buffering=1)

    def write_row(self, row: Mapping[str, Any]) -> None:
        """Append one row (atomic single write of the full line)."""
        if self._f.closed:  # post-end() stragglers must not crash
            self._f = open(self._path, "a", buffering=1)
        self._f.write(json.dumps(row) + "\n")

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if step % self._every:
            return
        row = {"step": step, "time": time.time()}
        for k, v in metrics.items():
            try:
                row[k] = float(v)
            except (TypeError, ValueError):
                continue
        self.write_row(row)

    def end(self, state):
        if not self._f.closed:
            self._f.close()


class TensorBoardHook(Hook):
    """Scalar summaries into TensorBoard event files every ``every_steps``
    (default 100, the reference's SummarySaverHook cadence — TF
    monitored_session.py:517-518), via the no-TF writer in
    :mod:`harness.summary`."""

    def __init__(self, workdir: str, every_steps: int = 100):
        # Chief-only, like the reference's SummarySaverHook (TF
        # monitored_session.py:566-609 chief hooks) — non-zero processes
        # would write duplicate event streams.
        self._writer = None
        if jax.process_index() == 0:
            from distributed_tensorflow_models_tpu.harness.summary import (
                SummaryWriter,
            )

            self._writer = SummaryWriter(
                os.path.join(workdir, "tensorboard")
            )
        self._every = every_steps

    def wants_step(self, step):
        return self._writer is not None and step % self._every == 0

    def after_step(self, state, metrics, step):
        if self._writer is None or step % self._every:
            return
        self._writer.scalars(step, metrics)
        # Flush each write (log-cadence, ~50 bytes): a live TensorBoard
        # sees events immediately and a preemption (SIGKILL skips end())
        # loses nothing buffered.
        self._writer.flush()

    def end(self, state):
        if self._writer is not None:
            self._writer.close()


# The routing statistics a model with top-k experts puts into the step's
# metrics (core/train_loop.py::lm_loss_fn), always the three together.
MOE_KEYS = ("moe_load_max_over_mean", "moe_aux_loss", "moe_z_loss")
# Beside them where the expert layers hold a range of the router's
# experts only, the two together: the share of the step's assignments
# that fell on it, and the slabs of rows a layer worked through them in
# (1: an ordinary step; ``parallel/moe.py::_held_experts``).
MOE_HELD_KEYS = ("moe_held_share", "moe_held_slabs")


class TelemetryHook(Hook):
    """Snapshot the telemetry registry every ``every_steps`` and inject the
    derived scalars into the per-step ``metrics`` dict, where the
    downstream writer hooks (MetricWriterHook → ``metrics.jsonl``,
    TensorBoardHook → event files) pick them up on the same cadence.
    **Must be ordered before the writer hooks** (``fit`` does this).

    Injected keys (interval = since the previous cadence firing):

    - ``step_time_s``    — mean full-iteration wall time over the interval
    - ``data_wait_s``    — mean per-step time blocked on the input pipeline
    - ``assemble_s`` / ``shard_s`` — the input path's work, mean per
      BATCH over the interval: the dataset producing one batch
      (``pipeline/assemble``; with N pool workers it is host work per
      batch, not wall) and its host-to-device placement
      (``pipeline/shard``).  Always the two together.
    - ``dispatch_s``     — mean per-step host dispatch time
    - ``steps_per_sec``  — interval throughput
    - ``stall_fraction`` — data-wait share of interval wall time
    - ``mfu``            — FLOPs retired / (interval wall × peak);
      0.0 when the device has no known peak (CPU) or FLOPs are unknown
    - ``compile_count`` / ``compile_s`` — cumulative compile events
    - ``checkpoint_s``   — cumulative blocking checkpoint time (save +
      restore + wait + the overlapped-save durability fence)
    - ``checkpoint/fence_s`` — the fence share alone: wall time saves
      spent blocked on a PREVIOUS async save, i.e. how much tightening
      ``checkpoint_every_steps`` actually costs
    - ``startup/restore_s`` / ``startup/aot_compile_s`` /
      ``startup/time_to_first_step_s`` — the restart-MTTR gauges
      (always the three together — the schema lint checks the set)
    - ``host_queue_depth`` — producer buffer depth right now
    - ``restarts`` / ``rollbacks`` / ``skipped_batches`` — resilience
      counters (recoverable_fit restarts; nan_policy=rollback rewinds
      and the batches their skips discarded)
    - ``moe_load_max_over_mean`` / ``moe_aux_loss`` / ``moe_z_loss`` —
      only where the step reports them (a model with top-k routed
      experts): the mean over the steps this hook walked since the
      previous firing, replacing the firing step's own value.  The
      steps' device scalars are kept and fetched at the cadence, where
      the loss row is fetched anyway: no further device sync.
      ``moe_held_share`` and ``moe_held_slabs`` join them, the same way,
      where the step reports them (expert layers that hold a share of
      the experts).

    Multi-host: steps/sec and stall fraction are allgathered
    (``multihost_utils.process_allgather`` — a collective, so the hook
    must run on EVERY process at the same steps; cadence is
    deterministic in ``step``) and the chief's writers record
    ``hosts/steps_per_sec_{min,mean}`` and ``hosts/stall_fraction_max``
    — one slow or input-bound host is visible without ssh'ing into it.
    """

    def __init__(
        self,
        registry: telemetry.MetricsRegistry,
        every_steps: int = 100,
        process_count: Optional[int] = None,
    ):
        self._reg = registry
        self._every = every_steps
        self._nproc = (
            jax.process_count() if process_count is None else process_count
        )
        # Whole-mesh peak: the FLOPs numerator is the global SPMD
        # program's cost, so the denominator is per-chip peak x all
        # participating devices.
        # None on the CPU; an unlisted accelerator raises here, at fit
        # start, rather than logging mfu 0.0 for the whole run.
        peak = telemetry.peak_flops(jax.devices()[0].device_kind)
        self._peak = peak and peak * len(jax.devices())
        self._last: Optional[tuple[float, int, dict]] = None
        self.last_emitted: Optional[dict] = None
        self._moe: list[tuple] = []

    def begin(self, state):
        self._last = (
            time.perf_counter(), int(state.step), self._reg.snapshot()
        )

    def wants_step(self, step):
        # Deterministic in step — required: the multi-host branch of
        # after_step is a collective, so every process must walk the
        # same rows under the fused loop's wants_step gating.
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if MOE_KEYS[0] in metrics:
            keys = MOE_KEYS + MOE_HELD_KEYS * (MOE_HELD_KEYS[0] in metrics)
            self._moe.append(tuple(metrics[k] for k in keys))
        if step % self._every:
            return
        now = time.perf_counter()
        snap = self._reg.snapshot()
        t0, s0, prev = self._last or (now, step, {})
        self._last = (now, step, snap)
        d_wall = max(now - t0, 1e-9)
        d_steps = max(step - s0, 0)

        def delta(key: str) -> float:
            return snap.get(key, 0.0) - prev.get(key, 0.0)

        def mean(name: str) -> float:
            n = delta(f"{name}/count")
            return delta(f"{name}/total_s") / n if n else 0.0

        data_wait = delta(f"{telemetry.DATA_WAIT}/total_s")
        sps = d_steps / d_wall
        stall_frac = data_wait / d_wall
        # FLOPs actually retired this interval (signature-exact — mixed
        # batch shapes are each priced at their own program's cost).
        flops_done = delta(telemetry.FLOPS_TOTAL)
        out = {
            "step_time_s": mean(telemetry.STEP_TIME),
            "data_wait_s": data_wait / max(d_steps, 1),
            "assemble_s": mean(telemetry.ASSEMBLE),
            "shard_s": mean(telemetry.SHARD),
            "dispatch_s": mean(telemetry.DISPATCH),
            "steps_per_sec": sps,
            "stall_fraction": stall_frac,
            "mfu": (
                flops_done / (d_wall * self._peak)
                if self._peak and flops_done > 0
                else 0.0
            ),
            "compile_count": snap.get(f"{telemetry.COMPILE}/count", 0.0),
            "compile_s": snap.get(f"{telemetry.COMPILE}/total_s", 0.0),
            "checkpoint_s": (
                snap.get(f"{telemetry.CKPT_SAVE}/total_s", 0.0)
                + snap.get(f"{telemetry.CKPT_RESTORE}/total_s", 0.0)
                + snap.get(f"{telemetry.CKPT_WAIT}/total_s", 0.0)
                + snap.get(f"{telemetry.CKPT_FENCE}/total_s", 0.0)
            ),
            "checkpoint/fence_s": snap.get(
                f"{telemetry.CKPT_FENCE}/total_s", 0.0
            ),
            "startup/restore_s": snap.get(telemetry.STARTUP_RESTORE, 0.0),
            "startup/aot_compile_s": snap.get(
                telemetry.STARTUP_AOT_COMPILE, 0.0
            ),
            "startup/time_to_first_step_s": snap.get(
                telemetry.STARTUP_FIRST_STEP, 0.0
            ),
            "host_queue_depth": snap.get(telemetry.HOST_QUEUE_DEPTH, 0.0),
            # Resilience counters (always the three together — the schema
            # lint checks them as a set): cumulative within this fit
            # attempt; a recoverable_fit restart resets rollbacks/
            # skipped_batches and bumps restarts (fresh per-run registry,
            # seeded with the attempt count).
            "restarts": snap.get(telemetry.RESTARTS, 0.0),
            "rollbacks": snap.get(telemetry.ROLLBACKS, 0.0),
            "skipped_batches": snap.get(telemetry.SKIPPED_BATCHES, 0.0),
        }
        if self._moe:
            means = np.mean(np.asarray(jax.device_get(self._moe)), axis=0)
            out.update(zip(MOE_KEYS + MOE_HELD_KEYS, map(float, means)))
            self._moe = []
        if self._nproc > 1:
            from jax.experimental import multihost_utils

            gathered = np.asarray(
                multihost_utils.process_allgather(
                    np.asarray([sps, stall_frac], np.float32)
                )
            ).reshape(-1, 2)
            out["hosts/steps_per_sec_min"] = float(gathered[:, 0].min())
            out["hosts/steps_per_sec_mean"] = float(gathered[:, 0].mean())
            out["hosts/stall_fraction_max"] = float(gathered[:, 1].max())
        self.last_emitted = out
        if isinstance(metrics, MutableMapping):
            # dict in the unfused loop, LazyMetricRow (overlay write) in
            # the fused loop — both take the injection for the writer
            # hooks downstream.
            metrics.update(out)


class FleetHook(Hook):
    """Chief-only fleet-health gauges from the heartbeat directory
    (``resilience/heartbeat.py``): every ``every_steps`` it reads the
    peers' heartbeat files — plain shared-filesystem reads, never a
    collective — and injects/records

    - ``fleet/peers_alive``     — processes with a fresh heartbeat,
    - ``fleet/step_lag``        — max−min step among alive peers (the
      straggler / slowest-host skew),
    - ``fleet/heartbeat_age_s`` — the worst heartbeat age,

    into the metrics row (→ metrics.jsonl / TensorBoard via the writer
    hooks downstream — order this before them, like TelemetryHook) and
    the registry (→ telemetry.json).  A dead host shows up here within
    one cadence of its heartbeat going stale, with its process index in
    the chief's log — per-host failure attribution without ssh."""

    def __init__(
        self,
        registry: telemetry.MetricsRegistry,
        directory: str,
        num_processes: int,
        every_steps: int = 100,
        *,
        stale_after_s: float = 15.0,
    ):
        self._reg = registry
        self._dir = directory
        self._nproc = num_processes
        self._every = max(1, every_steps)
        self._stale = stale_after_s
        self._warned_dead: set[int] = set()

    def wants_step(self, step):
        return step % self._every == 0

    def after_step(self, state, metrics, step):
        if step % self._every:
            return
        from distributed_tensorflow_models_tpu.resilience import heartbeat

        try:
            views = heartbeat.read_fleet(self._dir, self._nproc)
            # One snapshot for both the per-peer warnings and the
            # gauges — a second read could classify a peer differently
            # mid-walk.
            summary = heartbeat.fleet_summary(
                self._dir, self._nproc, stale_after_s=self._stale,
                views=views,
            )
        except Exception:  # noqa: BLE001 — telemetry must never kill a run
            log.exception("fleet heartbeat read failed")
            return
        for i, view in enumerate(views):
            stale = view is None or view["age_s"] > self._stale
            if stale and i not in self._warned_dead:
                self._warned_dead.add(i)
                log.warning(
                    "fleet: process %d heartbeat is %s (last step %s)",
                    i,
                    "missing" if view is None else f"{view['age_s']:.1f}s stale",
                    "?" if view is None else view.get("step"),
                )
            elif not stale:
                self._warned_dead.discard(i)
        out = {
            telemetry.FLEET_PEERS_ALIVE: float(summary["peers_alive"]),
            telemetry.FLEET_STEP_LAG: float(summary["step_lag"]),
            telemetry.FLEET_HEARTBEAT_AGE: float(summary["heartbeat_age_s"]),
        }
        for key, value in out.items():
            self._reg.gauge(key).set(value)
        if isinstance(metrics, MutableMapping):
            metrics.update(out)


class CheckpointHook(Hook):
    """Save every ``every_secs`` (default 600 s, the reference's
    CheckpointSaverHook default — TF monitored_session.py:525-528) and at
    ``end``.  ``save_fn(state, step)`` is provided by the driver so the hook
    stays agnostic of checkpoint layout.

    Multi-host: orbax saves are collective, so every process must decide
    "save now" at the *same step*.  A per-process wall clock cannot
    guarantee that (clocks cross the threshold at different steps and the
    early process deadlocks in the save barrier while the others run ahead).
    With ``process_count > 1`` the chief alone reads the clock and its
    decision is broadcast, polled every ``poll_every_steps`` steps to keep
    the collective off the per-step hot path; step-based triggers
    (``every_steps``) are deterministic on every process and need no sync.
    """

    def __init__(self, save_fn, every_secs: float = 600.0,
                 every_steps: Optional[int] = None,
                 poll_every_steps: int = 20):
        self._save = save_fn
        self._every_secs = every_secs
        self._every_steps = every_steps
        self._poll = max(1, poll_every_steps)
        self._last_time = time.time()
        self._multiproc = jax.process_count() > 1

    def _time_due(self, step: int) -> bool:
        if self._every_secs is None:
            return False
        if not self._multiproc:
            return time.time() - self._last_time >= self._every_secs
        if step % self._poll:
            return False
        from jax.experimental import multihost_utils

        chief_due = (
            jax.process_index() == 0
            and time.time() - self._last_time >= self._every_secs
        )
        return bool(
            multihost_utils.broadcast_one_to_all(
                np.asarray(chief_due, np.int32)
            )
        )

    def wants_step(self, step):
        # Step triggers and the multi-host poll cadence are deterministic
        # in step (required — the poll broadcast is a collective); the
        # single-process clock check is local, so reading it here is safe.
        if self._every_steps and step % self._every_steps == 0:
            return True
        if self._every_secs is None:
            return False
        if self._multiproc:
            return step % self._poll == 0
        return time.time() - self._last_time >= self._every_secs

    def after_step(self, state, metrics, step):
        due_step = self._every_steps and step % self._every_steps == 0
        if due_step or self._time_due(step):
            self._save(state, step)
            self._last_time = time.time()

    def end(self, state):
        self._save(state, int(state.step))

    def abort(self, state):
        # Crash-time save is safe (and valuable) single-process; with peers
        # it is a collective this lone failing process must NOT enter — the
        # others are blocked in the next step's all-reduce, not the save
        # barrier.  Recovery then restores the last *scheduled* checkpoint.
        if not self._multiproc:
            self._save(state, int(state.step))
        else:
            log.warning(
                "skipping crash-time checkpoint save on multi-host failure "
                "(collective save cannot run from one process)"
            )


class FaultInjectionHook(Hook):
    """Raise a chosen exception at a chosen step, once.

    The reference has no fault injection anywhere (SURVEY.md §5.3); the
    rebuild adds it as a first-class hook so the recovery path — the
    analogue of ``_RecoverableSession``'s retry loop (TF
    monitored_session.py:1261-1274) — is testable on demand rather than
    only on real preemptions."""

    def __init__(self, step: int, exc_factory=None):
        self._step = step
        self._fired = False
        self._exc_factory = exc_factory or (
            lambda: RuntimeError("injected preemption")
        )

    def wants_step(self, step):
        return step == self._step and not self._fired

    def after_step(self, state, metrics, step):
        if step == self._step and not self._fired:
            self._fired = True
            raise self._exc_factory()


def run_hooks_after_step(hooks: Sequence[Hook], state, metrics, step) -> bool:
    """Returns False when a hook requested stop.  Every hook runs every
    step — a StopRequested from one hook must not starve later hooks of the
    final step's metrics (logging/metric-writer/checkpoint all fire on the
    stop step before the loop exits)."""
    stop = False
    for h in hooks:
        try:
            h.after_step(state, metrics, step)
        except StopRequested:
            stop = True
    return not stop


def run_hooks_after_chunk(
    hooks: Sequence[Hook],
    state,
    stacked_metrics: Mapping,
    start_step: int,
    length: int,
    registry: Optional[telemetry.MetricsRegistry] = None,
    final_row: Optional[LazyMetricRow] = None,
) -> bool:
    """Walk hooks for the ``length`` steps of one fused chunk, skipping
    every step no hook wants (:meth:`Hook.wants_step`) — the K−1 quiet
    steps cost one predicate sweep each, no metric sync, no hook walk.

    The chunk covers steps ``start_step+1 .. start_step+length``; each
    walked step gets a :class:`LazyMetricRow` over ``stacked_metrics``
    (row i ↔ step ``start_step+1+i``).  ``state`` is the end-of-chunk
    state — the only one the fused program materialises; hooks that save
    it (CheckpointHook) therefore always persist chunk-boundary state,
    consistent with the data-position contract of
    ``data/pipeline.py::BatchStacker.get_state``.

    Full walks are counted into ``registry``'s ``train/hook_walks``
    (the micro-guard's numerator).  Per-walk semantics match
    :func:`run_hooks_after_step`: every hook runs, StopRequested defers
    to the end of the walk, and remaining walked steps still run so the
    stop step's metrics reach the writers.

    ``final_row``, when given, is used as the last row's metrics object
    instead of a fresh :class:`LazyMetricRow`, so overlay writes
    (TelemetryHook's injected scalars) are visible to the caller —
    ``fit`` passes the row it returns as ``FitResult.final_metrics``.
    """
    stop = False
    for i in range(length):
        step = start_step + 1 + i
        if not any(h.wants_step(step) for h in hooks):
            continue
        if registry is not None:
            registry.counter(telemetry.HOOK_WALKS).inc()
        if i == length - 1 and final_row is not None:
            row = final_row
        else:
            row = LazyMetricRow(stacked_metrics, i, start_step + 1)
        for h in hooks:
            try:
                h.after_step(state, row, step)
            except StopRequested:
                stop = True
        if stop:
            # Mirror the unfused loop: nothing fires after the stop step
            # (its own walk completed — writers got the final metrics).
            break
    return not stop
