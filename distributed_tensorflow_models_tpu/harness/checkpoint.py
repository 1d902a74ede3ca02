"""Checkpoint save/restore: the Saver/SessionManager replacement.

Reference semantics being reproduced (SURVEY.md §2.2 F12, §5.4):
``tf.train.Saver`` writes ``model.ckpt-N`` keeping the last k, a
CheckpointSaverHook fires every 600 s, and ``SessionManager.prepare_session``
decides restore-vs-init at startup.  Improvements the TPU stack makes
natural: checkpoints are *atomic pytree snapshots* (no partial-variable
states), saves are async (orbax writes in the background while training
continues), and the **input-pipeline position is checkpointed too** — the
reference's queues lose their position on restart (SURVEY.md §5.4 gap).

What is saved per step: the array leaves of :class:`TrainState`
(step/params/batch_stats/opt_state/ema_params/carry) plus a JSON blob with
the dataset iterator state.

Multi-host: orbax saves are collective (every process calls ``save``; array
shards are written by their owning hosts, the JSON by the primary), so the
orbax JSON records process 0's iterator position.  With more than one
process each process *additionally* writes its own dataset state to a
per-step sidecar (``checkpoints/dataset_states/<step>/p<pid>.json``,
atomic rename, pruned alongside orbax's keep-k GC) and restores from its
own sidecar — exact per-process resume even for the file-sharded ImageNet
stream, where every process's shard position differs.  The reference's
queue pipeline cannot resume input position at all (SURVEY.md §5.4).

Every fleet-visible *decision* about the shared checkpoint directory —
the save skip/replace choice, the restore walk's step pick, and
restore-vs-fresh-init — is **chief-decided**: process 0 computes it from
its own storage view and broadcasts it
(``resilience/consensus.py``; exact no-op single-process), so storage
with cross-host visibility skew (object stores, replicated NFS) cannot
put two processes into different collectives.  A follower whose local
view disagrees obeys the chief, logs the skew, and counts it into
``fleet/consensus_overrides``.

Elastic resize (cross-topology resume): every save stamps the writing
fleet's process count into the orbax JSON item and each sidecar.  A
restore whose live process count differs reshards the global arrays
onto the live mesh (:func:`restore_abstract_tree` builds the abstract
targets from the LIVE template's shardings) and re-splits the dataset
cursor with the conservative fleet-minimum rule (``data/resplit.py``):
every new process resumes from the smallest saved position — re-reading
at most one in-flight chunk per host, never skipping an untrained
batch.  The source pick is fleet-agreed via consensus *after* the walk
settles on a candidate (see ``_finalize_resize`` — a broadcast inside
the per-candidate restore would desync the collective order whenever a
peer's restore throws), counted into ``checkpoint/resize_restores``,
and audited by a chief-written ``resize_ledger.json`` next to the
crossing step's sidecars.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
from typing import Any, Callable, Optional, Sequence

import jax

from distributed_tensorflow_models_tpu import telemetry
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.data import resplit as resplitlib
from distributed_tensorflow_models_tpu.harness.startup import import_orbax
from distributed_tensorflow_models_tpu.resilience import consensus as conslib
from distributed_tensorflow_models_tpu.resilience import fsck as fscklib

ocp = import_orbax()

log = logging.getLogger("dtm")

PyTree = Any

# Chief-broadcast save decision codes (ints — broadcastable).
_SAVE_PROCEED = 0
_SAVE_SKIP_INFLIGHT = 1
_SAVE_SKIP_EXISTS = 2
_SAVE_REPLACE = 3

# Reserved key stamped into the orbax JSON ``data`` item at save time so
# a restore knows the writing fleet's topology even before it looks at
# sidecars (and for single-process runs, which write none).  Stripped on
# restore — the train harness never sees it.
_FLEET_META_KEY = "__fleet__"

# Name of the re-split audit artifact the chief writes next to the
# crossing step's sidecars (see CheckpointManager._write_resize_ledger).
RESIZE_LEDGER = "resize_ledger.json"


class NoValidCheckpointError(FileNotFoundError):
    """Checkpoints exist but every candidate is torn/unrestorable.
    Distinct from the bare ``FileNotFoundError`` ("no checkpoint found")
    so ``restore_or_init`` can fall back to a fresh init with a loud
    warning instead of crashing the job at recovery time."""


def _array_tree(state: TrainState) -> dict:
    return {
        "step": state.step,
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "ema_params": state.ema_params,
        "carry": state.carry,
    }


def restore_abstract_tree(template: TrainState) -> dict:
    """Abstract restore targets (shape/dtype/sharding) for ``template``.

    The shardings come from the LIVE template — the state the caller
    just built on *this* run's mesh — never from anything recorded in
    the checkpoint.  Checkpointed shapes are global, so this is the
    whole elastic-resize story on the array side: a checkpoint written
    by an N-process fleet restores onto an M-process mesh because orbax
    is told to materialise each global array under the new mesh's
    sharding and reshards at read time.  Pulling shardings from the
    *saved* topology instead would pin restore to the writing fleet's
    device set — exactly the fixed-topology assumption this replaces.
    """

    def as_abstract(x):
        sharding = getattr(x, "sharding", None)
        if sharding is not None and hasattr(x, "shape"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return ocp.utils.to_shape_dtype_struct(x)

    return jax.tree.map(as_abstract, _array_tree(template))


class CheckpointManager:
    """keep-last-k, async, atomic checkpoints under ``workdir/checkpoints``.

    ``process_index``/``process_count`` default to the live jax values;
    they are injectable so the per-process sidecar path is unit-testable
    without a real multi-process cluster.
    """

    def __init__(
        self,
        workdir: str,
        keep: int = 5,
        *,
        process_index: Optional[int] = None,
        process_count: Optional[int] = None,
        registry: Optional[telemetry.MetricsRegistry] = None,
        consensus: Optional[conslib.Consensus] = None,
        step_filter: Optional[Callable[[Sequence[int]], Sequence[int]]] = None,
    ):
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        # Absolute path required: orbax's async tensorstore writer rejects
        # relative paths at SAVE time ("Checkpoint path should be
        # absolute") — i.e. a relative --workdir would train fine and then
        # fail at the first checkpoint, losing the run.
        self._dir = os.path.abspath(os.path.join(workdir, "checkpoints"))
        self._mgr = ocp.CheckpointManager(
            self._dir,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=keep, create=True
            ),
        )
        self._pid = (
            jax.process_index() if process_index is None else process_index
        )
        self._nproc = (
            jax.process_count() if process_count is None else process_count
        )
        # Consensus defaults to the LIVE process facts, not the injected
        # ones: the injectable pid/nproc exist so sidecar paths are
        # unit-testable in a single process, and such a test must not be
        # handed a backend that would try real collectives.  Tests that
        # want the fleet decision protocol inject a scripted backend.
        self._consensus = (
            conslib.Consensus() if consensus is None else consensus
        )
        # View filter (chaos visibility-skew simulation): applied to
        # every *listing* this manager reasons from — never to reads,
        # which is the real shape of object-store metadata lag.
        self._step_filter = step_filter
        # Pre-create the fence timer: it records only when a save
        # actually blocked on a previous in-flight save, so without this
        # a run whose cadence never outran the background writer would
        # have NO checkpoint/fence entry in telemetry.json — and "zero
        # fences" (the healthy reading) would be indistinguishable from
        # "fence not instrumented".
        self._registry.timer(telemetry.CKPT_FENCE)
        # Same zero-vs-missing argument for the degraded-resume counters:
        # both record only on warning paths, and zero is the healthy
        # reading the schema-coverage gate must be able to see.
        self._registry.counter(telemetry.CKPT_SIDECAR_FALLBACKS)
        self._registry.counter(telemetry.CKPT_RESIZE_RESTORES)
        # Cross-topology restore bookkeeping: _pending_resize is staged
        # by _restore_step (local, deterministic) and resolved by
        # _finalize_resize AFTER the walk has fleet-agreed on the
        # candidate — the consensus broadcast must not live inside
        # _restore_step, where one host may throw (torn/unrestorable)
        # while peers proceed, desyncing the collective order.
        self._pending_resize: Optional[dict] = None
        self._last_resize: Optional[dict] = None

    @property
    def consensus(self) -> conslib.Consensus:
        return self._consensus

    @property
    def last_resize(self) -> Optional[dict]:
        """Details of the cross-topology re-split the most recent
        restore performed (``{"step", "from_nproc", "to_nproc",
        "source_pid"}``), or None when the restore was same-shape.  The
        train harness reads this to announce the crossing and drop a
        flight record on every host."""
        return self._last_resize

    def _visible_steps(self) -> list[int]:
        steps: Sequence[int] = sorted(self._mgr.all_steps())
        if self._step_filter is not None:
            steps = sorted(self._step_filter(steps))
        return list(steps)

    def _sidecar(self, step: int, pid: Optional[int] = None) -> str:
        pid = self._pid if pid is None else pid
        return os.path.join(
            self._dir, "dataset_states", str(step), f"p{pid}.json"
        )

    def _local_save_decision(self, step: int) -> int:
        """This process's view of what ``save(step)`` should do.  The
        acting decision is the chief's (broadcast in :meth:`save`) —
        orbax saves are collective, so the fleet must skip together or
        save together; a per-process choice under storage-visibility
        skew would strand the skipping processes out of the barrier."""
        if step not in self._visible_steps():
            return _SAVE_PROCEED
        step_dir = self._step_dir(step)
        if not os.path.isdir(step_dir):
            # Listed but no finalized dir yet: an in-flight async
            # save of this very step (orbax registers the step while
            # still writing the tmp dir).  It IS this state —
            # deterministic in step — so skip; deleting/overwriting
            # would corrupt the write in progress.
            return _SAVE_SKIP_INFLIGHT
        if not fscklib.validate_step_dir(step_dir):
            # Idempotent by construction: training is deterministic
            # in step, so a VALID checkpoint for this step IS this
            # state.  Orbax raises StepAlreadyExistsError here
            # (force=True included), which would turn e.g. a
            # preemption's emergency save at a boundary the cadence
            # save just wrote into a crash.
            return _SAVE_SKIP_EXISTS
        # A FINALIZED dir that fails validation is damage, not a
        # checkpoint — treating it as one would silently suppress a
        # real save (e.g. the emergency save "succeeding" while
        # resume walks back past the damage).  Replace it.
        return _SAVE_REPLACE

    def _agree_int(self, value: int, label: str) -> int:
        """Chief-decides broadcast with the skew audit: a follower whose
        local decision is overridden bumps ``fleet/consensus_overrides``
        (the consensus module logs the specifics) and the override lands
        on the flight-recorder timeline — which host's storage view
        disagreed, on which decision, is exactly the cross-host fact a
        skew post-mortem reconstructs."""
        agreed = self._consensus.broadcast_int(value, label=label)
        if agreed != value:
            self._registry.counter(telemetry.CONSENSUS_OVERRIDES).inc()
            self._registry.trace.instant(
                "fleet/consensus_override",
                {"label": label, "local": value, "agreed": agreed},
            )
        return agreed

    def save(
        self,
        state: TrainState,
        dataset_state: Optional[dict] = None,
        *,
        force: bool = False,
    ) -> bool:
        step = int(state.step)
        decision = self._local_save_decision(step)
        if self._consensus.active:
            decision = self._agree_int(decision, f"save-decision@{step}")
        if decision == _SAVE_SKIP_INFLIGHT:
            log.info(
                "checkpoint at step %d is still being written; "
                "skipping duplicate save", step,
            )
            return False
        if decision == _SAVE_SKIP_EXISTS:
            log.info(
                "checkpoint at step %d already exists; skipping save",
                step,
            )
            return False
        if decision == _SAVE_REPLACE:
            log.warning(
                "existing checkpoint at step %d is torn; replacing it",
                step,
            )
            self._registry.trace.instant(
                "checkpoint/replace_torn", {"step": step}
            )
            self.delete(step)
        elif step in self._mgr.all_steps():
            # Chief said PROCEED but this process's *unfiltered* listing
            # already has the step (the chief's view lags ours — the
            # reverse skew): reconcile by clearing the local registration
            # so the collective save cannot die on StepAlreadyExists.
            if not os.path.isdir(self._step_dir(step)):
                # Listed-but-no-dir = OUR async save of this step is
                # still flushing; deleting now would corrupt the write
                # in progress.  Make it durable first — the delete then
                # removes a finalized checkpoint of this very state,
                # which the chief-decided re-save recreates.
                self.wait()
            log.warning(
                "chief-decided save at step %d but the step exists in "
                "this process's view; clearing it to rejoin the "
                "collective save", step,
            )
            self.delete(step)
        # Overlapped-save structure: orbax would otherwise block INSIDE
        # _mgr.save until the previous async save is durable, charging
        # that durability wait to the save span on the step path.  Fence
        # first (its own metric, skipped when nothing is pending) so
        # CKPT_SAVE times only the irreducible blocking portion — the
        # device→host snapshot + orbax dispatch — and a tightened
        # checkpoint_every_steps shows its true cost as checkpoint/fence
        # time rather than mysteriously fat saves.  The write itself
        # still finishes in the background; wait()/close() (teardown,
        # emergency, rollback) remain the explicit durability points.
        self.fence()
        # Topology stamp: restore reads this (and strips it) to detect a
        # fleet coming back with a different process count — including
        # single-process runs, which write no sidecars to stamp.
        payload = dict(dataset_state or {})
        payload[_FLEET_META_KEY] = {"nproc": self._nproc}
        with self._registry.span(telemetry.CKPT_SAVE):
            saved = self._mgr.save(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardSave(_array_tree(state)),
                    data=ocp.args.JsonSave(payload),
                ),
                force=force,
            )
            if saved and self._nproc > 1 and dataset_state is not None:
                self._write_sidecar(step, dataset_state)
        if saved:
            log.info("saved checkpoint at step %d", step)
        return saved

    def _write_sidecar(self, step: int, dataset_state: dict) -> None:
        """Per-process dataset position (atomic rename), pruned to the
        steps orbax retains.  The process count is recorded alongside: a
        sidecar written under a different shard topology must not be
        restored as an exact position."""
        path = self._sidecar(step)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp"
        with open(tmp, "w") as f:
            json.dump({"nproc": self._nproc, "state": dataset_state}, f)
        os.replace(tmp, path)
        base = os.path.join(self._dir, "dataset_states")
        keep = {str(s) for s in self._mgr.all_steps()} | {str(step)}
        for name in os.listdir(base):
            if name not in keep:
                shutil.rmtree(os.path.join(base, name), ignore_errors=True)

    def latest_step(self) -> Optional[int]:
        steps = self._visible_steps()
        return steps[-1] if steps else None

    def all_steps(self) -> list[int]:
        """Ascending retained steps (rollback and fsck candidates), as
        seen through this process's view (``step_filter`` applied — the
        chaos visibility-skew seam)."""
        return self._visible_steps()

    def delete(self, step: int) -> None:
        """Remove one retained step (best-effort).  The rollback path
        deletes the abandoned timeline's checkpoints after rewinding —
        they hold post-divergence state that must never be restored, and
        their steps will be re-saved by the replay."""
        try:
            self._mgr.delete(step)
        except Exception:  # noqa: BLE001 — stale steps are non-fatal
            log.exception("failed to delete checkpoint step %d", step)

    @property
    def directory(self) -> str:
        """The orbax checkpoint root (``<workdir>/checkpoints``)."""
        return self._dir

    def _step_dir(self, step: int) -> str:
        return os.path.join(self._dir, str(step))

    def restore(
        self, template: TrainState, step: Optional[int] = None
    ) -> tuple[TrainState, dict]:
        """Restore into the structure of ``template`` (a freshly-created
        state — supplies static fields and the pytree layout).  Returns the
        restored state and the dataset iterator state dict.

        With ``step=None`` (the auto-resume path) candidates are validated
        structurally (``resilience/fsck.py`` — orbax completeness markers)
        and restore *walks back* to the newest valid step instead of
        crashing on a torn write; a candidate that passes validation but
        still fails orbax restore (damage the structural check can't see)
        is likewise skipped with a warning.  An explicit ``step`` is taken
        at its word and restored directly — callers naming a step want
        that step or the error.

        No finiteness gate here: eval/generate restore through this path
        and must see the newest checkpoint even if e.g. its opt_state
        diverged (they read only params/EMA).  The *training* resume
        path adds the gate in :func:`restore_or_init`."""
        if step is None:
            return self.restore_newest_valid(template)
        return self._finalize_resize(self._restore_step(template, step))

    def restore_newest_valid(
        self,
        template: TrainState,
        accept=None,
        accept_name: str = "",
    ) -> tuple[TrainState, dict]:
        """Walk candidate steps newest-first, skipping torn (structural
        validation), unrestorable, and — when ``accept(state)`` is given
        — rejected candidates (the rollback path passes a finiteness
        gate).  Raises :class:`NoValidCheckpointError` when nothing
        survives.

        Multi-host the walk is **chief-decided**: process 0 validates
        against its own storage view, names the step, and broadcasts it;
        followers restore that step *strictly* (their own listings are
        never consulted for the pick — under visibility skew the listing
        lags but the read goes through).  Restore failures and
        ``accept`` rejections are agreed with an any-host reduction, so
        every process walks back together or returns together — two
        hosts settling on different steps is a de-synced fleet, not a
        degraded restore.  The chief prefers *fleet-valid* candidates
        (every process's dataset sidecar present and parseable) and
        falls back to structurally-valid-only steps — an approximate
        resume for the sidecar-less peers — when no candidate clears
        the higher bar."""
        if self._consensus.active:
            return self._restore_newest_valid_fleet(
                template, accept, accept_name
            )
        return self._restore_newest_valid_local(
            template, accept, accept_name
        )

    def _restore_newest_valid_local(
        self,
        template: TrainState,
        accept=None,
        accept_name: str = "",
    ) -> tuple[TrainState, dict]:
        """Single-process walk (the PR-4 behavior, bit-for-bit)."""
        candidates = sorted(self._visible_steps(), reverse=True)
        if not candidates:
            raise FileNotFoundError("no checkpoint found")
        last_error: Optional[BaseException] = None
        for i, step in enumerate(candidates):
            issues = fscklib.validate_step_dir(self._step_dir(step))
            if issues:
                log.warning(
                    "checkpoint step %d fails validation (%s); walking "
                    "back to an earlier step (scripts/fsck_checkpoints.py "
                    "reports and can --repair)",
                    step, "; ".join(issues),
                )
                self._trace_walk_back(step, "torn")
                continue
            try:
                out = self._restore_step(template, step)
            except Exception as e:  # noqa: BLE001 — damage fsck can't see
                last_error = e
                log.warning(
                    "checkpoint step %d passed validation but failed to "
                    "restore (%s); walking back", step, e,
                )
                self._trace_walk_back(step, "unrestorable")
                continue
            if accept is not None and not accept(out[0]):
                log.warning(
                    "checkpoint step %d rejected (%s); walking back",
                    step, accept_name or "accept predicate",
                )
                self._trace_walk_back(step, accept_name or "rejected")
                continue
            if i > 0:
                log.warning(
                    "restored step %d instead of the newest step %d "
                    "(newer candidates torn/unrestorable/rejected)",
                    step, candidates[0],
                )
            return self._finalize_resize(out)
        raise NoValidCheckpointError(
            f"no valid checkpoint among steps {candidates} under "
            f"{self._dir}"
        ) from last_error

    def _trace_walk_back(self, step: int, why: str) -> None:
        """Torn-dir-walk forensics: each skipped candidate is one instant
        on the timeline, so a restore that silently landed three steps
        back is reconstructable from the flight recorder alone."""
        self._registry.trace.instant(
            "checkpoint/walk_back", {"step": step, "why": why}
        )

    def _walk_order(self) -> list[int]:
        """Candidate order for the fleet walk, from THIS process's view:
        newest-first within two tiers — fleet-valid steps (structural +
        every peer sidecar) first, then structurally-valid-only steps.
        Only the chief's order decides; followers compute theirs anyway
        so a disagreement (visibility skew) is logged and counted."""
        structural = [
            s
            for s in sorted(self._visible_steps(), reverse=True)
            if not fscklib.validate_step_dir(self._step_dir(s))
        ]
        # A step whose sidecar set is complete for its *stamped* topology
        # clears the same bar even when that topology differs from the
        # live fleet: every writing process's cursor is on disk, so the
        # cross-topology re-split can resume it without skipping a batch.
        complete = [
            s
            for s in structural
            if fscklib.fleet_sidecars_complete(self._dir, s, self._nproc)
            or fscklib.stamped_topology(self._dir, s) is not None
        ]
        done = set(complete)
        return complete + [s for s in structural if s not in done]

    def _restore_newest_valid_fleet(
        self,
        template: TrainState,
        accept=None,
        accept_name: str = "",
    ) -> tuple[TrainState, dict]:
        """The chief-decides walk (``restore_newest_valid`` docstring).
        Every round is: broadcast the chief's next candidate (−1 =
        exhausted → everyone raises together), all processes enter the
        collective restore of that step, then agree on failure/rejection
        with any-host reductions before accepting."""
        queue = self._walk_order()
        newest = queue[0] if queue else None
        tried: set[int] = set()
        last_error: Optional[BaseException] = None
        while True:
            # −1 = candidates existed but the walk exhausted them; −2 =
            # the chief saw no checkpoints at all.  The *agreed* code
            # picks the exception, so every process raises the same
            # class — a follower whose local view disagrees must not
            # crash differently from its chief.
            if any(s not in tried for s in queue):
                local_pick = next(s for s in queue if s not in tried)
            else:
                local_pick = -2 if not queue else -1
            step = self._agree_int(local_pick, "restore-pick")
            if step == -2:
                raise FileNotFoundError("no checkpoint found")
            if step < 0:
                raise NoValidCheckpointError(
                    f"no valid checkpoint among steps {sorted(tried)} "
                    f"under {self._dir} (chief-decided walk exhausted)"
                ) from last_error
            tried.add(step)
            failed = False
            out: Optional[tuple[TrainState, dict]] = None
            try:
                out = self._restore_step(template, step)
            except Exception as e:  # noqa: BLE001 — damage fsck can't see
                last_error = e
                failed = True
                log.warning(
                    "chief-decided step %d failed to restore here (%s)",
                    step, e,
                )
            if self._consensus.any_flag(failed, label="restore-failed"):
                if not failed:
                    log.warning(
                        "a peer failed to restore chief-decided step %d; "
                        "walking back with the fleet", step,
                    )
                self._trace_walk_back(
                    step, "unrestorable" if failed else "peer-unrestorable"
                )
                continue
            assert out is not None
            rejected = accept is not None and not accept(out[0])
            if self._consensus.any_flag(rejected, label="restore-rejected"):
                log.warning(
                    "checkpoint step %d rejected by the fleet (%s); "
                    "walking back",
                    step, accept_name or "accept predicate",
                )
                self._trace_walk_back(step, accept_name or "fleet-rejected")
                continue
            if newest is not None and step != newest:
                log.warning(
                    "restored step %d instead of the newest step %d "
                    "(newer candidates torn/unrestorable/rejected/"
                    "sidecar-incomplete)", step, newest,
                )
            # Consensus point: every process reached the same accepted
            # candidate (failure/rejection fleet-agreed above), so the
            # re-split pick broadcast below is in lockstep.
            return self._finalize_resize(out)

    def _restore_step(
        self, template: TrainState, step: int
    ) -> tuple[TrainState, dict]:
        # A previous walk candidate may have staged a re-split and then
        # been discarded (peer restore failure); never let it leak into
        # this candidate's finalize.
        self._pending_resize = None
        abstract = restore_abstract_tree(template)
        with self._registry.span(telemetry.CKPT_RESTORE):
            out = self._mgr.restore(
                step,
                args=ocp.args.Composite(
                    state=ocp.args.StandardRestore(abstract),
                    data=ocp.args.JsonRestore(),
                ),
            )
        tree = out.state
        state = template.replace(
            step=tree["step"],
            params=tree["params"],
            batch_stats=tree["batch_stats"],
            opt_state=tree["opt_state"],
            ema_params=tree["ema_params"],
            carry=tree["carry"],
        )
        data = dict(out.data or {})
        meta = data.pop(_FLEET_META_KEY, None)
        saved_nproc: Optional[int] = None
        if isinstance(meta, dict):
            try:
                saved_nproc = int(meta["nproc"])
            except (KeyError, TypeError, ValueError):
                saved_nproc = None
        if saved_nproc is None:
            # Pre-stamp checkpoint: fall back to the sidecar set's
            # stamped topology (None again for a genuinely unstamped
            # single-process or legacy layout).  The orbax meta is the
            # authoritative detector — every host reads the same JSON,
            # so crossing detection cannot skew across the fleet.
            saved_nproc = fscklib.stamped_topology(self._dir, step)
        if saved_nproc is not None and saved_nproc != self._nproc:
            data = self._prepare_resize(step, saved_nproc, data)
        elif self._nproc > 1:
            path = self._sidecar(step)
            wrapped = None
            missing_why = "no per-process dataset sidecar"
            if os.path.exists(path):
                # A truncated/unparseable sidecar (torn write at
                # preemption time) must degrade to the primary's
                # position exactly like a missing one — never kill the
                # job at restore time over an *auxiliary* file.
                try:
                    with open(path) as f:
                        wrapped = json.load(f)
                except (OSError, ValueError) as e:
                    missing_why = f"dataset sidecar is unreadable ({e})"
            if wrapped is None:
                log.warning(
                    "%s at %s; using the primary's position (approximate "
                    "resume)",
                    missing_why,
                    path,
                )
                self._registry.counter(
                    telemetry.CKPT_SIDECAR_FALLBACKS
                ).inc()
            elif "nproc" not in wrapped:
                # Legacy bare-dict sidecar (pre-topology-stamp): same
                # format, assume same topology — and stamp-and-rewrite
                # the file so the unstamped format cannot survive into a
                # later resize undetected (an unstamped sidecar is
                # invisible to stamped_topology and would silently
                # degrade a crossing to the primary's position).
                data = wrapped
                self._stamp_legacy_sidecar(path, wrapped)
            elif wrapped["nproc"] == self._nproc:
                data = wrapped["state"]
            else:
                # Stamp says a different topology than both the live
                # fleet and the orbax meta (mixed/partial sidecar set):
                # degrade like a missing sidecar rather than adopt a
                # wrong-shard position.
                log.warning(
                    "dataset sidecar at %s is from a %s-process run, not "
                    "%d; using the primary's position (approximate resume)",
                    path,
                    wrapped["nproc"],
                    self._nproc,
                )
                self._registry.counter(
                    telemetry.CKPT_SIDECAR_FALLBACKS
                ).inc()
        return state, data

    def _stamp_legacy_sidecar(self, path: str, bare_state: dict) -> None:
        """Rewrite a legacy bare-dict sidecar in the stamped format
        (atomic, best-effort — failing to upgrade an auxiliary file must
        never fail the restore that read it fine)."""
        try:
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump({"nproc": self._nproc, "state": bare_state}, f)
            os.replace(tmp, path)
            log.info(
                "stamped legacy dataset sidecar %s with nproc=%d",
                path, self._nproc,
            )
        except OSError as e:  # noqa: BLE001 — upgrade is advisory
            log.warning("could not stamp legacy sidecar %s (%s)", path, e)

    def _read_sidecar_state(self, step: int, pid: int) -> Optional[dict]:
        """One saved process's dataset state at ``step`` (unwrapped;
        handles both stamped and legacy shapes), or None."""
        try:
            with open(self._sidecar(step, pid)) as f:
                wrapped = json.load(f)
        except (OSError, ValueError):
            return None
        if not isinstance(wrapped, dict):
            return None
        if "nproc" in wrapped:
            state = wrapped.get("state")
            return state if isinstance(state, dict) else None
        return wrapped

    def _prepare_resize(
        self, step: int, saved_nproc: int, primary: dict
    ) -> dict:
        """Stage the cross-topology dataset re-split for this candidate.

        Local and deterministic only: reads the writing fleet's sidecars
        and computes the fleet-minimum pick (``data/resplit.py``).  The
        consensus broadcast, counters, and ledger happen in
        :meth:`_finalize_resize`, after the walk has agreed this
        candidate is the one — a broadcast here would be reached by a
        subset of hosts whenever a peer's restore throws.
        """
        states: dict = {}
        for pid in range(saved_nproc):
            state = self._read_sidecar_state(step, pid)
            if state is not None:
                states[pid] = state
        local_pick = resplitlib.pick_source(states)
        self._pending_resize = {
            "step": step,
            "from_nproc": saved_nproc,
            "states": states,
            "local_pick": local_pick,
            "primary": primary,
        }
        return primary if local_pick < 0 else states[local_pick]

    def _finalize_resize(
        self, out: tuple[TrainState, dict]
    ) -> tuple[TrainState, dict]:
        """Resolve a staged cross-topology re-split on the accepted
        candidate: fleet-agree the source pid (chief broadcasts, exact
        no-op single-process), adopt that sidecar's cursor everywhere,
        count + trace the crossing, and have the chief write the audit
        ledger.  Identity for same-shape restores (nothing staged)."""
        pend, self._pending_resize = self._pending_resize, None
        self._last_resize = None
        if pend is None:
            return out
        step = pend["step"]
        pick = pend["local_pick"]
        if self._consensus.active:
            pick = self._agree_int(pick, f"resize-pick@{step}")
        self._registry.counter(telemetry.CKPT_RESIZE_RESTORES).inc()
        self._registry.trace.instant(
            "checkpoint/resize_restore",
            {
                "step": step,
                "from_nproc": pend["from_nproc"],
                "to_nproc": self._nproc,
                "source_pid": pick,
            },
        )
        state = pend["states"].get(pick) if pick >= 0 else None
        if state is None and pick >= 0:
            # The chief picked a sidecar this host failed to read
            # (visibility skew); the pick names a file, so retry the
            # read rather than silently diverge from the fleet.
            state = self._read_sidecar_state(step, pick)
        if state is None:
            log.warning(
                "cross-topology restore at step %d (%d -> %d processes): "
                "no usable dataset cursor among the saved sidecars; "
                "using the primary's position (approximate resume)",
                step, pend["from_nproc"], self._nproc,
            )
            self._registry.counter(telemetry.CKPT_SIDECAR_FALLBACKS).inc()
            data = pend["primary"]
        else:
            log.warning(
                "CROSS-TOPOLOGY RESTORE at step %d: checkpoint written "
                "by %d process(es), restoring onto %d — dataset cursor "
                "re-split to the fleet-minimum safe position (source "
                "sidecar p%d); at most one in-flight chunk per host is "
                "re-read and no untrained batch is skipped",
                step, pend["from_nproc"], self._nproc, pick,
            )
            data = state
        self._last_resize = {
            "step": step,
            "from_nproc": pend["from_nproc"],
            "to_nproc": self._nproc,
            "source_pid": pick,
        }
        if self._pid == 0:
            self._write_resize_ledger(pend, pick)
        return out[0], data

    def _write_resize_ledger(self, pend: dict, pick: int) -> None:
        """Audit artifact for the crossing (chief only, atomic,
        best-effort): every saved pid's cursor position, the agreed
        source, and the adopted position — the proof, checkable after
        the fact, that the resume point was <= every saved position,
        i.e. that no untrained batch was skipped."""
        step = pend["step"]
        base = os.path.join(self._dir, "dataset_states", str(step))
        adopted = resplitlib.cursor_position(pend["states"].get(pick))
        ledger = dict(resplitlib.describe_positions(pend["states"]))
        ledger.update(
            {
                "step": step,
                "from_nproc": pend["from_nproc"],
                "to_nproc": self._nproc,
                "source_pid": pick,
                "adopted_position": (
                    list(adopted) if adopted is not None else None
                ),
            }
        )
        try:
            os.makedirs(base, exist_ok=True)
            path = os.path.join(base, RESIZE_LEDGER)
            tmp = f"{path}.tmp"
            with open(tmp, "w") as f:
                json.dump(ledger, f, indent=1)
            os.replace(tmp, path)
        except OSError as e:  # noqa: BLE001 — audit trail is advisory
            log.warning("could not write resize ledger at %s (%s)", base, e)

    def is_saving(self) -> bool:
        """True while a previously dispatched async save is still being
        written in the background."""
        try:
            return bool(self._mgr.is_saving_in_progress())
        except Exception:  # noqa: BLE001 — orbax API drift: assume pending
            return True

    def fence(self) -> None:
        """Durability fence for the *overlap* path: block until pending
        async saves finish, recorded under ``checkpoint/fence``.  No-op
        (and no metric record) when nothing is in flight, so the timer's
        count is the number of times the save cadence actually outran
        the background writer and its total is the wall time that
        overrun cost — the exact number the ``checkpoint_every_steps``
        tightening trade is priced on.  Teardown/emergency paths use
        :meth:`wait` instead (always recorded: their block is the point).
        """
        if not self.is_saving():
            return
        with self._registry.span(telemetry.CKPT_FENCE):
            self._mgr.wait_until_finished()

    def wait(self) -> None:
        """Block until pending async saves are durable (the explicit
        fence of the emergency-save / rollback / chaos-tear / teardown
        paths — always recorded, under ``checkpoint/wait``)."""
        with self._registry.span(telemetry.CKPT_WAIT):
            self._mgr.wait_until_finished()

    def close(self) -> None:
        with self._registry.span(telemetry.CKPT_WAIT):
            self._mgr.wait_until_finished()
        self._mgr.close()


def restore_or_init(
    manager: CheckpointManager, template: TrainState
) -> tuple[TrainState, dict, bool]:
    """``SessionManager.prepare_session`` semantics (TF
    session_manager.py:259): restore the latest checkpoint when one exists,
    otherwise return the fresh ``template``.  Returns
    ``(state, dataset_state, restored)``.

    When checkpoints exist but every candidate is torn (restore
    hardening found no valid step), training starts fresh with a loud
    warning — for auto-resume, re-training from scratch is strictly
    better than a job that can never start again until a human deletes
    the damage.

    Training resume additionally gates candidates on finiteness: a
    crash-time save after a NaN trip (CheckpointHook.abort) is
    structurally valid but poisoned — without the gate it becomes the
    newest checkpoint and every rerun restores NaN and dies, bricking
    the workdir.  (Eval/generate restore via ``manager.restore`` and
    stay ungated — they read only params/EMA.)

    Multi-host, restore-vs-init is itself **chief-decided**: whether any
    checkpoint exists is read from process 0's view and broadcast, so a
    fleet where one host's listing lags (visibility skew) still makes
    one choice — all restore (the chief-decided walk names the step) or
    all init fresh."""
    cons = manager.consensus
    has_checkpoint = manager.latest_step() is not None
    if cons.active:
        has_checkpoint = bool(
            cons.broadcast_int(int(has_checkpoint), label="restore-or-init")
        )
    if not has_checkpoint:
        return template, {}, False
    from distributed_tensorflow_models_tpu.core.train_loop import (
        state_is_finite,
    )

    try:
        state, data = manager.restore_newest_valid(
            template,
            accept=state_is_finite,
            accept_name="non-finite state (post-divergence save)",
        )
    except NoValidCheckpointError as e:
        log.error(
            "checkpoints exist but none are restorable (%s); "
            "initializing fresh — run scripts/fsck_checkpoints.py "
            "--repair to clear the torn steps", e,
        )
        return template, {}, False
    resize = manager.last_resize
    if resize is not None:
        log.warning(
            "RESUMING ACROSS A FLEET RESIZE: checkpoint at step %d was "
            "written by %d process(es), this fleet has %d — arrays were "
            "resharded onto the live mesh and the dataset cursor was "
            "re-split (source sidecar p%d; see %s in the step's "
            "dataset_states dir).  Same-shape guarantees do not apply: "
            "the post-resize trajectory is equivalent, not bit-identical.",
            resize["step"], resize["from_nproc"], resize["to_nproc"],
            resize["source_pid"], RESIZE_LEDGER,
        )
    log.info("restored checkpoint at step %d", int(state.step))
    return state, data, True
