"""The compiled SPMD train step and its builders.

This single module replaces the reference's entire synchronization stack
(SURVEY.md §3.1–§3.2): per-variable ``ConditionalAccumulator``s on PS tasks,
the chief's ``take_grad(N)`` aggregation thread, the token ``FIFOQueue``
barrier, and ``MonitoredTrainingSession``'s chief/worker session dance
(TF sync_replicas_optimizer.py:215-338; monitored_session.py:428).

The TPU-native form: the batch is one global array sharded over the ``data``
mesh axis; parameters are replicated (or sharded over ``model`` for tensor
parallelism); the loss is a global mean.  ``jax.grad`` of that mean makes XLA
emit a partial gradient per chip plus an all-reduce over ICI — the whole
accumulator/token protocol becomes one fused collective inside one compiled
program, and the barrier is implicit in the collective's semantics.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, Callable, Mapping, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh

from distributed_tensorflow_models_tpu import telemetry
from distributed_tensorflow_models_tpu.core import sharding as shardlib
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.ops import ema as emalib
from distributed_tensorflow_models_tpu.ops import losses as losslib
from distributed_tensorflow_models_tpu.ops import metrics as metriclib

log = logging.getLogger("dtm")

PyTree = Any
Batch = Mapping[str, jax.Array]
# loss_fn(params, state, batch, rngs) -> (loss, aux) where aux is a dict
# that may carry: 'metrics' (dict of scalars), 'batch_stats' (updated BN
# state), 'carry' (updated recurrent state).  Omitted keys mean "unchanged".
LossFn = Callable[
    [PyTree, TrainState, Batch, Mapping[str, jax.Array]],
    tuple[jax.Array, dict],
]


def classification_loss_fn(
    apply_fn: Callable,
    *,
    label_smoothing: float = 0.0,
    weight_decay: float = 0.0,
    aux_loss_weight: float = 0.0,
) -> LossFn:
    """Forward + loss for image-classification models.

    Covers every CNN config in the reference zoo (SURVEY.md §2.1 R3-R7):
    plain softmax cross entropy; slim-style L2 weight decay on kernels;
    label smoothing and the 0.4-weighted auxiliary-logits head for
    Inception-v3 (R5).  Models return either ``logits`` or
    ``(logits, aux_logits)``.
    """

    def loss_fn(params, state, batch, rngs):
        batch_stats = state.batch_stats
        variables = {"params": params}
        has_bn = bool(jax.tree_util.tree_leaves(batch_stats))
        if has_bn:
            variables["batch_stats"] = batch_stats
            outputs, updated = apply_fn(
                variables,
                batch["image"],
                train=True,
                rngs=dict(rngs),
                mutable=["batch_stats"],
            )
            new_batch_stats = updated["batch_stats"]
        else:
            outputs = apply_fn(
                variables, batch["image"], train=True, rngs=dict(rngs)
            )
            new_batch_stats = batch_stats
        if isinstance(outputs, (tuple, list)):
            logits, aux_logits = outputs[0], outputs[1]
        else:
            logits, aux_logits = outputs, None

        labels = batch["label"]
        xent = losslib.mean_softmax_cross_entropy(
            logits, labels, label_smoothing
        )
        loss = xent
        if aux_logits is not None and aux_loss_weight:
            loss = loss + aux_loss_weight * losslib.mean_softmax_cross_entropy(
                aux_logits, labels, label_smoothing
            )
        if weight_decay:
            loss = loss + losslib.l2_weight_decay(params, weight_decay)
        metrics = {
            "loss": loss,
            "xent": xent,
            "accuracy": metriclib.accuracy(logits, labels),
        }
        return loss, {"metrics": metrics, "batch_stats": new_batch_stats}

    return loss_fn


def lm_loss_fn(apply_fn: Callable, fused_unembed: bool = False) -> LossFn:
    """Forward + loss for the PTB LSTM (SURVEY.md §2.1 R8).

    ``fused_unembed=True`` routes the head projection + cross entropy
    through :func:`...ops.losses.fused_unembed_mean_xent` (the model must
    accept ``return_hidden=True`` — the transformer does); bfloat16 MXU
    matmul, f32 accumulation, O(chunk, V) peak memory instead of
    O(B·T·V).

    Batch keys: ``inputs`` and ``targets``, both ``[B, T]`` int32 (targets
    are inputs shifted by one token, the reference PTB reader convention).
    The model consumes and returns the recurrent carry; the carry is read
    from ``state.carry`` and the updated value is returned through aux, so
    truncated-BPTT state threads across segments exactly as the reference
    threads final LSTM state into the next ``session.run`` (SURVEY.md
    §7.4.5).  Gradients do not flow into previous segments — the carry
    enters as a leaf input, which *is* truncation.

    Metrics include ``nll`` (mean per-token negative log-likelihood);
    perplexity = ``exp(nll)`` as the reference reports it.

    Models may ``sow`` scalar regularizers into the ``losses`` collection
    (the transformer's Switch-MoE load-balancing loss does); every leaf is
    summed into the objective but kept out of ``nll`` so perplexity stays
    comparable across dense and MoE configs.  What a model sows into
    ``moe_stats`` (the top-k expert layer: its unweighted ``aux_loss`` and
    ``z_loss`` and ``load_max_over_mean``) is averaged over layers into
    the metrics as ``moe_<name>`` and never touches the objective.
    """

    def loss_fn(params, state, batch, rngs):
        if fused_unembed:
            # Fused path: the model stops at the post-ln_f hidden states
            # and the head projection + xent run chunked in one op —
            # never materializing [B*T, V] f32 logits, and finishing
            # the head's gradient while each chunk's logits are live
            # (ops/losses.py::fused_unembed_mean_xent).
            (hidden, new_carry), updated = apply_fn(
                {"params": params},
                batch["inputs"],
                carry=state.carry,
                train=True,
                rngs=dict(rngs),
                mutable=["losses", "moe_stats"],
                return_hidden=True,
            )
            if "head" in params:
                head = params["head"]
                kernel, bias = head["kernel"], head.get("bias")
            else:
                # A tied head: the embedding matrix ``[V, d]`` the other
                # way round; its gradient joins the gather's.
                kernel, bias = params["embedding"]["embedding"].T, None
            nll = losslib.fused_unembed_mean_xent(
                hidden, kernel, bias, batch["targets"]
            )
        else:
            (logits, new_carry), updated = apply_fn(
                {"params": params},
                batch["inputs"],
                carry=state.carry,
                train=True,
                rngs=dict(rngs),
                mutable=["losses", "moe_stats"],
            )
            nll = jnp.mean(losslib.token_xent(logits, batch["targets"]))
        aux = sum(
            jnp.sum(leaf)
            for leaf in jax.tree_util.tree_leaves(updated.get("losses", {}))
        )
        loss = nll + aux
        metrics = {"loss": loss, "nll": nll}
        if updated.get("losses"):
            metrics["aux_loss"] = aux
        by_name: dict = {}
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            updated.get("moe_stats", {})
        ):
            by_name.setdefault(path[-1].key, []).append(leaf)
        for name, leaves in by_name.items():
            metrics[f"moe_{name}"] = sum(leaves) / len(leaves)
        return loss, {"metrics": metrics, "carry": new_carry}

    return loss_fn


def make_train_step(
    loss_fn: LossFn,
    rng_names: Sequence[str] = ("dropout",),
    donate: bool | None = None,
    state_shardings: Optional[PyTree] = None,
) -> Callable[[TrainState, Batch, jax.Array], tuple[TrainState, dict]]:
    """Build the jitted ``(state, batch, rng) -> (state, metrics)`` step.

    Equivalent of the whole worker-side hot loop in SURVEY.md §3.1 plus the
    chief's §3.2 aggregation duties, compiled to one XLA program.  The step
    is deterministic given ``rng`` and ``state.step`` (per-step keys are
    derived by ``fold_in``), which is what makes the distributed run
    reproducible — no arrival-order races as in the reference's async mode
    (SURVEY.md §5.2).

    ``donate`` defaults to True on accelerators (in-place state update —
    halves HBM pressure for the params/opt_state pytrees) and to False on
    the CPU: the XLA CPU thunk runtime can wedge its in-process collective
    rendezvous when donated buffers and cross-partition all-reduces mix on
    a small host thread pool (one partition never reaches the rendezvous;
    the runtime aborts after 40 s).  CPU is only used for fake-mesh
    testing, where donation buys nothing anyway.

    ``state_shardings`` is the layout of the state the program is built
    for (:func:`_jit_state_program`).
    """
    step_fn = make_train_step_fn(loss_fn, rng_names)

    def one_step(state: TrainState, batch: Batch, rng: jax.Array):
        # Compiled as the K=1 instance of the fused multi-step program —
        # the exact lax.scan body :func:`make_multi_step` runs.  XLA
        # optimizes a while-loop body slightly differently from the same
        # math as straight-line code (measured ~1e-7 param drift per step
        # on the CPU fake mesh), so sharing the scan form is what makes
        # ``steps_per_loop ∈ {1, K}`` trajectories bit-identical rather
        # than merely close (tests/test_train_loop.py pins this; scan
        # programs of different lengths agree exactly).  The length-1
        # expand/squeeze is free: layout-only ops inside the jit.
        chunk = jax.tree.map(lambda x: x[None], batch)

        def body(s, b):
            return step_fn(s, b, rng)

        new_state, rows = jax.lax.scan(body, state, chunk)
        return new_state, jax.tree.map(lambda x: x[0], rows)

    return _jit_state_program(one_step, donate, state_shardings)


def default_donate() -> bool:
    """Donation default shared by the single-step and fused multi-step
    builders: on for every accelerator backend, off on the CPU (see
    :func:`make_train_step`)."""
    return jax.default_backend() != "cpu"


def make_multi_step(
    loss_fn: LossFn,
    unroll: int = 1,
    rng_names: Sequence[str] = ("dropout",),
    donate: bool | None = None,
    state_shardings: Optional[PyTree] = None,
) -> Callable[[TrainState, Batch, jax.Array], tuple[TrainState, dict]]:
    """Fused K-step train program: one dispatch, one device→host metrics
    transfer per *chunk* of K steps instead of per step.

    ``lax.scan``s the raw step over batches stacked on a new leading axis
    (``data/pipeline.py::BatchStacker`` assembles them): the returned
    jitted callable maps ``(state, stacked_batches, rng) ->
    (state, stacked_metrics)`` where every metrics leaf gains a leading
    length-K axis — per-step rows, accumulated on device, fetched in one
    transfer (or lazily, row by row, by the hook layer).

    Trajectory equivalence with K dispatches of :func:`make_train_step` is
    exact, not approximate, because every per-step dependency threads
    through the scan carry exactly as it threads through the host loop:

    - **rng**: per-step keys derive from ``fold_in(rng, state.step)`` with
      the *in-carry* step, so step ``s`` draws identical randomness
      whichever loop ran it;
    - **BN/carry**: ``batch_stats`` and the recurrent ``carry`` ride the
      ``TrainState`` carry, so step ``s+1`` sees step ``s``'s statistics;
    - **donation**: the chunk program donates the input state into the
      scan carry (same default as the single step), so HBM pressure
      does not grow with K.

    K is a trace-time constant (the stacked leading dim): each distinct
    chunk length compiles its own program, so drivers should stick to one
    K plus the few shrunken boundary tails.  ``unroll`` is forwarded to
    ``lax.scan`` (bigger compiled program, more cross-step overlap for
    XLA to find; 1 — the default — compiles fastest).
    ``state_shardings`` as in :func:`make_train_step`.
    """
    step_fn = make_train_step_fn(loss_fn, rng_names)

    def multi_step_fn(state: TrainState, batches: Batch, rng: jax.Array):
        def body(s, batch):
            return step_fn(s, batch, rng)

        return jax.lax.scan(body, state, batches, unroll=unroll)

    return _jit_state_program(multi_step_fn, donate, state_shardings)


def _jit_state_program(
    fn: Callable, donate: bool | None, state_shardings: Optional[PyTree]
) -> Callable:
    """The one jit site of every program ``(state, batch, rng) -> (state,
    metrics)``.

    ``state_shardings`` is a ``TrainState``-shaped tree of shardings: the
    ``.sharding`` of every leaf of the placed state the program is built
    for (:func:`state_layout` decides them).  The program is compiled to
    hand the state back under exactly those, so every later step's input
    has the layout of the first: the compiler's propagation cannot move
    a leaf nobody named (on a mesh with a second axis it did, and the
    next call compiled again or, ahead of time, was refused), and
    donation aliases every leaf.  The metrics' layout is the compiler's
    choice, and with ``None`` (a caller with no placed state) the
    state's is too.
    """
    if donate is None:
        donate = default_donate()
    return jax.jit(
        fn,
        donate_argnums=(0,) if donate else (),
        out_shardings=(state_shardings, None),
    )


def _abstract(args: PyTree) -> PyTree:
    """Shape, dtype and sharding of every array leaf: enough to lower
    the same program again after the buffers are gone."""
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if isinstance(x, jax.Array)
        else x,
        args,
    )


def program_flops(lowered, compiled=None) -> float:
    """GLOBAL FLOPs of one call of the ``lowered`` program, from XLA's
    cost analysis.

    Where the backend costs a lowering (the CPU), that is the unoptimized
    global module: trace-only, no backend compile, and it matches the
    compiled count for matmul/conv-dominated graphs.  The TPU's PJRT
    plugin costs compiled programs only (``Lowered.cost_analysis()`` is
    None there).  A compiled SPMD program is ONE device's partition, so
    its count is scaled by the number of devices it runs on.  Pass
    ``compiled`` when the executable is already at hand; otherwise the
    lowering is compiled here, which the persistent cache makes cheap
    once the same program has been compiled by the step itself.

    Either way a scan/while body is counted once whatever its trip count,
    and Pallas custom-calls count zero, so MFU is conservative, never
    inflated.
    """
    cost = lowered.cost_analysis()
    scale = 1
    if cost is None:
        if compiled is None:
            compiled = lowered.compile()
        cost = compiled.cost_analysis()
        scale = jax.tree.leaves(compiled.input_shardings)[0].num_devices
    return max(float(cost["flops"]), 0.0) * scale


class InstrumentedStep:
    """Wrap a jitted train step with compile + dispatch telemetry.

    jit compiles silently inside the first call (and again on every new
    input signature), which makes two production failure classes
    invisible: a recompile storm (shape or sharding instability re-paying
    the compile cost every few steps) and compile time masquerading as
    slow steps.  This wrapper surfaces both without changing execution
    semantics — every call still goes through the wrapped jit, or
    through the AOT executable compiled from it.

    - **Compile events**: the jit's compilation-cache size is read before
      and after each call (~0.05 µs); a growth means that call compiled,
      and its wall time is recorded into the ``train/compile`` timer
      (count = compile events, total = seconds — compile-dominated, one
      dispatch's enqueue time included).  Works for *every* recompile
      trigger, including sharding changes a batch-shape key would miss.
    - **FLOPs**: per new batch signature (leaf shapes/dtypes), XLA's
      cost analysis of the step program (:func:`program_flops`) feeds the
      ``train/flops_per_step`` gauge (the *current* program's cost) and,
      per executed step, the per-signature FLOPs accumulate into the
      ``train/flops_total`` counter — the MFU numerator.  The counter,
      not ``gauge × steps``, is what MFU readers use, so a ragged final
      batch (smaller program, new signature) scales the accounting for
      *its* steps only instead of silently re-pricing the whole run.
      The program is lowered
      *before* the call, while input buffers are still valid under
      donation, and costed *after* it, when the compiled program exists
      (in the AOT handle, or in the persistent cache).  A step whose
      FLOPs cannot be had raises: an MFU of 0.0 on a chip would be a
      wrong number, not a missing one.
    - **Dispatch**: non-compiling calls are timed into ``train/dispatch``
      (host-side enqueue under async dispatch — the data-wait vs
      dispatch split is the diagnostic, not a device profile).
    """

    def __init__(
        self,
        step_fn: Callable,
        registry: Optional[telemetry.MetricsRegistry] = None,
        aot: Optional[object] = None,
    ):
        self._fn = step_fn
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        # Optional ahead-of-time handle (harness/startup.py::AotTrainStep):
        # when its batch signature matches a call's, the pre-compiled
        # executable runs instead of the jit dispatch.  The FIRST AOT use
        # is accounted as the run's compile event (one train/compile
        # record covering the join-on-in-flight-compile remainder plus
        # that dispatch) so compile/dispatch counts stay exactly what the
        # jit path produces — per-signature: one compile, then dispatches.
        self._aot = aot
        self._flops_by_sig: dict = {}
        self.flops_per_step: Optional[float] = None
        # What the calls ran, per batch signature, for the scope map
        # (:meth:`executables`): the AOT executable itself, or the
        # abstract arguments of a jit call (its executable is the jit
        # cache's own and can only be had by lowering again).
        self._aot_ran: dict = {}
        self._jit_ran: dict = {}

    @staticmethod
    def _signature(batch) -> tuple:
        return tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(batch)
        )

    def _cache_size(self) -> Optional[int]:
        try:
            return self._fn._cache_size()
        except Exception:  # noqa: BLE001 — non-jitted callable
            return None

    def _lower_for_flops(self, state, batch, rng):
        """Trace-only lowering of the program that prices one step; None
        for a non-jitted callable, which has no program to cost."""
        lower = getattr(self._fn, "lower", None)
        return lower(state, batch, rng) if lower is not None else None

    def _compiled_for_flops(self, sig):
        """The already-compiled form of that program, if any: the AOT
        handle's executable when it serves this signature."""
        return self._aot.executable(sig) if self._aot is not None else None

    def _record_flops(self, sig, lowered) -> float:
        flops = 0.0
        if lowered is not None:
            flops = program_flops(lowered, self._compiled_for_flops(sig))
        if flops > 0:
            self.flops_per_step = flops
            self._registry.gauge(telemetry.FLOPS_PER_STEP).set(flops)
        self._flops_by_sig[sig] = flops
        return flops

    def _call_timed(self, sig, state, batch, rng):
        """Run the step via the AOT executable (signature match) or the
        jit fn, timed into exactly one compile-or-dispatch record.  The
        compile classification covers both triggers: a jit cache growth,
        or the first use of the AOT program (whose record includes any
        blocking on the still-in-flight background compile)."""
        before = self._cache_size()
        t0 = time.perf_counter()
        fn, used_aot, aot_first = self._fn, False, False
        if self._aot is not None:
            exe, aot_first = self._aot.acquire(sig)
            if exe is not None:
                fn, used_aot = exe, True
                self._aot_ran[sig] = exe
        if not used_aot and sig not in self._jit_ran:
            # Before the call: donation deletes the state's buffers.
            self._jit_ran[sig] = _abstract((state, batch, rng))
        try:
            out = fn(state, batch, rng)
        except TypeError:
            if not used_aot:
                raise
            # An AOT executable is stricter than jit: it REJECTS inputs
            # whose avals/shardings drifted with a TypeError raised
            # BEFORE executing, so no buffers were consumed and the jit
            # retry is safe even under donation.  Deliberately narrow —
            # a mid-execution runtime failure may already have
            # invalidated donated inputs, and retrying would mask the
            # real error with "Array has been deleted"; those propagate.
            log.warning(
                "AOT train-step executable rejected the call; falling "
                "back to the jit path", exc_info=True,
            )
            self._aot.disable()
            self._aot_ran.pop(sig, None)
            self._jit_ran[sig] = _abstract((state, batch, rng))
            out = self._fn(state, batch, rng)
        dt = time.perf_counter() - t0
        compiled = aot_first or (
            before is not None and self._cache_size() != before
        )
        name = telemetry.COMPILE if compiled else telemetry.DISPATCH
        self._registry.timer(name).record(dt)
        tr = self._registry.trace
        if tr.enabled:
            # The dispatch/compile split on the flight-recorder timeline:
            # compile events are rare and load-bearing (a recompile storm
            # is visible as a train of them); dispatches bound the ring's
            # reach, which is the ring's job.
            tr.complete(
                name, dt, ts_mono=t0,
                args={"aot": True} if used_aot else None,
            )
        return out

    def _call_and_account(self, sig, steps, state, batch, rng):
        """One timed call, then ``steps`` × the signature's per-step
        FLOPs onto the retired-FLOPs counter.  A new signature is lowered
        before the call and costed after it (see the class docstring)."""
        flops = self._flops_by_sig.get(sig)
        lowered = (
            self._lower_for_flops(state, batch, rng) if flops is None else None
        )
        out = self._call_timed(sig, state, batch, rng)
        if flops is None:
            flops = self._record_flops(sig, lowered)
        if flops:
            self._registry.counter(telemetry.FLOPS_TOTAL).inc(flops * steps)
        return out

    def executables(self) -> list:
        """The compiled programs the calls so far ran, one per batch
        signature: the AOT executable where it served the signature,
        else the jit program lowered and compiled again from the call's
        abstract arguments (a read of the persistent cache for a program
        worth caching; the tracing and lowering are paid in full).  For
        reading after the run (``fit`` writes the scope map from their
        text under ``cfg.trace_export``), never on the step path."""
        out = list(self._aot_ran.values())
        lower = getattr(self._fn, "lower", None)
        if lower is not None:
            out += [lower(*args).compile() for args in self._jit_ran.values()]
        return out

    def __call__(self, state, batch, rng):
        sig = self._signature(batch)
        if sig not in self._flops_by_sig and self._flops_by_sig:
            log.warning(
                "train step saw a new batch signature %s (%d prior) "
                "— recompile storms show up as a growing compile "
                "count in telemetry",
                sig,
                len(self._flops_by_sig),
            )
        return self._call_and_account(sig, 1, state, batch, rng)


class InstrumentedMultiStep(InstrumentedStep):
    """Chunk-aware :class:`InstrumentedStep` for the fused multi-step
    program: ``__call__(state, stacked_batches, rng)`` where the stacked
    leading axis is the chunk length K.

    Telemetry stays comparable across ``steps_per_loop`` values:

    - **FLOPs per chunk = K × the per-step signature cost.**  XLA cost
      analysis visits a scan/while body ONCE, ignoring the trip count (a
      K-step scan is costed as its body), so analysing the chunk program
      would under-count by exactly K.  Instead the per-step cost
      comes from the raw single step (``flops_step_fn``) lowered on one
      unstacked batch row (and, on the TPU, compiled — a second program
      beside the chunk's, paid once per signature), and the
      ``train/flops_total`` counter advances by K× that per executed
      chunk — so MFU readers see the same numerator either loop produces.
    - **Dispatch/compile**: one ``train/dispatch`` (or ``train/compile``)
      record per chunk — the per-chunk host cost IS the quantity the
      fused loop exists to amortise, so it is recorded raw; per-step
      comparisons divide by K (TelemetryHook's ``dispatch_s`` reads
      per-chunk under K>1, documented in README "Performance").

    ``train/step_time`` (chunk wall ÷ K) is recorded by the driver, which
    owns the full-iteration clock.
    """

    def __init__(
        self,
        multi_fn: Callable,
        flops_step_fn: Optional[Callable] = None,
        registry: Optional[telemetry.MetricsRegistry] = None,
        aot: Optional[object] = None,
    ):
        super().__init__(multi_fn, registry, aot=aot)
        # Lowered for its cost, never run: nothing to donate or to pin.
        self._flops_fn = (
            _jit_state_program(
                flops_step_fn, donate=False, state_shardings=None
            )
            if flops_step_fn is not None
            else None
        )

    def _lower_for_flops(self, state, batches, rng):
        """Per-STEP program: the raw single step on batch row 0 (one
        device gather per new signature; trace-only lowering after
        that)."""
        if self._flops_fn is None:
            return None
        row = jax.tree.map(lambda x: x[0], batches)
        return self._flops_fn.lower(state, row, rng)

    def _compiled_for_flops(self, sig):
        # The AOT handle holds the chunk program, not the single step.
        return None

    def __call__(self, state, batches, rng):
        k = jax.tree_util.tree_leaves(batches)[0].shape[0]
        sig = self._signature(batches)
        if sig not in self._flops_by_sig:
            # New signature == new chunk length or batch shape; each
            # compiles its own scan program.  The driver keeps the set
            # small (one main K plus boundary tails), so tolerate a few
            # before raising the parent's recompile-storm diagnostic —
            # a shape-unstable dataset must still be surfaced.
            if len(self._flops_by_sig) >= 3:
                log.warning(
                    "fused train step saw a new chunk signature %s "
                    "(%d prior — expected one main K plus a few "
                    "boundary tails); recompile storms show up as a "
                    "growing compile count in telemetry",
                    sig,
                    len(self._flops_by_sig),
                )
        return self._call_and_account(sig, k, state, batches, rng)


def per_step_rngs(
    rng: jax.Array, salt: jax.Array | int, rng_names: Sequence[str]
) -> dict[str, jax.Array]:
    """Derive the per-step named rng dict: ``fold_in`` the step (or event)
    counter, then one fold per rng name.  Shared by the sync train step and
    the async-PS emulator so their trajectories agree by construction."""
    step_rng = jax.random.fold_in(rng, salt)
    return {
        name: jax.random.fold_in(step_rng, i)
        for i, name in enumerate(rng_names)
    }


# ``jax.named_scope`` of everything after the gradients (gradient norm,
# clipping, ``tx.update``, ``apply_updates``, EMA): a path element of
# every such instruction's ``op_name`` in the compiled step, which
# ``step_scopes_p<i>.json`` carries to the device trace (PERF.md section 3).
OPTIMIZER_SCOPE = "optimizer"


@jax.named_scope(OPTIMIZER_SCOPE)
def apply_gradients(state: TrainState, grads: PyTree, aux: dict) -> TrainState:
    """Optimizer update + state advance from one grad computation's output.

    Consumes the full ``aux`` contract of :data:`LossFn` (``batch_stats``,
    ``carry``) and maintains the EMA shadows — the single place where a
    gradient becomes a new :class:`TrainState`, used by both the sync SPMD
    step and the async-PS emulation (TF optimizer.py:656's
    ``apply_gradients`` role)."""
    updates, new_opt_state = state.tx.update(
        grads, state.opt_state, state.params
    )
    new_params = optax.apply_updates(state.params, updates)
    new_ema = state.ema_params
    if state.ema_params is not None:
        new_ema = emalib.update_ema(
            state.ema_params,
            new_params,
            state.ema_decay,
            num_updates=state.step,
        )
    return state.replace(
        step=state.step + 1,
        params=new_params,
        batch_stats=aux.get("batch_stats", state.batch_stats),
        opt_state=new_opt_state,
        ema_params=new_ema,
        carry=aux.get("carry", state.carry),
    )


def make_train_step_fn(
    loss_fn: LossFn,
    rng_names: Sequence[str] = ("dropout",),
) -> Callable[[TrainState, Batch, jax.Array], tuple[TrainState, dict]]:
    """The raw (unjitted) step — compose into larger compiled programs,
    e.g. ``lax.scan`` over many steps for single-dispatch epochs/benchmarks
    (amortises host round-trips, lets XLA overlap across step boundaries)."""

    def step_fn(state: TrainState, batch: Batch, rng: jax.Array):
        rngs = per_step_rngs(rng, state.step, rng_names)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        (_, aux), grads = grad_fn(state.params, state, batch, rngs)
        metrics = dict(aux.get("metrics", {}))
        with jax.named_scope(OPTIMIZER_SCOPE):
            metrics["grad_norm"] = optax.global_norm(grads)
        return apply_gradients(state, grads, aux), metrics

    return step_fn


def state_is_finite(state: TrainState) -> bool:
    """True when every float leaf of the *trajectory-carrying* state —
    params, batch_stats, carry, opt_state, EMA shadows — is finite: the
    rollback path's checkpoint-candidate gate (``nan_policy="rollback"``).
    A checkpoint saved after divergence began must not be restored as a
    rollback target, or the retry replays the poison
    ``rollback_budget`` times; opt_state matters as much as params (an
    inf Adam second moment zeroes its update, leaving params finite
    while the optimizer is already poisoned).  One program and one
    scalar sync — cheap enough for the (rare) rollback path, never on
    the hot path.  One program, not an eager reduction a leaf: over a
    sharded state each of those is a collective of its own, and the CPU
    backend's in-process rendezvous wedges (and aborts after 40 s) when
    a few hundred are in flight on a busy host."""
    leaves = [
        leaf
        for tree in (
            state.params,
            state.batch_stats,
            state.carry,
            state.opt_state,
            state.ema_params,
        )
        for leaf in jax.tree_util.tree_leaves(tree)
        if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.floating)
    ]
    if not leaves:
        return True
    return bool(_all_finite(leaves))


@jax.jit
def _all_finite(leaves: list) -> jax.Array:
    return jnp.all(jnp.stack([jnp.all(jnp.isfinite(x)) for x in leaves]))


def make_eval_step(
    apply_fn: Callable, use_ema: bool = True
) -> Callable[[TrainState, Batch], dict]:
    """Jitted eval step returning top-1/top-5 *counts* (summed over the
    global batch, so the host just accumulates integers across batches —
    the reference eval loop's counting scheme, SURVEY.md §3.5)."""

    def eval_fn(state: TrainState, batch: Batch):
        params = state.eval_params if use_ema else state.params
        variables = {"params": params}
        if jax.tree_util.tree_leaves(state.batch_stats):
            variables["batch_stats"] = state.batch_stats
        outputs = apply_fn(variables, batch["image"], train=False)
        logits = (
            outputs[0] if isinstance(outputs, (tuple, list)) else outputs
        )
        labels = batch["label"]
        # Rows with label < 0 are padding (partial final eval batches padded
        # up to the mesh size) and are excluded from every count.
        valid = (labels >= 0).astype(jnp.float32)
        return {
            "top1_count": jnp.sum(
                metriclib.top_k_correct(logits, labels, 1) * valid
            ),
            "top5_count": jnp.sum(
                metriclib.top_k_correct(logits, labels, 5) * valid
            ),
            "count": jnp.sum(valid),
            "xent_sum": jnp.sum(
                losslib.softmax_cross_entropy(
                    logits, jnp.maximum(labels, 0)
                )
                * valid
            ),
        }

    return jax.jit(eval_fn)


def _collective_free_put(x, s):
    """``device_put`` onto ``s`` without cross-process collectives.

    ``jax.device_put`` onto a sharding that spans processes runs a
    value-equality broadcast of the *whole tensor* per leaf
    (``multihost_utils.assert_equal``), so laying out a model issues one
    cross-host collective per parameter before training starts.  Besides
    the startup cost, those broadcasts overlap in flight with the
    placement transfers and can interleave on the wire.  Every caller
    here holds the full global value on every process (same seed, same
    init), so each process can contribute its local shards directly and
    skip the wire entirely.
    """
    if s.is_fully_addressable:
        return jax.device_put(x, s)
    x = np.asarray(x)
    arrs = [
        jax.device_put(x[idx], d)
        for d, idx in s.addressable_devices_indices_map(x.shape).items()
    ]
    return jax.make_array_from_single_device_arrays(x.shape, s, arrs)


def state_layout(
    state: TrainState,
    mesh: Mesh,
    param_rules: Sequence[shardlib.ShardingRule] = (),
) -> TrainState:
    """The layout of a train state on the mesh: a ``TrainState``-shaped
    tree with a ``NamedSharding`` for every array leaf.  THE one decision
    — :func:`place_state` applies it and the step programs are compiled
    to return it (:func:`_jit_state_program`).  ``state`` may be abstract
    (``jax.eval_shape``): only shapes are read.

    With no rules everything is replicated — classic data parallelism, the
    reference's sync mode minus the parameter servers.  ``param_rules``
    shard selected weight dimensions over the ``model`` axis (tensor
    parallelism); optimizer slots and EMA shadows follow their parameters'
    sharding automatically, the analogue of TF slot variables inheriting
    their primary's PS placement (TF optimizer.py:463,
    device_setter.py:92-125).  ``step``, ``batch_stats`` and whatever of
    the optimizer state parallels no parameter (counts) are replicated;
    the recurrent carry is batch-major activation state and shards over
    ``data``.
    """
    rep = shardlib.replicated(mesh)
    param_sh = shardlib.tree_param_shardings(mesh, state.params, param_rules)

    def like_param(slot, param, sh):
        # A slot of another shape than its parameter (a factored moment)
        # has no dimension the parameter's spec names.
        return sh if slot.shape == param.shape else rep

    return state.replace(
        step=rep,
        params=param_sh,
        batch_stats=jax.tree.map(lambda _: rep, state.batch_stats),
        # Every copy of the parameter tree inside the optimizer state,
        # found by where ``tx.init`` puts its argument.
        opt_state=optax.tree_utils.tree_map_params(
            state.tx,
            like_param,
            state.opt_state,
            state.params,
            param_sh,
            transform_non_params=lambda _: rep,
        ),
        ema_params=None if state.ema_params is None else param_sh,
        carry=(
            None
            if state.carry is None
            else shardlib.tree_batch_shardings(mesh, state.carry)
        ),
    )


def place_state(
    state: TrainState,
    mesh: Mesh,
    param_rules: Sequence[shardlib.ShardingRule] = (),
) -> TrainState:
    """Lay the train state out on the mesh as :func:`state_layout` says.
    Placement is collective-free: every process holds the full initial
    state, so global arrays are assembled from local shards
    (``_collective_free_put``) rather than broadcast."""
    return jax.tree.map(
        _collective_free_put, state, state_layout(state, mesh, param_rules)
    )
