"""Functional training state.

Bundles everything the reference scatters across PS-resident variables —
model parameters, optimizer slots (TF optimizer.py:463 slot variables),
BN moving statistics, the EMA shadow copies (TF moving_averages.py:284), and
``global_step`` (TF training_util.py:40) — into one immutable pytree that the
jitted train step maps to a new value.  Checkpointing this one object
replaces ``tf.train.Saver``'s variable collection walk (SURVEY.md §2.2 F12).
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from flax import struct

PyTree = Any


class TrainState(struct.PyTreeNode):
    """State threaded through the train loop.

    ``apply_fn`` / ``tx`` / ``ema_decay`` are static (not traced); everything
    else is device-resident array data.
    """

    step: jax.Array
    params: PyTree
    batch_stats: PyTree  # {} for models without BN
    opt_state: PyTree
    ema_params: Optional[PyTree]  # None when EMA is disabled
    # Recurrent carry threaded across train steps — the PTB LSTM's
    # truncated-BPTT state (the reference threads the final LSTM state of
    # each segment into the next, SURVEY.md §7.4.5).  None for feed-forward
    # models.  Batch-major, so it shards over the data axis like any
    # activation.
    carry: Optional[PyTree]
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)
    ema_decay: Optional[float] = struct.field(pytree_node=False, default=None)

    @property
    def eval_params(self) -> PyTree:
        """Parameters to evaluate with: EMA shadows when maintained, matching
        the reference eval drivers' ``variables_to_restore`` swap
        (TF moving_averages.py:638 — SURVEY.md §3.5)."""
        return self.ema_params if self.ema_params is not None else self.params

    @classmethod
    def create(
        cls,
        model,
        tx: optax.GradientTransformation,
        rng: jax.Array,
        sample_input: PyTree,
        ema_decay: Optional[float] = None,
        carry: Optional[PyTree] = None,
        init_kwargs: dict | None = None,
        jit_init: Optional[bool] = None,
    ) -> "TrainState":
        """Initialise params on the host and assemble the state.

        The reference's equivalent is chief-only ``init_op`` execution with
        workers polling ``wait_for_session`` (TF session_manager.py:259,419);
        under SPMD every process computes the same deterministic init.

        ``jit_init=None`` (auto) compiles ``model.init`` as ONE program
        whenever a persistent compilation cache is configured
        (``harness/startup.py`` wires it for production; the test
        conftest for CI): eager init executes the whole forward
        op-by-op — seconds of per-op dispatch for deep CNNs on every
        relaunch — while the jitted init is deserialized from the cache
        after the first run (measured on this host: ResNet-32 3.0 s
        eager → 0.85 s warm; even LeNet's tiny init wins).  Values are
        identical either way (deterministic PRNG + the same XLA ops —
        pinned in tests/test_startup.py); with no cache configured,
        eager is kept — a one-shot jit compile would only slow a
        cacheless cold start.  The jitted program returns the two
        collections the state keeps and no other: a collection the
        forward pass sows (an expert layer's ``moe_stats``) would keep
        the whole forward pass alive in a program that only has to draw
        the parameters (the Kimi Linear cell's init, compiled for a v5e:
        47 s with the sown statistics, 20 s without).
        """
        if jit_init is None:
            jit_init = bool(jax.config.jax_compilation_cache_dir)
        if jit_init:
            kept = ("params", "batch_stats")
            variables = jax.jit(
                lambda r, s: {
                    k: v
                    for k, v in model.init(r, s, **(init_kwargs or {})).items()
                    if k in kept
                }
            )(rng, sample_input)
        else:
            variables = model.init(rng, sample_input, **(init_kwargs or {}))
        params = variables.get("params", {})
        batch_stats = variables.get("batch_stats", {})
        ema_params = None
        if ema_decay is not None:
            ema_params = jax.tree.map(
                lambda x: x.astype(jnp.float32), params
            )
        return cls(
            step=jnp.zeros((), jnp.int32),
            params=params,
            batch_stats=batch_stats,
            opt_state=tx.init(params),
            ema_params=ema_params,
            carry=carry,
            apply_fn=model.apply,
            tx=tx,
            ema_decay=ema_decay,
        )
