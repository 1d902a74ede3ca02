"""Optimizers and LR schedules with the reference's exact update semantics.

The reference trains with TF 1.x optimizers (SURVEY.md §2.2 F6):
``GradientDescentOptimizer`` (PTB, MNIST), ``MomentumOptimizer`` (CIFAR,
ResNet-50), ``RMSPropOptimizer`` (Inception-v3; TF rmsprop.py:50), wrapped in
``SyncReplicasOptimizer`` for sync data parallelism.  Here each is an
``optax.GradientTransformation``; the SyncReplicas wrapper has no equivalent
because gradient aggregation is compiled into the train step (SURVEY.md §7.1).

The update rules below are pinned to TF's kernels where they differ from
optax defaults — most importantly RMSProp's epsilon *inside* the square root
(SURVEY.md §4.2: "epsilon-inside-sqrt differences must be pinned by test").
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

ScalarOrSchedule = float | optax.Schedule


class TfRMSPropState(NamedTuple):
    count: jax.Array  # step counter, drives LR schedules
    ms: optax.Updates  # mean of squared gradients
    mom: optax.Updates  # momentum accumulator
    mg: Optional[optax.Updates]  # mean gradient (centered variant only)


def tf_rmsprop(
    learning_rate: ScalarOrSchedule,
    decay: float = 0.9,
    momentum: float = 0.9,
    epsilon: float = 1.0,
    centered: bool = False,
) -> optax.GradientTransformation:
    """RMSProp with TF-1.x kernel semantics (TF rmsprop.py:50).

    Per-variable update, exactly as the TF C++ kernel (and unlike optax's
    default, epsilon sits *inside* the sqrt)::

        ms  <- decay * ms + (1 - decay) * g^2
        mom <- momentum * mom + lr * g / sqrt(ms - mg^2? + epsilon)
        var <- var - mom

    The defaults (decay=0.9, momentum=0.9, epsilon=1.0) are the slim
    Inception-v3 training configuration the reference uses (SURVEY.md §2.1
    R5).  ``ms`` is initialised to **ones** as in TF, not zeros — with
    epsilon=1.0 this materially changes the first steps.
    """

    def init(params):
        ms = jax.tree.map(jnp.ones_like, params)
        mom = jax.tree.map(jnp.zeros_like, params)
        mg = jax.tree.map(jnp.zeros_like, params) if centered else None
        return TfRMSPropState(
            count=jnp.zeros((), jnp.int32), ms=ms, mom=mom, mg=mg
        )

    def update(grads, state, params=None):
        del params
        lr = (
            learning_rate(state.count)
            if callable(learning_rate)
            else learning_rate
        )
        ms = jax.tree.map(
            lambda m, g: decay * m + (1.0 - decay) * jnp.square(g),
            state.ms,
            grads,
        )
        if centered:
            mg = jax.tree.map(
                lambda m, g: decay * m + (1.0 - decay) * g, state.mg, grads
            )
            denom = jax.tree.map(
                lambda m2, m1: m2 - jnp.square(m1) + epsilon, ms, mg
            )
        else:
            mg = None
            denom = jax.tree.map(lambda m2: m2 + epsilon, ms)
        mom = jax.tree.map(
            lambda mo, g, d: momentum * mo + lr * g * jax.lax.rsqrt(d),
            state.mom,
            grads,
            denom,
        )
        updates = jax.tree.map(lambda m: -m, mom)
        new_state = TfRMSPropState(
            count=state.count + 1, ms=ms, mom=mom, mg=mg
        )
        return updates, new_state

    return optax.GradientTransformation(init, update)


def tf_momentum(
    learning_rate: ScalarOrSchedule,
    momentum: float = 0.9,
    use_nesterov: bool = False,
) -> optax.GradientTransformation:
    """``tf.train.MomentumOptimizer`` semantics (TF momentum.py:25)::

        accum <- momentum * accum + g
        var   <- var - lr * accum            (heavy-ball)
        var   <- var - lr * (g + momentum * accum)   (nesterov)

    optax's ``trace`` matches this accumulator convention, so this is a thin
    assembly kept for explicitness.
    """
    return optax.chain(
        optax.trace(decay=momentum, nesterov=use_nesterov),
        _scale_by_neg_lr(learning_rate),
    )


def sgd(learning_rate: ScalarOrSchedule) -> optax.GradientTransformation:
    """``tf.train.GradientDescentOptimizer`` (TF gradient_descent.py:27)."""
    return _scale_by_neg_lr(learning_rate)


def adam(
    learning_rate: ScalarOrSchedule,
    b1: float = 0.9,
    b2: float = 0.999,
    eps: float = 1e-8,
) -> optax.GradientTransformation:
    """``tf.train.AdamOptimizer`` (TF adam.py:28).  TF applies the bias
    correction through the effective LR, mathematically identical to optax's
    ``scale_by_adam`` followed by LR scaling."""
    return optax.chain(
        optax.scale_by_adam(b1=b1, b2=b2, eps=eps),
        _scale_by_neg_lr(learning_rate),
    )


def _scale_by_neg_lr(learning_rate: ScalarOrSchedule):
    if callable(learning_rate):
        return optax.scale_by_learning_rate(learning_rate, flip_sign=True)
    return optax.scale(-learning_rate)


def exponential_decay(
    initial_lr: float,
    decay_steps: int,
    decay_rate: float,
    staircase: bool = True,
) -> optax.Schedule:
    """``tf.train.exponential_decay`` (TF legacy_learning_rate_decay.py:29):
    ``lr * decay_rate ** (step / decay_steps)``, floored to an integer power
    when ``staircase`` — the schedule used by the reference's Inception and
    CIFAR drivers (SURVEY.md §2.2 F16)."""
    return optax.exponential_decay(
        init_value=initial_lr,
        transition_steps=decay_steps,
        decay_rate=decay_rate,
        staircase=staircase,
    )


def linear_warmup(lr: ScalarOrSchedule, warmup_steps: int) -> optax.Schedule:
    """``lr`` (a number or a schedule) behind a linear warm-up: step ``t``
    (from 0) runs at ``min(1, (t + 1) / warmup_steps)`` of its value, so
    the first step already moves and step ``warmup_steps - 1`` is the
    first at the full rate."""
    base = lr if callable(lr) else (lambda count: lr)

    def schedule(count):
        return base(count) * jnp.minimum(1.0, (count + 1) / warmup_steps)

    return schedule


def zaremba_decay(
    initial_lr: float,
    steps_per_epoch: int,
    hold_epochs: int,
    decay_rate: float,
) -> optax.Schedule:
    """The PTB staged schedule (SURVEY.md §2.1 R8, Zaremba et al.):
    constant for the first ``hold_epochs`` epochs, then multiplied by
    ``decay_rate`` once per epoch —
    ``lr * decay_rate ** max(0, epoch + 1 - hold_epochs)`` with
    ``epoch = step // steps_per_epoch`` (the reference reassigns the LR
    variable at each epoch boundary with exactly this exponent)."""

    def schedule(count):
        epoch = count // steps_per_epoch
        exponent = jnp.maximum(0, epoch + 1 - hold_epochs)
        return initial_lr * decay_rate ** exponent.astype(jnp.float32)

    return schedule


def piecewise_constant(
    boundaries: list[int], values: list[float]
) -> optax.Schedule:
    """``tf.train.piecewise_constant`` — staged LR drops (PTB's per-epoch
    LR decay, SURVEY.md §2.1 R8, is expressed with this).

    TF semantics: ``values[i]`` while ``x <= boundaries[i]`` — the old value
    still applies *at* the boundary step and the drop lands at
    ``boundary + 1``.  optax scales at ``count >= boundary``, so boundaries
    are shifted by one here to pin the TF behavior.
    """
    if len(values) != len(boundaries) + 1:
        raise ValueError("need len(values) == len(boundaries) + 1")
    scales = {
        b + 1: values[i + 1] / values[i] for i, b in enumerate(boundaries)
    }
    return optax.piecewise_constant_schedule(values[0], scales)


def clip_by_global_norm(max_norm: float) -> optax.GradientTransformation:
    """``tf.clip_by_global_norm`` (TF ops/clip_ops.py:300) — the PTB driver
    clips gradients to global norm 5/10 before applying (SURVEY.md §2.2
    F17).  optax's transform implements the same rescale-if-exceeds rule."""
    return optax.clip_by_global_norm(max_norm)


def global_norm(tree) -> jax.Array:
    return optax.global_norm(tree)
