"""PTB LSTM language model — truncated-BPTT on TPU via ``lax.scan``.

Reference component R8 (SURVEY.md §2.1): the TF PTB tutorial — a 2-layer
LSTM LM (Zaremba et al. 2014) with truncated BPTT over ``num_steps`` tokens,
dropout between layers, gradients clipped by global norm, SGD with staged LR
decay, and small/medium/large configs.  Critically, the reference threads
the final LSTM state of each segment into the next (SURVEY.md §7.4.5) — here
the carry is an explicit input/output of ``__call__`` so the train loop can
keep it in the (sharded) train state.

TPU-first, cuDNN-style decomposition: layers scan over time one at a time
(mathematically identical to stepping the whole stack per timestep — layers
only couple through the previous layer's full hidden sequence), which lets
each layer's input-to-hidden projection for ALL timesteps run as ONE
``[B·T, in] x [in, 4h]`` MXU matmul hoisted out of the scan.  The scan body
is left with just the recurrent ``h @ W_hh [h, 4h]`` matmul + gate
elementwise — half the sequential matmul count of the step-the-stack
layout, and the hoisted half runs at full batch instead of batch-per-step.
Gates are fused (i|f|g|o in one 4h projection); parameter count matches the
per-gate layout exactly (8h² + 4h per layer, zero-init biases).
"""

from __future__ import annotations

from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_models_tpu.models import register
from distributed_tensorflow_models_tpu.ops.embed import TokenEmbed

# Per-layer carry: (c, h) tuples, batch-major.
Carry = Sequence[tuple[jax.Array, jax.Array]]


def _blockwise_orthogonal(key, shape, dtype=jnp.float32):
    """Orthogonal init per [h, h] gate block of a fused [h, 4h] recurrent
    kernel — the distribution flax's per-gate cells give each recurrent
    gate matrix."""
    h, four_h = shape
    n = four_h // h
    orth = nn.initializers.orthogonal()
    keys = jax.random.split(key, n)
    return jnp.concatenate(
        [orth(k, (h, h), dtype) for k in keys], axis=1
    )


class _RecurrentCore(nn.Module):
    """The sequential part of one LSTM layer: consumes the precomputed
    input-gate activations ``gx [B, 4h]`` for a single timestep."""

    hidden_size: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, carry, gx):
        # Cell state stays float32 whatever the compute dtype: the c
        # accumulation is a long additive recurrence, exactly the pattern
        # bf16 destroys (the standard mixed-precision LSTM recipe —
        # matmuls in bf16 on the MXU, state in f32).  With dtype=float32
        # this path is bitwise the pre-mixed-precision behavior.
        c, h = carry
        # No bias here: the hoisted ih projection already carries the one
        # gate bias (total parameter count matches the per-gate layout).
        # Per-gate ORTHOGONAL recurrent init, as flax's LSTM cells use —
        # it is what keeps deep-in-time gradients stable; a plain fused
        # lecun_normal would silently change training dynamics.
        gates = gx + nn.Dense(
            4 * self.hidden_size, dtype=self.dtype, use_bias=False,
            kernel_init=_blockwise_orthogonal,
            name="hh",
        )(h.astype(self.dtype))
        gates = gates.astype(jnp.float32)
        i, f, g, o = jnp.split(gates, 4, axis=-1)
        c = jax.nn.sigmoid(f) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
        h = jax.nn.sigmoid(o) * jnp.tanh(c)  # f32, like c
        return (c, h), h.astype(self.dtype)


class PTBLSTM(nn.Module):
    """Input ``tokens [B, T]`` int32 + carry; returns ``(logits [B, T, V],
    new_carry)``."""

    vocab_size: int = 10000
    hidden_size: int = 650  # "medium" config
    num_layers: int = 2
    dropout_rate: float = 0.5
    dtype: jnp.dtype = jnp.float32

    def initial_carry(self, batch_size: int) -> Carry:
        # float32 regardless of compute dtype — see _RecurrentCore.
        zeros = lambda: jnp.zeros(
            (batch_size, self.hidden_size), jnp.float32
        )
        return tuple(
            (zeros(), zeros()) for _ in range(self.num_layers)
        )

    @nn.compact
    def __call__(self, tokens, carry: Carry | None = None,
                 train: bool = False, return_hidden: bool = False):
        if carry is None:
            carry = self.initial_carry(tokens.shape[0])
        # TokenEmbed == nn.Embed plus the DTM_EMBED_GRAD backward A/B
        # knob (ops/embed.py).
        x = TokenEmbed(
            self.vocab_size, self.hidden_size, dtype=self.dtype,
            name="embedding",
        )(tokens)
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)

        new_carry = []
        for layer in range(self.num_layers):
            # Hoisted: input projections for every timestep in one
            # matmul (bias lives here so the scan body adds none).
            gx = nn.Dense(
                4 * self.hidden_size, dtype=self.dtype,
                name=f"lstm_{layer}_ih",
            )(x)  # [B, T, 4h]
            core = nn.scan(
                _RecurrentCore,
                variable_broadcast="params",
                split_rngs={"params": False},
                in_axes=1,
                out_axes=1,
            )(self.hidden_size, self.dtype, name=f"lstm_{layer}")
            c_out, x = core(tuple(carry[layer]), gx)
            new_carry.append(c_out)
            # Inter-layer (and pre-head) dropout, as the reference
            # applies it to each layer's output sequence.
            if self.dropout_rate:
                x = nn.Dropout(
                    self.dropout_rate, deterministic=not train
                )(x)
        if return_hidden:
            # Fused chunked unembed+xent path
            # (ops/losses.py::fused_unembed_mean_xent): the head projection —
            # HALF this model's per-token FLOPs (2·h·V vs ~2·8h² for the
            # LSTM stack at h=650, V=10k) — runs inside the loss instead.
            return x, tuple(new_carry)
        logits = nn.Dense(
            self.vocab_size, dtype=jnp.float32, name="head"
        )(x)
        return logits, tuple(new_carry)


# The three classic Zaremba configs the reference exposes (SURVEY.md §2.1 R8).
PTB_CONFIGS = {
    "small": dict(hidden_size=200, dropout_rate=0.0),
    "medium": dict(hidden_size=650, dropout_rate=0.5),
    "large": dict(hidden_size=1500, dropout_rate=0.65),
}


@register("ptb_lstm")
def build_ptb_lstm(config: str = "medium", **kwargs) -> PTBLSTM:
    base = dict(PTB_CONFIGS[config])
    base.update(kwargs)
    return PTBLSTM(**base)
