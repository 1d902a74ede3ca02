"""Token embedding lookup with a selectable gradient lowering.

The forward is a plain gather — XLA lowers it well on TPU.  The
BACKWARD is the interesting half: the native vjp of ``take`` is a
scatter-add over ``B*T`` token indices, and XLA's TPU scatter is the
classic hidden cost of LM train steps (serialized row updates).  The MXU
alternative every TPU embedding implementation reaches for is the
one-hot matmul: ``dTable = one_hot(tokens)^T @ dOut`` — 2·N·V·d extra
FLOPs (~84 GFLOP at the flagship transformer config, ~0.4 ms of MXU
time) in exchange for zero scatter traffic; the one-hot is built from an
iota compare that XLA fuses into the matmul operand read, so it is
never materialized in HBM.

``grad_impl``:

- ``"scatter"`` — the native lowering (f32 accumulation), the default
  until a hardware A/B says otherwise (measured-defaults principle:
  every perf default in this repo cites a banked artifact).
- ``"matmul"`` — chunked one-hot matmul, f32 accumulation, chunked over
  the flattened token dim so the (chunk, V) one-hot stays fusion-sized.

Both accumulate in f32 and produce the same values up to f32 summation
order (pinned in tests/test_ops.py).  The trace-time env knob
``DTM_EMBED_GRAD`` selects the default for the model zoo's
:class:`TokenEmbed` (same contract as DTM_CONV_IMPL: invalid values fail
loudly naming the knob).
"""

from __future__ import annotations

import functools
import os

import flax.linen as nn
import jax
import jax.numpy as jnp

_VALID_IMPLS = ("scatter", "matmul")


def resolve_embed_grad_impl(impl: str = "auto") -> str:
    if impl == "auto":
        impl = os.environ.get("DTM_EMBED_GRAD", "scatter")
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"embed grad impl (DTM_EMBED_GRAD) must be one of "
            f"{_VALID_IMPLS}, got {impl!r}"
        )
    return impl


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def embed_lookup(
    table: jax.Array,
    tokens: jax.Array,
    grad_impl: str = "scatter",
    chunk: int = 2048,
) -> jax.Array:
    """``table[tokens]`` with the backward lowering chosen by
    ``grad_impl`` (see module docstring).  ``tokens`` may have any
    integer shape; output shape is ``tokens.shape + (d,)``."""
    return jnp.take(table, tokens, axis=0)


def _embed_fwd(table, tokens, grad_impl, chunk):
    # Residuals must be JAX types: a (V, 0) empty array is a zero-byte
    # witness for the table's vocab size and dtype.
    witness = jnp.zeros((table.shape[0], 0), table.dtype)
    return embed_lookup(table, tokens, grad_impl, chunk), (tokens, witness)


def _embed_bwd(grad_impl, chunk, res, g):
    tokens, witness = res
    V, tdtype = witness.shape[0], witness.dtype
    d = g.shape[-1]
    flat = tokens.reshape(-1)
    gf = g.reshape(-1, d)
    n = flat.shape[0]
    if grad_impl == "scatter":
        dt = (
            jnp.zeros((V, d), jnp.float32)
            .at[flat]
            .add(gf.astype(jnp.float32))
        )
        return dt.astype(tdtype), None
    # Chunked one-hot matmul.  Padding rows carry g = 0, so whatever
    # token index they one-hot against contributes nothing.  Negative
    # ids wrap numpy-style in the forward gather (and in the scatter
    # path), so wrap them here too or the one-hot compare would silently
    # drop their gradient and the two impls would train different
    # models.  max(1, ...) keeps the empty-token edge from a
    # divide-by-zero the scatter path doesn't have.
    flat = jnp.where(flat < 0, flat + V, flat)
    chunk = max(1, min(chunk, n))
    pad = (-n) % chunk
    if pad:
        flat = jnp.pad(flat, (0, pad))
        gf = jnp.pad(gf, ((0, pad), (0, 0)))
    toks = flat.reshape(-1, chunk)
    gs = gf.reshape(-1, chunk, d)
    vocab = jax.lax.broadcasted_iota(flat.dtype, (1, V), 1)

    def body(acc, xs):
        tok_c, g_c = xs
        # One-hot in g's dtype: {0, 1} is exact in bf16, products are
        # exact, and the dot accumulates f32 — only summation ORDER
        # differs from the scatter path.
        oh = (tok_c[:, None] == vocab).astype(g_c.dtype)  # [chunk, V]
        acc = acc + jax.lax.dot_general(
            oh, g_c, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [V, d]
        return acc, None

    dt, _ = jax.lax.scan(
        body, jnp.zeros((V, d), jnp.float32), (toks, gs)
    )
    return dt.astype(tdtype), None


embed_lookup.defvjp(_embed_fwd, _embed_bwd)


class TokenEmbed(nn.Module):
    """Drop-in for ``nn.Embed`` (same param path ``<name>/embedding``,
    same default init, same dtype promotion) with the selectable
    gradient lowering.  ``grad_impl="auto"`` resolves DTM_EMBED_GRAD at
    trace time, defaulting to the native scatter."""

    num_embeddings: int
    features: int
    dtype: jnp.dtype = jnp.float32
    grad_impl: str = "auto"

    @nn.compact
    def __call__(self, tokens: jax.Array) -> jax.Array:
        table = self.param(
            "embedding",
            nn.initializers.variance_scaling(
                1.0, "fan_in", "normal", out_axis=0
            ),
            (self.num_embeddings, self.features),
        )
        impl = resolve_embed_grad_impl(self.grad_impl)
        return embed_lookup(
            table.astype(self.dtype), tokens, impl
        )

    def attend(self, hidden: jax.Array) -> jax.Array:
        """``hidden @ table^T`` in float32: the logits of a head tied to
        this embedding (``nn.Embed.attend``, at the precision the
        untied ``nn.Dense(dtype=float32)`` head has)."""
        table = self.get_variable("params", "embedding")
        return jnp.einsum(
            "...d,vd->...v", hidden.astype(jnp.float32),
            table.astype(jnp.float32),
        )
