"""Delta-rule linear attention with a decay per channel (KDA), chunk-wise.

Kimi Delta Attention (Kimi Linear technical report, Moonshot AI 2025,
arXiv:2510.26692) keeps, per head, a state ``S`` ``[dk, dv]`` that every
token decays channel by channel, corrects by the delta rule and reads::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = scale * S_t^T q_t

with ``a_t`` in (0, 1)^dk (given here as its logarithm ``g_t <= 0``) and
``b_t`` in (0, 1).  :func:`recurrent_kda` is that recurrence token by
token (a ``lax.scan`` over time; the oracle of the tests).
:func:`chunked_kda` is what the model runs: matrix products inside
chunks of ``chunk`` tokens and the state carried from chunk to chunk.

The chunk-wise form.  With ``G_t = sum_{r<=t} g_r`` inside a chunk that
starts from ``S_0``, and ``u_t = b_t (v_t - S_{t-1}^T (a_t * k_t))`` (what
the delta rule really writes), ``S_t = diag(e^{G_t}) S_0 + sum_{s<=t}
diag(e^{G_t - G_s}) k_s u_s^T``, and the ``u`` of a chunk solve

    (I + A) U = diag(b) (V - (K * e^G) S_0),
    A_ts = b_t sum_c k_tc k_sc e^{G_tc - G_sc}   (s < t),

a unit lower-triangular system per chunk and head.  So per chunk:
``T = (I + A)^-1``; ``U = T diag(b) V - (T diag(b) (K * e^G)) S_0``;
``O = scale * ((Q * e^G) S_0 + tril(QK) U)`` with ``QK_ts = sum_c q_tc
k_sc e^{G_tc - G_sc}`` (``s <= t``); ``S_C = diag(e^{G_C}) S_0 + (K *
e^{G_C - G})^T U``.  Only ``U`` and the state need the chunks in order.

Decays near 0.  ``e^{G_t - G_s}`` cannot be split as ``e^{G_t} e^{-G_s}``:
sixty-four steps of a strong decay put ``e^{-G_s}`` beyond float32 while
the quotient is an ordinary number.  Every exponent taken here is of a
difference ``G_later - G_earlier <= 0``: the pairs of one ``sub``-token
block are computed one by one (``[sub, sub, dk]`` exponentials), a pair
of different blocks through a point between them, the first row of the
later block (``e^{G_t - G_ref} e^{G_ref - G_s}``, both at most 1), so
those are matrix products.

Precision.  ``g``, ``G``, every exponential, ``A``, ``T``, the state and
all accumulations are float32.  The matrix products take their operands
in the dtype of ``v`` (bfloat16 in a bf16 model, float32 otherwise) and
accumulate in float32.  ``T`` is made in float32: exact forward
substitution inside ``sub`` x ``sub`` blocks, then ``T_21 = -T_22 A_21
T_11`` block by block at full precision.

The backward pass is autodiff through all of it except ``T``, whose
cotangent is ``-T^T dT T^T`` (no pass through the substitution).  What
it keeps is per chunk (the state at each chunk's start, ``U``, ``T``),
never a state per token.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ``jax.named_scope`` of the chunk-wise core, forward and backward: a path
# element of every instruction's ``op_name`` in the compiled step (PERF.md
# section 3).  The projections, the convolutions, the gates and the output
# norm of the mixer stay outside it (``linear_attn`` holds them all).
KDA_CORE_SCOPE = "kda_core"

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def recurrent_kda(q, k, v, g, beta, *, scale: Optional[float] = None):
    """The recurrence token by token, float32.  ``q``, ``k`` ``[B, T, H,
    dk]``, ``v`` ``[B, T, H, dv]``, ``g`` ``[B, T, H, dk]`` (log decay,
    <= 0), ``beta`` ``[B, T, H]``; returns ``[B, T, H, dv]``."""
    B, T, H, dk = q.shape
    scale = dk**-0.5 if scale is None else scale
    q, k, v, g, beta = (x.astype(_F32) for x in (q, k, v, g, beta))

    def step(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None] * S
        read = jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_HI)
        u = b_t[..., None] * (v_t - read)
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, scale * jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_HI)

    time_first = lambda x: jnp.moveaxis(x, 1, 0)
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), _F32)
    _, out = lax.scan(step, S0, tuple(map(time_first, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1)


def _diagonal_blocks(a, size: int):
    """The ``size`` x ``size`` blocks on the diagonal of ``a`` ``[..., C,
    C]``, as ``[..., C/size, size, size]``."""
    C = a.shape[-1]
    n = C // size
    blocks = a.reshape(*a.shape[:-2], n, size, n, size)
    return jnp.stack([blocks[..., i, :, i, :] for i in range(n)], axis=-3)


def _inverse(a, sub: int):
    C = a.shape[-1]
    d = _diagonal_blocks(a, sub)
    eye = jnp.eye(sub, dtype=a.dtype)
    # Forward substitution inside a block: row i of (I + D)^-1 is e_i
    # less row i of D times the rows before it.
    rows = [jnp.broadcast_to(eye[0], d.shape[:-2] + (sub,))]
    for i in range(1, sub):
        before = jnp.stack(rows, axis=-2)
        rows.append(eye[i] - jnp.sum(d[..., i, :i, None] * before, axis=-2))
    t = jnp.stack(rows, axis=-2)  # [..., C/sub, sub, sub]
    size = sub
    while size < C:
        t11, t22 = t[..., 0::2, :, :], t[..., 1::2, :, :]
        a21 = _diagonal_blocks(a, 2 * size)[..., size:, :size]
        t21 = -jnp.matmul(jnp.matmul(t22, a21, precision=_HI), t11, precision=_HI)
        t = jnp.concatenate(
            [
                jnp.concatenate([t11, jnp.zeros_like(t11)], axis=-1),
                jnp.concatenate([t21, t22], axis=-1),
            ],
            axis=-2,
        )
        size *= 2
    return t[..., 0, :, :]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a, sub: int = 16):
    """``(I + a)^-1`` for strictly lower-triangular ``a`` ``[..., C, C]``
    (float32; ``C`` a power-of-two multiple of ``sub``).  Its cotangent
    is ``-T^T dT T^T``, kept strictly lower-triangular like ``a``."""
    return _inverse(a, sub)


def _unit_lower_inverse_fwd(a, sub):
    t = _inverse(a, sub)
    return t, t


def _unit_lower_inverse_bwd(sub, t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    da = -jnp.matmul(jnp.matmul(tt, dt, precision=_HI), tt, precision=_HI)
    return (jnp.tril(da, -1),)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _decayed_products(xs, k, G, sub: int, dtype):
    """For each ``x`` of ``xs`` (``[..., C, dk]``): ``M_ts = sum_c x_tc
    k_sc e^{G_tc - G_sc}`` for ``s <= t``, 0 above the diagonal, ``[...,
    C, C]`` float32.  No exponent taken is positive (module docstring)."""
    lead, (C, dk) = k.shape[:-2], k.shape[-2:]
    n = C // sub
    blocked = lambda a: a.astype(_F32).reshape(*lead, n, sub, dk)
    Gb, kb = blocked(G), blocked(k)
    xbs = [blocked(x) for x in xs]
    # Pairs inside one block, one by one.
    lower = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    diff = Gb[..., :, None, :] - Gb[..., None, :, :]  # [..., n, t, s, dk]
    kd = kb[..., None, :, :] * jnp.exp(jnp.where(lower, diff, -jnp.inf))
    inside = [jnp.sum(xb[..., :, None, :] * kd, axis=-1) for xb in xbs]
    rows = [[] for _ in xs]
    for i in range(n):
        ref = Gb[..., i, :1, :]  # the first row of block i: between s and t
        if i:
            ks = kb[..., :i, :, :] * jnp.exp(ref[..., None, :, :] - Gb[..., :i, :, :])
            ks = ks.reshape(*lead, i * sub, dk).astype(dtype)
        scale_t = jnp.exp(Gb[..., i, :, :] - ref)
        for row, xb, own in zip(rows, xbs, inside):
            parts = [own[..., i, :, :]]
            if i:
                xt = (xb[..., i, :, :] * scale_t).astype(dtype)
                parts.insert(0, jnp.einsum(
                    "...tc,...sc->...ts", xt, ks, preferred_element_type=_F32
                ))
            if i < n - 1:
                parts.append(jnp.zeros((*lead, sub, (n - 1 - i) * sub), _F32))
            row.append(jnp.concatenate(parts, axis=-1))
    return [jnp.concatenate(row, axis=-2) for row in rows]


@jax.named_scope(KDA_CORE_SCOPE)
def chunked_kda(
    q, k, v, g, beta, *, scale: Optional[float] = None, chunk: int = 64,
    sub: int = 16,
):
    """:func:`recurrent_kda` computed chunk-wise (module docstring); same
    arguments, the result in the dtype of ``v``.  Chunks of 64 in blocks
    of 16 run 37 ms forward and 104 with the backward pass at ``[2, 8192,
    32, 128]`` on a v5e; chunks of 32 read 32 and 95 there, and
    ``kimi_linear_train``'s whole step then no longer fits the chip (the
    ``[.., 16, 16]`` blocks pad eightfold; PERF.md, PR 30).  A length the chunk does
    not divide is padded with tokens that leave the state alone (``g`` 0,
    ``beta`` 0)."""
    B, T, H, dk = q.shape
    dv = v.shape[-1]
    dtype = v.dtype
    scale = dk**-0.5 if scale is None else scale
    sub = min(sub, chunk)
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(
            f"chunk {chunk} has to be a power-of-two multiple of sub {sub}"
        )
    pad = -T % chunk
    n = (T + pad) // chunk

    def chunks(x):
        # [B, T, H, ...] -> [n, B, H, chunk, ...]: the scan runs over axis 0.
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(B, n, chunk, H, *x.shape[3:])
        return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)

    q, k, v = chunks(q), chunks(k), chunks(v)
    b = chunks(beta.astype(_F32))[..., None]  # [n, B, H, chunk, 1]
    G = jnp.cumsum(chunks(g.astype(_F32)), axis=-2)  # [n, B, H, chunk, dk]
    G_end = G[..., -1:, :]

    kk, qk = _decayed_products((k, q), k, G, sub, dtype)
    A = b * jnp.tril(kk, -1)
    t = unit_lower_inverse(A, sub).astype(dtype)
    decayed = jnp.exp(G)
    kf = k.astype(_F32)
    rhs = jnp.concatenate([b * v.astype(_F32), b * kf * decayed], axis=-1)
    solved = jnp.matmul(t, rhs.astype(dtype), preferred_element_type=_F32)
    u_own, w = solved[..., :dv], solved[..., dv:].astype(dtype)
    k_end = (kf * jnp.exp(G_end - G)).astype(dtype)

    def step(S, x):
        u_own_c, w_c, k_end_c, decay_c = x
        Sd = S.astype(dtype)
        u = u_own_c - jnp.matmul(w_c, Sd, preferred_element_type=_F32)
        u = u.astype(dtype)
        S = decay_c * S + jnp.einsum(
            "bhck,bhcv->bhkv", k_end_c, u, preferred_element_type=_F32
        )
        return S, (Sd, u)

    S0 = jnp.zeros((B, H, dk, dv), _F32)
    _, (S_at, U) = lax.scan(
        step, S0, (u_own, w, k_end, jnp.swapaxes(jnp.exp(G_end), -1, -2))
    )
    q_in = (q.astype(_F32) * decayed).astype(dtype)
    out = jnp.matmul(q_in, S_at, preferred_element_type=_F32) + jnp.matmul(
        qk.astype(dtype), U, preferred_element_type=_F32
    )
    out = (scale * out).astype(dtype)
    # [n, B, H, chunk, dv] -> [B, T, H, dv]
    out = jnp.moveaxis(jnp.moveaxis(out, 2, 3), 0, 1).reshape(B, n * chunk, H, dv)
    return out[:, :T]
