"""Delta-rule linear attention, chunk-wise, for two kinds of decay: one
per channel (KDA) and one per head (the gated delta rule).

Kimi Delta Attention (Kimi Linear technical report, Moonshot AI 2025,
arXiv:2510.26692) keeps, per head, a state ``S`` ``[dk, dv]`` that every
token decays channel by channel, corrects by the delta rule and reads::

    S_t = (I - b_t k_t k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t = scale * S_t^T q_t

with ``a_t`` in (0, 1)^dk (given here as its logarithm ``g_t <= 0``) and
``b_t`` in (0, 1).  The gated delta rule (Yang, Kautz, Hatamizadeh 2024,
"Gated Delta Networks", arXiv:2412.06464; Olmo-Hybrid's linear layers) is
the same recurrence with ``a_t`` one number a head, ``g`` ``[B, T, H]``,
and ``b_t`` in (0, 2) where the model allows the transition ``I - b k
k^T`` eigenvalues down to -1; its key and value widths need not agree
(96 and 192).  :func:`recurrent_kda` is the recurrence token by token (a
``lax.scan`` over time; the oracle of the tests, of both: a scalar decay
is ``g`` spread over the key channels).  :func:`chunked_kda` and
:func:`chunked_gdn` are what the models run: matrix products inside
chunks of ``chunk`` tokens and the state carried from chunk to chunk.

The chunk-wise form.  With ``G_t = sum_{r<=t} g_r`` inside a chunk that
starts from ``S_0``, and ``u_t = b_t (v_t - S_{t-1}^T (a_t * k_t))`` (what
the delta rule really writes), ``S_t = diag(e^{G_t}) S_0 + sum_{s<=t}
diag(e^{G_t - G_s}) k_s u_s^T``, and the ``u`` of a chunk solve

    (I + A) U = diag(b) (V - (K * e^G) S_0),
    A_ts = b_t sum_c k_tc k_sc e^{G_tc - G_sc}   (s < t),

a unit lower-triangular system per chunk and head (for any ``b``: nothing
here needs ``b < 1``).  So per chunk: ``T = (I + A)^-1``; ``U = T diag(b)
V - (T diag(b) (K * e^G)) S_0``; ``O = scale * ((Q * e^G) S_0 + tril(QK)
U)`` with ``QK_ts = sum_c q_tc k_sc e^{G_tc - G_sc}`` (``s <= t``); ``S_C
= diag(e^{G_C}) S_0 + (K * e^{G_C - G})^T U``.  Only ``U`` and the state
need the chunks in order.

Decays near 0.  ``e^{G_t - G_s}`` cannot be split as ``e^{G_t} e^{-G_s}``:
sixty-four steps of a strong decay put ``e^{-G_s}`` beyond float32 while
the quotient is an ordinary number.  Every exponent taken here is of a
difference ``G_later - G_earlier <= 0``.  With a decay per channel the
pairs of one ``sub``-token block are computed one by one (``[sub, sub,
dk]`` exponentials), a pair of different blocks through a point between
them, the first row of the later block (``e^{G_t - G_ref} e^{G_ref -
G_s}``, both at most 1), so those are matrix products.  With one decay a
head ``e^{G_t - G_s}`` leaves the sum over channels: ``K K^T`` and ``Q
K^T`` are plain matrix products under one ``[C, C]`` mask of such
quotients and there is no pair loop (:func:`plain_gdn`); everything after
the two products is one function for both decays (:func:`_chunkwise`).

Precision.  ``g``, ``G``, every exponential, ``A``, ``T``, the state and
all accumulations are float32.  The matrix products take their operands
in the dtype of ``v`` (bfloat16 in a bf16 model, float32 otherwise) and
accumulate in float32.  ``T`` is made in float32: exact forward
substitution inside ``sub`` x ``sub`` blocks, then ``T_21 = -T_22 A_21
T_11`` block by block at full precision.

Two routes, one entry.  :func:`chunked_kda` runs :func:`kernel_kda`, the
chunk-wise form as two Pallas (Mosaic) kernels under one ``custom_vjp``,
on a TPU where such a kernel can lower and the shapes are whole tiles
(key and value widths multiples of 128, chunks of 64 in blocks of 16),
and :func:`plain_kda`, the same form in ``jax.numpy``, everywhere else
(the CPU, other head sizes, a ``jit`` over several devices outside
``shard_map``); the choice is counted once per traced call
(``kda/route_kernel``, ``kda/route_plain``).  The two paragraphs above
hold for both, forward and backward.  :func:`chunked_gdn` has the one
route, :func:`plain_gdn`, and counts its traced calls as
``gdn/route_plain``.

The kernels read and write the flat ``[B, T, H * d]`` views
(:func:`kernel_kda_flat`); :func:`kernel_kda` folds ``[B, T, H, d]``
arguments into them.  A caller that holds flat views already, the KDA
mixer on its fused route (:func:`kda_mixer_route`), enters through
:func:`chunked_kda_flat` and does its own element-wise work between the
projections and the core and after it as fused passes over the same views
(:func:`kda_prologue`, :func:`kda_epilogue`; the section comment above
them): outside the ``kda_core`` scope, so that scope's time and its
yardstick keep meaning the core.

The backward pass of the plain routes is autodiff through all of it
except ``T``, whose cotangent is ``-T^T dT T^T`` (no pass through the
substitution); the kernels' is by hand (the section comment below).
What the plain routes keep is per chunk (the state at each chunk's start,
``T`` and ``U``); the kernels keep ``T`` per chunk and one state a grid
step's token block (eight chunks at most), from which the backward kernel
walks the block's chunks forward again (:func:`_carry_terms`: what carries
the state given ``T``, a fraction of a forward pass); never a state per
token.  That residual and the output are small enough for a caller that
recomputes the call's surroundings to keep (:func:`kernel_kda_results`,
:func:`chunked_kda_flat`'s ``keep``): its backward pass then runs no
forward kernel.
"""

from __future__ import annotations

import functools
import types
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_models_tpu.ops.attention import (
    _LANES,
    _vma,
    mosaic_can_lower,
)
from distributed_tensorflow_models_tpu.telemetry.registry import (
    GDN_ROUTE_PLAIN,
    KDA_ROUTE_KERNEL,
    KDA_ROUTE_PLAIN,
    get_registry,
)

# ``jax.named_scope`` of the chunk-wise core, forward and backward: a path
# element of every instruction's ``op_name`` in the compiled step (PERF.md
# section 3).  The projections, the convolutions, the gates and the output
# norm of the mixer stay outside it (``linear_attn`` holds them all).
KDA_CORE_SCOPE = "kda_core"
# The same of :func:`chunked_gdn` (one decay a head): a scope of its own,
# because its need per step is another count (``benchmark/flops/``).
GDN_CORE_SCOPE = "gdn_core"

# The innermost scope of the mixer's fused element-wise passes' kernels
# (inside ``linear_attn``, outside ``kda_core``).  The kernels'
# ``custom_vjp`` rules are jitted (one trace and one lowering for a step's
# many identical calls: traced call by call they were 58 of a warm start's
# 131 s, PERF.md, PR 33), which makes the rule's name the innermost element
# of a kernel's ``op_name`` and, with XLA, the name of its instruction; so
# each ``pallas_call`` is bound under its scope again, and a trace's rows
# read ``kda_core`` and ``kda_pass`` whatever the rules are called.
KDA_PASS_SCOPE = "kda_pass"

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


def _check_chunk(chunk: int, sub: int) -> int:
    """``sub`` clamped to the chunk; a chunk is a power-of-two multiple of
    it (:func:`unit_lower_inverse` doubles the blocks)."""
    sub = min(sub, chunk)
    if chunk % sub or (chunk // sub) & (chunk // sub - 1):
        raise ValueError(
            f"chunk {chunk} has to be a power-of-two multiple of sub {sub}"
        )
    return sub


def _in_chunks(x, chunk: int):
    """``[B, T, H, ...]`` -> ``[n, B, H, chunk, ...]`` (a scan runs over
    axis 0), the length padded to whole chunks with zeros: tokens that
    leave the state alone (``g`` 0, ``beta`` 0)."""
    B, T, H = x.shape[:3]
    pad = -T % chunk
    x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
    x = x.reshape(B, (T + pad) // chunk, chunk, H, *x.shape[3:])
    return jnp.moveaxis(jnp.moveaxis(x, 1, 0), 3, 2)


def _chunkwise(q, k, v, b, G, kk, qk, *, scale: float, sub: int, length: int):
    """The chunk-wise form from the decayed products on (module
    docstring), for either decay: ``q``, ``k``, ``v`` in chunks ``[n, B,
    H, C, d]``, ``b`` ``[n, B, H, C, 1]``, the running sum ``G`` of the
    log decay ``[n, B, H, C, dk]`` (a decay per channel) or ``[n, B, H, C,
    1]`` (one a head), ``kk`` and ``qk`` ``[n, B, H, C, C]`` float32.
    Returns ``[B, length, H, dv]`` in the dtype of ``v``."""
    n, B, H, chunk, dk = k.shape
    dv = v.shape[-1]
    dtype = v.dtype
    G_end = G[..., -1:, :]
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
    return out[:, :length]


def plain_kda(
    q, k, v, g, beta, *, scale: Optional[float] = None, chunk: int = 64,
    sub: int = 16,
):
    """The chunk-wise form in plain ``jax.numpy`` (module docstring): the
    route of :func:`chunked_kda` wherever the kernels do not run, and
    their oracle.  Same arguments as :func:`recurrent_kda`, the result in
    the dtype of ``v``.  Chunks of 64 in blocks of 16 run 37-41 ms forward
    and 104-107 with the backward pass at ``[2, 8192, 32, 128]`` on a v5e
    (every intermediate goes through HBM; PERF.md, PRs 30 and 31).  A
    length the chunk does not divide is padded with tokens that leave the
    state alone (``g`` 0, ``beta`` 0)."""
    T, dk = q.shape[1], q.shape[-1]
    scale = dk**-0.5 if scale is None else scale
    sub = _check_chunk(chunk, sub)
    q, k, v = (_in_chunks(x, chunk) for x in (q, k, v))
    b = _in_chunks(beta.astype(_F32), chunk)[..., None]  # [n, B, H, chunk, 1]
    G = jnp.cumsum(_in_chunks(g.astype(_F32), chunk), axis=-2)  # [.., chunk, dk]
    kk, qk = _decayed_products((k, q), k, G, sub, v.dtype)
    return _chunkwise(q, k, v, b, G, kk, qk, scale=scale, sub=sub, length=T)


def plain_gdn(
    q, k, v, g, beta, *, scale: Optional[float] = None, chunk: int = 64,
    sub: int = 16,
):
    """The chunk-wise form for **one decay a head and step** (the gated
    delta rule; module docstring): ``g`` ``[B, T, H]``, the key and value
    widths free (96 and 192 in Olmo-Hybrid).  A scalar decay leaves the
    key-key and query-key products ordinary matrix products under one
    ``[C, C]`` mask ``e^{G_t - G_s}`` (``s <= t``: no exponent is
    positive), so there is no pair loop; everything after them is
    :func:`plain_kda`'s own code."""
    T, dk = q.shape[1], q.shape[-1]
    scale = dk**-0.5 if scale is None else scale
    sub = _check_chunk(chunk, sub)
    q, k, v = (_in_chunks(x, chunk) for x in (q, k, v))
    b = _in_chunks(beta.astype(_F32), chunk)[..., None]  # [n, B, H, chunk, 1]
    G = jnp.cumsum(_in_chunks(g.astype(_F32), chunk), axis=-1)  # [n, B, H, chunk]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    mask = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    masked = lambda x: mask * jnp.einsum(
        "...tc,...sc->...ts", x, k, preferred_element_type=_F32
    )
    return _chunkwise(
        q, k, v, b, G[..., None], masked(k), masked(q), scale=scale, sub=sub,
        length=T,
    )


# --- The same chunk-wise form as Pallas (Mosaic) kernels --------------------
#
# One grid step is a group of heads' ``block`` tokens (whole chunks, worked
# through in order by a ``fori_loop``); the grid is (batch, token block,
# head group) with the token blocks in sequence, so every head's state
# ``S^T`` ``[dv, dk]`` float32 stays in VMEM scratch from block to block.
# Heads are lane blocks of the flat ``[B, T, H * d]`` views, which is how
# the kernels' entry takes and returns them (:func:`kernel_kda_flat`):
# nothing is transposed in HBM inside it, and a caller that holds such
# views (the mixer's projections write them) has nothing relaid out on
# either side; :func:`kernel_kda` folds ``[B, T, H, d]`` arguments, which
# on the chip's ``(8, 128)`` tiles is a relayout and not a bitcast (PERF.md,
# PR 33).  Per chunk nothing leaves VMEM but the output and,
# where a backward pass will follow, ``T``; per grid step, then, the state
# at the token block's start too (a state a chunk was 537 MB a layer at
# ``kimi_linear_train``'s call, two thirds of what the backward was
# handed; a state a block of eight chunks is 67 MB: ISSUE 47).  The
# backward kernel sweeps the token blocks in reverse with the state's
# cotangent in scratch.  At the start of a grid step it walks the block's
# chunks forward once from the kept state and puts every chunk's start
# state into scratch: ``G``, ``e^G``, ``w = T (b k e^G)``, ``u = T (b v) - w
# S^T``, ``S <- S e^{G_end} + u^T (k e^{G_end - G})``, the forward kernel's
# own expressions in its order and dtypes (:func:`_carry_terms`), so its
# states bit for bit, with no pair loop, no level products and no output.
# The reverse sweep then makes every other intermediate of a chunk again
# from the inputs and what was kept.
#
# A chunk and head is a chain of some dozen small dependent products, so
# the kernels are bound by that chain's latency and by the passes of its
# float32 products, not by operations or bytes (PERF.md, PR 31): the pair
# loop is unrolled (its sixteen steps are independent but for a select and
# the substitution's rank-one update; not unrolled it ran seven times
# slower), a grid step works through two heads whose chains the scheduler
# interleaves, and the backward is given ``T`` and not the chain that
# makes it.
#
# Same mathematics, same precision (module docstring): ``G`` is the sum of
# three bfloat16 products of a triangle of ones with the three pieces of
# ``g``, float32 sums, exact as a float32 product at full precision
# (:func:`_sum_rows`); the pairs inside a
# ``sub`` x ``sub`` block are float32 on the vector unit, column ``j`` of
# every block per loop step, and the same step is one step of the exact
# forward substitution inside the blocks (``(I + D)^-1 = (I - d_15 e_15^T)
# ... (I - d_0 e_0^T)``, ``d_j`` column ``j`` of ``D``: a rank-one update
# of all four blocks at once, not a row at a time); pairs of different
# blocks go through the first row of the later block; the blocks'
# inverses combine as ``T <- T - T A_off T`` level by level at full
# precision (that is ``T_21 = -T_22 A_21 T_11``); every other product
# takes its operands in the dtype of ``v`` with float32 accumulation.  The
# backward is by hand: ``dA = -T^T dT T^T``; for ``M_ts = sum_c x_tc k_sc
# e^{G_tc - G_sc}`` with ``dx``, ``dk`` the cotangents through ``M`` alone,
# ``dG = x dx - k dk`` (the same for the factors ``e^G``, ``e^{G_end -
# G}``, ``e^{G_end}``), and ``dg`` is the reverse running sum of ``dG``
# inside the chunk.  No exponent taken is positive there either.

_KERNEL_CHUNK, _KERNEL_SUB = 64, 16
_KERNEL_BLOCK_CHUNKS = (8, 4, 2, 1)  # chunks a grid step, the most that divides
_KERNEL_HEADS = (2, 1)  # heads a grid step, likewise
_KERNEL_VMEM_BYTES = 64 * 1024 * 1024  # of v5e's 128 MiB

_NN = (((1,), (0,)), ((), ()))  # a @ b
_NT = (((1,), (1,)), ((), ()))  # a @ b^T
_TN = (((0,), (0,)), ((), ()))  # a^T @ b


def _mm(a, b, dims=_NN):
    """A matrix product with float32 accumulation; float32 operands at
    full precision, others as they are (said outright: an ambient
    ``jax.default_matmul_precision`` is not for bfloat16 operands)."""
    return lax.dot_general(
        a, b, dims, preferred_element_type=_F32,
        precision=_HI if a.dtype == _F32 else lax.Precision.DEFAULT,
    )


def _sum_rows(ones, x):
    """``ones @ x`` for a matrix of zeros and ones and float32 ``x``, as
    exact as a float32 product at full precision at half its passes: the
    three bfloat16 pieces of ``x`` add up to it exactly, the ones have no
    second piece, and the sums are float32."""
    ones = ones.astype(jnp.bfloat16)
    total = None
    for _ in range(3):
        piece = x.astype(jnp.bfloat16)
        x = x - piece.astype(_F32)
        part = _mm(ones, piece)
        total = part if total is None else total + part
    return total


def kernel_admissible(q, k, v, g, beta, *, chunk: int, sub: int) -> bool:
    """Whether the kernels take this call: whole tiles (key and value
    widths multiples of 128 lanes, chunks of 64 in blocks of 16), one
    dtype for ``q``, ``k``, ``v``.  Visible at trace time; the backend is
    the caller's question."""
    return (
        q.shape == k.shape == g.shape
        and q.shape[:3] == v.shape[:3] == beta.shape
        and q.dtype == k.dtype == v.dtype
        and q.shape[-1] % _LANES == 0
        and v.shape[-1] % _LANES == 0
        and (chunk, sub) == (_KERNEL_CHUNK, _KERNEL_SUB)
    )


def _same_group(row, col, size):
    """Whether row and column index fall into the same group of ``size``
    (a power of two)."""
    shift = size.bit_length() - 1
    return lax.shift_right_logical(row, shift) == lax.shift_right_logical(col, shift)


def _block_rows(ref, a, j, n, sub, width):
    """Row ``j`` of each of the ``n`` blocks of ``ref[a]`` ``[n * sub,
    width]``, each spread over its block's rows."""
    return jnp.concatenate(
        [
            jnp.broadcast_to(ref[a, pl.ds(i * sub + j, 1), :], (sub, width))
            for i in range(n)
        ],
        axis=0,
    )


def _chunk_terms(q, k, v, g, b, St, G_scr, kf_scr, X_scr, a, *, sub, T=None):
    """What a chunk's forward pass makes from its inputs ``[C, d]``, ``b``
    ``[C, 1]`` and the state ``S^T`` at its start, all in VMEM; the
    backward kernel makes the same again, but for ``T``, which it is
    given.  Slice ``a`` of ``G_scr``, ``kf_scr`` ``[heads, C, dk]`` and
    ``X_scr`` ``[heads, C, C]`` is this head's float32 scratch for the row
    reads of the pair loop."""
    C, dk = k.shape
    dtype = v.dtype
    n = C // sub
    qf, kf, vf = q.astype(_F32), k.astype(_F32), v.astype(_F32)
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    same_block = _same_group(row, col, sub)
    lower = col <= row
    G = _sum_rows(lower, g.astype(_F32))  # the running sum
    G_scr[a] = G
    kf_scr[a] = kf
    trow = lax.broadcasted_iota(jnp.int32, (C, dk), 0)
    in_block = trow & (sub - 1)

    # Pairs of different blocks, through the first row of the later one.
    scale_t = jnp.exp(G - _block_rows(G_scr, a, 0, n, sub, dk))
    kt, qt = (kf * scale_t).astype(dtype), (qf * scale_t).astype(dtype)
    es, ks = [None], [None]
    kk_rows = [jnp.zeros((sub, C), _F32)]
    qk_rows = [jnp.zeros((sub, C), _F32)]
    for i in range(1, n):
        ref = G[i * sub:i * sub + 1, :]
        es.append(jnp.exp(jnp.where(trow < i * sub, ref - G, -jnp.inf)))
        ks.append((kf * es[i]).astype(dtype))
        lhs = jnp.concatenate(
            [kt[i * sub:(i + 1) * sub], qt[i * sub:(i + 1) * sub]], axis=0
        )
        prod = _mm(lhs, ks[i], _NT)  # [2 sub, C]
        kk_rows.append(prod[:sub])
        qk_rows.append(prod[sub:])

    # Pairs inside one block, one by one: column j of every block a step,
    # and with it a step of the forward substitution inside the blocks:
    # (I + D)^-1 = (I - d_15 e_15^T) ... (I - d_0 e_0^T), d_j column j of D.
    below = in_block[:, :1]
    eye = (row == col).astype(_F32)

    def pairs(j, carry):
        kk_in, qk_in, X = carry
        Gs = _block_rows(G_scr, a, j, n, sub, dk)
        ksj = _block_rows(kf_scr, a, j, n, sub, dk)
        kd = ksj * jnp.exp(jnp.where(in_block >= j, G - Gs, -jnp.inf))
        at_j = (col & (sub - 1)) == j
        kk_j = jnp.sum(kf * kd, axis=1, keepdims=True)
        qk_j = jnp.sum(qf * kd, axis=1, keepdims=True)
        if T is None:
            X_scr[a] = X
            X = X - jnp.where(below > j, b * kk_j, 0.0) * _block_rows(
                X_scr, a, j, n, sub, C
            )
        return jnp.where(at_j, kk_j, kk_in), jnp.where(at_j, qk_j, qk_in), X

    zero = jnp.zeros((C, C), _F32)
    kk_in, qk_in, X = lax.fori_loop(0, sub, pairs, (zero, zero, eye), unroll=True)
    inside = same_block & lower
    kk = jnp.where(inside, kk_in, jnp.concatenate(kk_rows, axis=0))
    qk = jnp.where(inside, qk_in, jnp.concatenate(qk_rows, axis=0))

    # T = (I + A)^-1, float32: the blocks' inverses combine level by level
    # at full precision, T <- T - T A_off T (that is T_21 = -T_22 A_21 T_11).
    kk_strict = jnp.where(col < row, kk, 0.0)
    A = b * kk_strict
    size = sub
    while T is None and size < C:
        off = _same_group(row, col, 2 * size) & ~_same_group(row, col, size)
        X = X - _mm(_mm(X, jnp.where(off, A, 0.0)), X)
        size *= 2
    T = X if T is None else T

    x = _carry_terms(kf, vf, G, b, St, T, dtype)
    qi32 = qf * x.E
    return types.SimpleNamespace(
        qf=qf, kf=kf, vf=vf, G=G, scale_t=scale_t, kt=kt, qt=qt, es=es, ks=ks,
        kk_strict=kk_strict, qk=qk, T=T, qi32=qi32, qi=qi32.astype(dtype),
        row=row, col=col, same_block=same_block, in_block=in_block,
        **vars(x),
    )


def _carry_terms(kf, vf, G, b, St, T, dtype):
    """What carries the state ``S^T`` over a chunk once ``T`` is there:
    ``u`` (what the delta rule writes) and the factors of the update, from
    the float32 keys and values ``[C, d]``, the running sum ``G``, ``b``
    ``[C, 1]`` and the state at the chunk's start.  The forward pass's own
    expressions (:func:`_chunk_terms` ends in them), so the backward
    kernel's walk over a block's chunks makes the forward's states."""
    E = jnp.exp(G)
    G_end = G[G.shape[0] - 1:, :]
    Tb = T.astype(dtype)
    rv = (b * vf).astype(dtype)
    rk32 = b * kf * E
    rk = rk32.astype(dtype)
    u_own = _mm(Tb, rv)
    w = _mm(Tb, rk).astype(dtype)
    Sd = St.astype(dtype)
    u = (u_own - _mm(w, Sd, _NT)).astype(dtype)
    e_end = jnp.exp(G_end - G)
    ke32 = kf * e_end
    return types.SimpleNamespace(
        E=E, e_end=e_end, decay=jnp.exp(G_end), Tb=Tb, rv=rv, rk32=rk32,
        rk=rk, w=w, Sd=Sd, u=u, ke32=ke32, ke=ke32.astype(dtype),
    )


def _next_state(St, x):
    """``S^T`` at the chunk's end from :func:`_carry_terms`' ``x``."""
    return St * x.decay + _mm(x.u, x.ke, _TN)


def _beta_column(b_ref, rows, h):
    """Head ``h``'s column of the ``[C, H]`` tile, as ``[C, 1]``."""
    tile = b_ref[0, rows, :].astype(_F32)
    lane = lax.broadcasted_iota(jnp.int32, tile.shape, 1)
    return jnp.sum(jnp.where(lane == h, tile, 0.0), axis=1, keepdims=True)


def _kda_fwd_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, o_ref, *rest,
    scale, chunk, sub, chunks, heads, keep_states,
):
    """Grid (B, token blocks, H / heads).  ``S_scr`` ``[H, dv, dk]`` holds
    every head's ``S^T``; ``s_ref`` and ``t_ref`` (kept for a backward
    pass) take the state at the token block's start and every chunk's
    ``T``.  The ``heads`` of a step are independent chains of small
    dependent products: written one after the other in one block of code,
    the compiler's scheduler runs them side by side."""
    s_ref, t_ref = rest[:2] if keep_states else (None, None)
    S_scr, G_scr, kf_scr, X_scr = rest[-4:]
    dk, dv = G_scr.shape[-1], S_scr.shape[-2]
    first = pl.program_id(2) * heads

    @pl.when(pl.program_id(1) == 0)
    def _start():
        S_scr[pl.ds(first, heads)] = jnp.zeros((heads,) + S_scr.shape[1:], _F32)

    if keep_states:
        s_ref[0, 0] = S_scr[pl.ds(first, heads)]

    def one_chunk(c, _):
        rows = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        for a in range(heads):
            kl, vl = slice(a * dk, (a + 1) * dk), slice(a * dv, (a + 1) * dv)
            St = S_scr[first + a]
            x = _chunk_terms(
                q_ref[0, rows, kl], k_ref[0, rows, kl], v_ref[0, rows, vl],
                g_ref[0, rows, kl], _beta_column(b_ref, rows, first + a), St,
                G_scr, kf_scr, X_scr, a, sub=sub,
            )
            if keep_states:
                t_ref[0, c, a] = x.T
            out = _mm(x.qi, x.Sd, _NT) + _mm(x.qk.astype(x.u.dtype), x.u)
            o_ref[0, rows, vl] = (scale * out).astype(o_ref.dtype)
            S_scr[first + a] = _next_state(St, x)
        return 0

    lax.fori_loop(0, chunks, one_chunk, 0)


def _kernel_geometry(q, v, beta, chunk):
    """From the flat views ``[B, T, H * d]`` and ``beta`` ``[B, T, H]``;
    ``n`` whole chunks (the kernels are handed the length padded to them)."""
    B, T, H = beta.shape
    n = -(-T // chunk)
    chunks = next(m for m in _KERNEL_BLOCK_CHUNKS if n % m == 0)
    heads = next(m for m in _KERNEL_HEADS if H % m == 0)
    return B, T, H, q.shape[-1] // H, v.shape[-1] // H, n, chunks, heads


def _kernel_specs(chunk, H, dk, dv, chunks, heads, order):
    """Block specs over the grid (batch, token block, head group): ``[block,
    heads * d]`` tiles of the ``[B, T, H * d]`` views, the ``[block, H]``
    tile of ``beta``, a block's state and its ``chunks`` chunks' ``T``;
    ``order`` maps the grid's token block to the array's (the backward
    sweeps in reverse)."""
    block = chunks * chunk
    tile = lambda d: pl.BlockSpec(
        (1, block, heads * d), lambda b, t, h: (b, order(t), h)
    )
    return (
        tile(dk), tile(dv),
        pl.BlockSpec((1, block, H), lambda b, t, h: (b, order(t), 0)),
        pl.BlockSpec(
            (1, 1, heads, dv, dk), lambda b, t, h: (b, order(t), h, 0, 0)
        ),
        pl.BlockSpec(
            (1, chunks, heads, chunk, chunk),
            lambda b, t, h: (b, order(t), h, 0, 0),
        ),
    )


def _kernel_scratch(H, dk, dv, chunk, heads):
    """The state (or its cotangent) of every head, and a head group's
    scratch for the pair loop's row reads."""
    return [
        pltpu.VMEM((H, dv, dk), _F32),
        pltpu.VMEM((heads, chunk, dk), _F32),
        pltpu.VMEM((heads, chunk, dk), _F32),
        pltpu.VMEM((heads, chunk, chunk), _F32),
    ]


def _kernel_forward(q, k, v, g, beta, *, scale, chunk, sub, keep_states, interpret):
    """The output ``[B, T, H * dv]`` from the flat views and, with
    ``keep_states``, the rest of :func:`kernel_kda_results`; ``T`` a
    multiple of ``chunk``."""
    B, T, H, dk, dv, n, chunks, heads = _kernel_geometry(q, v, beta, chunk)
    kspec, vspec, bspec, sspec, tspec = _kernel_specs(
        chunk, H, dk, dv, chunks, heads, lambda t: t
    )
    results = (vspec, sspec, tspec) if keep_states else (vspec,)
    call = pl.pallas_call(
        functools.partial(
            _kda_fwd_kernel, scale=scale, chunk=chunk, sub=sub, chunks=chunks,
            heads=heads, keep_states=keep_states,
        ),
        grid=(B, n // chunks, H // heads),
        in_specs=[kspec, kspec, vspec, kspec, bspec],
        out_specs=list(results),
        out_shape=list(kernel_kda_results(q, v, beta, chunk)[:len(results)]),
        scratch_shapes=_kernel_scratch(H, dk, dv, chunk, heads),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BYTES,
        ),
        interpret=interpret,
    )
    with jax.named_scope(KDA_CORE_SCOPE):
        return tuple(call(q, k, v, g, beta))


def _kda_bwd_kernel(
    q_ref, k_ref, v_ref, g_ref, b_ref, s_ref, t_ref, do_ref,
    dq_ref, dk_ref, dv_ref, dg_ref, db_ref,
    dS_scr, G_scr, kf_scr, X_scr, dkc_scr, S_scr,
    *, scale, chunk, sub, chunks, heads,
):
    """Grid (B, token blocks in reverse, H / heads).  ``dS_scr`` ``[H, dv, dk]``
    holds the cotangent of every head's ``S^T`` at the end of the chunk
    being worked on; ``dkc_scr`` takes the rows of the pair loop's key
    cotangent; ``S_scr`` ``[heads, chunks, dv, dk]`` the state at the start
    of each of the block's chunks, walked forward from the one state the
    block was handed (``s_ref``) before the reverse sweep reads them.
    ``db_ref`` is the ``[block, H]`` tile every head of the block writes
    its column of."""
    first = pl.program_id(2) * heads
    n = chunk // sub
    dk, dv = G_scr.shape[-1], dS_scr.shape[-2]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dS_scr[pl.ds(first, heads)] = jnp.zeros((heads,) + dS_scr.shape[1:], _F32)

    def chunk_rows(c):
        return pl.ds(pl.multiple_of(c * chunk, chunk), chunk)

    # The chunks' states again: what carries ``S`` from chunk to chunk
    # given ``T`` (no pair loop, no level products, no output), the
    # forward kernel's expressions, so its states bit for bit.
    for a in range(heads):
        S_scr[a, 0] = s_ref[0, 0, a]

    def walk(c, _):
        rows = chunk_rows(c)
        lower = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1) <= lax.broadcasted_iota(
            jnp.int32, (chunk, chunk), 0
        )
        for a in range(heads):
            kl, vl = slice(a * dk, (a + 1) * dk), slice(a * dv, (a + 1) * dv)
            v = v_ref[0, rows, vl]
            St = S_scr[a, c]
            x = _carry_terms(
                k_ref[0, rows, kl].astype(_F32), v.astype(_F32),
                _sum_rows(lower, g_ref[0, rows, kl].astype(_F32)),
                _beta_column(b_ref, rows, first + a), St, t_ref[0, c, a], v.dtype,
            )
            S_scr[a, c + 1] = _next_state(St, x)
        return 0

    # Unrolled, so that what a chunk's walk makes without the state
    # (``G``, the exponentials, ``T``'s two products) may be scheduled
    # beside the chunk before's products with the state: at most seven
    # short bodies (PERF.md section 6, PR 47, has what was read of it).
    if chunks > 1:
        lax.fori_loop(0, chunks - 1, walk, 0, unroll=True)

    def one_head(a, c, rows):
        h = first + a
        kl, vl = slice(a * dk, (a + 1) * dk), slice(a * dv, (a + 1) * dv)
        St = S_scr[a, c]
        b = _beta_column(b_ref, rows, h)
        v = v_ref[0, rows, vl]
        dtype = v.dtype
        x = _chunk_terms(
            q_ref[0, rows, kl], k_ref[0, rows, kl], v, g_ref[0, rows, kl], b,
            St, G_scr, kf_scr, X_scr, a, sub=sub, T=t_ref[0, c, a],
        )
        qf, kf, G, E = x.qf, x.kf, x.G, x.E
        row, col = x.row, x.col
        C = chunk
        dSt = dS_scr[h]
        dSb = dSt.astype(dtype)
        dOb = (scale * do_ref[0, rows, vl].astype(_F32)).astype(dtype)

        # The output and the state's update.
        dqi = _mm(dOb, x.Sd)
        dqk = jnp.where(col <= row, _mm(dOb, x.u, _NT), 0.0)
        du = _mm(x.qk.astype(dtype), dOb, _TN) + _mm(x.ke, dSb, _NT)
        dke = _mm(x.u, dSb)
        dub = du.astype(dtype)
        # u = T (b v) - (T (b k e^G)) S.
        dwb = (-_mm(dub, x.Sd)).astype(dtype)
        dT = _mm(dub, x.rv, _NT) + _mm(dwb, x.rk, _NT)
        drv = _mm(x.Tb, dub, _TN)
        drk = _mm(x.Tb, dwb, _TN)
        dS_scr[h] = (
            dSt * x.decay + _mm(dOb, x.qi, _TN) - _mm(dub, x.w, _TN)
        )
        # T = (I + A)^-1: dA = -T^T dT T^T, strictly lower.
        dA = jnp.where(col < row, -_mm(_mm(x.T, dT, _TN), x.T, _NT), 0.0)
        dkk = b * dA
        db = (
            jnp.sum(dA * x.kk_strict, axis=1, keepdims=True)
            + jnp.sum(drv * x.vf, axis=1, keepdims=True)
            + jnp.sum(drk * kf * E, axis=1, keepdims=True)
        )

        # The decayed products: pairs of different blocks ...
        dkx_rows = [jnp.zeros((sub, dk), _F32)]
        dqx_rows = [jnp.zeros((sub, dk), _F32)]
        dkc = jnp.zeros((C, dk), _F32)
        for i_blk in range(1, n):
            blk = slice(i_blk * sub, (i_blk + 1) * sub)
            dm = jnp.concatenate([dkk[blk], dqk[blk]], axis=0).astype(dtype)
            dxt = _mm(dm, x.ks[i_blk])  # [2 sub, dk]
            dkx_rows.append(dxt[:sub] * x.scale_t[blk])
            dqx_rows.append(dxt[sub:] * x.scale_t[blk])
            xt = jnp.concatenate([x.kt[blk], x.qt[blk]], axis=0)
            dkc = dkc + _mm(dm, xt, _TN) * x.es[i_blk]
        dkx = jnp.concatenate(dkx_rows, axis=0)
        dqx = jnp.concatenate(dqx_rows, axis=0)

        # ... and the pairs inside one block, column j of every block a step.
        def pairs(j, carry):
            dkx, dqx = carry
            Gs = _block_rows(G_scr, a, j, n, sub, dk)
            ksj = _block_rows(kf_scr, a, j, n, sub, dk)
            e = jnp.exp(jnp.where(x.in_block >= j, G - Gs, -jnp.inf))
            kd = ksj * e
            at_j = x.same_block & ((col & (sub - 1)) == j)
            mk = jnp.sum(jnp.where(at_j, dkk, 0.0), axis=1, keepdims=True)
            mq = jnp.sum(jnp.where(at_j, dqk, 0.0), axis=1, keepdims=True)
            to_key = (mk * kf + mq * qf) * e
            for i_blk in range(n):
                dkc_scr[a, pl.ds(i_blk * sub + j, 1), :] = jnp.sum(
                    to_key[i_blk * sub:(i_blk + 1) * sub], axis=0, keepdims=True
                )
            return dkx + mk * kd, dqx + mq * kd

        dkx, dqx = lax.fori_loop(0, sub, pairs, (dkx, dqx), unroll=True)
        dkc = dkc + dkc_scr[a]

        # dG = x dx - k dk for every factor; dg its reverse running sum.
        to_end = dke * x.ke32
        dG = (
            kf * (dkx - dkc) + qf * dqx + dqi * x.qi32 + drk * x.rk32
            - to_end
        )
        dG_end = jnp.sum(to_end, axis=0, keepdims=True) + x.decay * jnp.sum(
            dSt * St, axis=0, keepdims=True
        )
        trow = lax.broadcasted_iota(jnp.int32, (C, dk), 0)
        dG = jnp.where(trow == C - 1, dG + dG_end, dG)
        dg_ref[0, rows, kl] = _sum_rows(col >= row, dG).astype(dg_ref.dtype)
        dq_ref[0, rows, kl] = (dqx + dqi * E).astype(dq_ref.dtype)
        dk_ref[0, rows, kl] = (
            dkx + dkc + drk * (b * E) + dke * x.e_end
        ).astype(dk_ref.dtype)
        dv_ref[0, rows, vl] = (b * drv).astype(dv_ref.dtype)
        lane = lax.broadcasted_iota(jnp.int32, (C, db_ref.shape[2]), 1)
        db_ref[0, rows, :] = jnp.where(
            lane == h, db, db_ref[0, rows, :].astype(_F32)
        ).astype(db_ref.dtype)

    def one_chunk(i, _):
        c = chunks - 1 - i
        for a in range(heads):
            one_head(a, c, chunk_rows(c))
        return 0

    lax.fori_loop(0, chunks, one_chunk, 0)


def _kernel_backward(q, k, v, g, beta, states, ts, do, *, scale, chunk, sub, interpret):
    B, T, H, dk, dv, n, chunks, heads = _kernel_geometry(q, v, beta, chunk)
    blocks = n // chunks
    kspec, vspec, bspec, sspec, tspec = _kernel_specs(
        chunk, H, dk, dv, chunks, heads, lambda t: blocks - 1 - t
    )
    vma = _vma(q)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)
    call = pl.pallas_call(
        functools.partial(
            _kda_bwd_kernel, scale=scale, chunk=chunk, sub=sub, chunks=chunks,
            heads=heads,
        ),
        grid=(B, blocks, H // heads),
        in_specs=[kspec, kspec, vspec, kspec, bspec, sspec, tspec, vspec],
        out_specs=[kspec, kspec, vspec, kspec, bspec],
        out_shape=[like(q), like(k), like(v), like(g), like(beta)],
        scratch_shapes=_kernel_scratch(H, dk, dv, chunk, heads)
        + [
            pltpu.VMEM((heads, chunk, dk), _F32),
            pltpu.VMEM((heads, chunks, dv, dk), _F32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BYTES,
        ),
        interpret=interpret,
    )
    with jax.named_scope(KDA_CORE_SCOPE):
        return call(q, k, v, g, beta, states, ts, do)


def _padded(x, pad):
    return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def kernel_kda_flat(
    q, k, v, g, beta, scale=None, chunk=_KERNEL_CHUNK, interpret=False, kept_as=None
):
    """The chunk-wise delta rule as Pallas kernels (section comment above),
    forward and backward, on the flat views the kernels read: ``q``, ``k``,
    ``g`` ``[B, T, H * dk]``, ``v`` ``[B, T, H * dv]``, ``beta`` ``[B, T,
    H]``; returns ``[B, T, H * dv]``.  For a caller that holds such views
    (the fused route of ``models/mixers.py::KDAMixer``): no ``[B, T, H,
    d]`` array exists on either side.  ``interpret=True`` runs the same
    kernels on the CPU for tests.  ``kept_as``: the ``checkpoint_name``
    the forward rule gives :func:`kernel_kda_results`, None for none
    (:func:`chunked_kda_flat`)."""
    return _kernel_fwd(q, k, v, g, beta, scale, chunk, interpret, None, False)[0]


def kernel_kda_results(q, v, beta, chunk=_KERNEL_CHUNK):
    """The shapes of what the forward kernel writes where a backward pass
    will follow, from the flat views: the output (the length in whole
    chunks), the state ``S^T`` at the start of each grid step's token
    block and every chunk's ``T``, float32 both.  What the backward
    kernel needs beside the inputs and the output's cotangent, and so what
    a caller that recomputes the call's surroundings keeps to have no
    forward kernel in its backward pass."""
    B, T, H, dk, dv, n, chunks, _ = _kernel_geometry(q, v, beta, chunk)
    like = functools.partial(jax.ShapeDtypeStruct, vma=_vma(q))
    return (
        like((B, n * chunk, H * dv), v.dtype),
        like((B, n // chunks, H, dv, dk), _F32),
        like((B, n, H, chunk, chunk), _F32),
    )


@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8, 9))
def _kernel_fwd(q, k, v, g, beta, scale, chunk, interpret, kept_as, keep_states=True):
    T, H = beta.shape[1:]
    scale = (q.shape[-1] // H) ** -0.5 if scale is None else scale
    pad = -T % chunk
    padded = tuple(_padded(x, pad) for x in (q, k, v, g, beta))
    out, *kept = _kernel_forward(
        *padded, scale=scale, chunk=chunk, sub=_KERNEL_SUB,
        keep_states=keep_states, interpret=interpret,
    )
    if kept_as is not None:
        out, *kept = (checkpoint_name(x, kept_as) for x in (out, *kept))
    return out[:, :T], padded + tuple(kept)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _kernel_bwd(scale, chunk, interpret, kept_as, res, do):
    del kept_as  # the forward rule's
    T, H = do.shape[1], res[4].shape[-1]
    scale = (res[0].shape[-1] // H) ** -0.5 if scale is None else scale
    grads = _kernel_backward(
        *res, _padded(do, -T % chunk), scale=scale, chunk=chunk,
        sub=_KERNEL_SUB, interpret=interpret,
    )
    return tuple(dx[:, :T] for dx in grads)


kernel_kda_flat.defvjp(_kernel_fwd, _kernel_bwd)


def kernel_kda(q, k, v, g, beta, scale=None, chunk=_KERNEL_CHUNK, interpret=False):
    """:func:`kernel_kda_flat` for ``[B, T, H, d]`` arguments, what
    :func:`chunked_kda` runs on a TPU for the calls
    :func:`kernel_admissible` admits: the heads' channels are folded into
    the lanes on the way in and out again on the way back."""
    B, T, H, dv = v.shape
    flat = lambda x: x.reshape(B, T, -1)
    out = kernel_kda_flat(flat(q), flat(k), flat(v), flat(g), beta, scale, chunk, interpret)
    return out.reshape(B, T, H, dv)


def kda_route(q, k, v, g, beta, *, chunk: int, sub: int) -> str:
    """What :func:`chunked_kda` runs for this call: ``"kernel"`` on a TPU
    for the calls the kernels admit, where a Mosaic kernel can lower;
    else ``"plain"``."""
    if (
        jax.default_backend() == "tpu"
        and kernel_admissible(q, k, v, g, beta, chunk=chunk, sub=sub)
        and mosaic_can_lower()
    ):
        return "kernel"
    return "plain"


@jax.named_scope(KDA_CORE_SCOPE)
def chunked_kda(
    q, k, v, g, beta, *, scale: Optional[float] = None, chunk: int = 64,
    sub: int = 16,
):
    """:func:`recurrent_kda` computed chunk-wise (module docstring); same
    arguments, the result in the dtype of ``v``.  On a TPU, for whole
    tiles, the Pallas kernels (:func:`kernel_kda`: 13.9 ms forward and
    31.2 with the backward pass at ``[2, 8192, 32, 128]`` on a v5e, chunks
    of 64; PERF.md, PR 31), else :func:`plain_kda`; the choice is counted
    once per traced call.  A length the chunk does not divide is padded
    with tokens that leave the state alone (``g`` 0, ``beta`` 0)."""
    route = kda_route(q, k, v, g, beta, chunk=chunk, sub=sub)
    get_registry().counter(
        KDA_ROUTE_KERNEL if route == "kernel" else KDA_ROUTE_PLAIN
    ).inc()
    if route == "kernel":
        return kernel_kda(q, k, v, g, beta, scale, chunk)
    return plain_kda(q, k, v, g, beta, scale=scale, chunk=chunk, sub=sub)


# --- The KDA mixer's element-wise work as fused passes ----------------------
#
# Everything between the mixer's projections and the chunk-wise core, and
# between the core and the output projection, on the flat ``[B, T, H * D]``
# views the projections write and the kernels above read: a head is a
# block of ``D`` lanes, a per-head reduction is a lane reduction, and no
# ``[B, T, H, D]`` array exists in HBM (on the chip's ``(8, 128)`` tiles
# the reshape between the two views is a relayout, not a bitcast, and XLA
# moved 5.5 times the bytes the work needs: PERF.md, PR 33).  Three
# passes, each forward and backward under a ``custom_vjp`` whose residuals
# are the pass's inputs (the backward makes the activations again in
# VMEM):
#
# - :func:`short_conv_silu`: ``silu(conv(x))``, the causal depthwise
#   convolution of a few taps, and for queries and keys the l2 norm over
#   each head's channels;
# - :func:`kda_decay`: ``g = -exp(A_log) * softplus(f + dt_bias)``;
# - :func:`kda_epilogue`: ``rmsnorm_head(o) * sigmoid(gate)``.
#
# One grid step is ``block`` tokens of a group of heads; the grid is
# (batch, head group, token block), the token blocks innermost so that the
# per-lane sums a backward pass owes the small parameters (the taps,
# ``dt_bias``, ``A_log``, the norm's scale) stay in one resident output
# block ``[k, 8, lanes]`` per batch row, eight sublanes of partial sums
# that are added up outside.  Inside a step a ``fori_loop`` walks the
# rows ``rows`` at a time and, in its body, the group's heads one after
# the other: a head's chain of some dozen element-wise operations lives
# in registers and not in VMEM, and the heads' chains are independent, so
# the scheduler fills one's latencies (the lane reduction, the
# exponential, the root) with the others (one head a loop step ran 1.7
# times slower: PERF.md, PR 33).  The
# convolution's rows of history are the last rows of the block before (a
# second, 16-row view of the same array: a bfloat16 tile; zeros before the
# sequence) and then of the chunk before; its transpose takes the
# cotangent of the rows after, so the backward walks a block's chunks in
# reverse and makes the first rows of the block after again from a third
# view.  A shift by ``s`` rows is a sublane rotation of the chunk with
# its neighbour's eight rows.  Same mathematics, same precision as the
# plain path of ``models/mixers.py`` or higher: everything is float32 in
# VMEM (the plain path rounds the convolution and the SiLU to the
# projections' dtype), the results leave in the dtype the plain path
# gives them (``q``, ``k``, ``v`` and the gated output in the
# projections', ``g`` in float32).  A length the block does not divide:
# the last block overhangs, what a step reads beyond the sequence is
# masked to zero where it could reach a sum or an earlier row (the
# backward), and what it writes there is dropped.

_PASS_BLOCK = 512  # tokens a grid step
_PASS_LANES = 1024  # lanes a grid step (whole heads)
_PASS_ROWS = 64  # rows of an inner step, a head at a time
_HALO = 16  # rows of a neighbouring block a step is handed: a bfloat16 tile
_SUB = 8  # rows of a float32 tile: what a chunk keeps of its neighbour


def _pass_geometry(T, W, D, block):
    """``(block, lanes, rows, token blocks)``: the token block clamped to
    the length in whole halos, the widest group of whole heads within
    ``_PASS_LANES`` that divides the width, the rows of an inner step
    (eight registers an array and head at 128 lanes a head)."""
    block = min(block, -(-T // _HALO) * _HALO)
    heads = max(1, min(_PASS_LANES, W) // D)
    while (W // D) % heads:
        heads -= 1
    rows = _PASS_ROWS if block % _PASS_ROWS == 0 and D <= _LANES else _HALO
    return block, heads * D, rows, pl.cdiv(T, block)


def _pass_call(
    kernel, tiles, per_lane, outs, *, head_dim, block, before=(), after=(),
    sums=0, interpret=False, **static,
):
    """One pass over ``[B, T, W]`` arrays.  The kernel gets, in order: a
    ``[1, block, lanes]`` tile of each of ``tiles``; the ``_HALO`` rows
    before the tile of each of ``before`` and after it of each of
    ``after`` (clamped at the sequence's ends: the kernel knows where it
    is); the ``[k, lanes]`` columns of each ``[k, W]`` of ``per_lane``;
    a tile of each output (dtypes ``outs``); and, with ``sums``, the
    resident ``[1, sums, 8, lanes]`` block of per-lane partial sums
    ``[B, sums, 8, W]`` float32, the last output."""
    B, T, W = tiles[0].shape
    block, lanes, rows, n = _pass_geometry(T, W, head_dim, block)
    per, last = block // _HALO, pl.cdiv(T, _HALO) - 1
    tile = pl.BlockSpec((1, block, lanes), lambda b, h, t: (b, t, h))
    halo = lambda at: pl.BlockSpec((1, _HALO, lanes), lambda b, h, t: (b, at(t), h))
    lead = halo(lambda t: jnp.maximum(t * per - 1, 0))
    trail = halo(lambda t: jnp.minimum((t + 1) * per, last))
    vma = _vma(tiles[0])
    out_shape = [jax.ShapeDtypeStruct((B, T, W), dt, vma=vma) for dt in outs]
    out_specs = [tile for _ in outs]
    if sums:
        out_shape.append(jax.ShapeDtypeStruct((B, sums, _SUB, W), _F32, vma=vma))
        out_specs.append(
            pl.BlockSpec((1, sums, _SUB, lanes), lambda b, h, t: (b, 0, 0, h))
        )
    call = pl.pallas_call(
        functools.partial(kernel, length=T, head_dim=head_dim, rows=rows, **static),
        grid=(B, W // lanes, n),
        in_specs=[tile for _ in tiles] + [lead for _ in before] + [trail for _ in after]
        + [pl.BlockSpec((p.shape[0], lanes), lambda b, h, t: (0, h)) for p in per_lane],
        out_specs=out_specs,
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_KERNEL_VMEM_BYTES,
        ),
        interpret=interpret,
    )
    with jax.named_scope(KDA_PASS_SCOPE):
        return call(*tiles, *before, *after, *per_lane)


def _heads_of(ref, D):
    """The lane slices of the heads in a tile."""
    return [slice(a * D, (a + 1) * D) for a in range(ref.shape[-1] // D)]


def _per_lane(ref, j, lanes, rows):
    """Row ``j`` of a ``[k, lanes]`` tile of per-lane numbers, spread over
    ``rows`` rows."""
    return jnp.broadcast_to(ref[j:j + 1, lanes].astype(_F32), (rows, lanes.stop - lanes.start))


def _live(x, first, length):
    """``x`` ``[n, D]`` with the rows at positions ``first + row >=
    length`` (beyond the sequence: whatever the overhanging block read)
    set to zero."""
    row = lax.broadcasted_iota(jnp.int32, x.shape, 0)
    return jnp.where(first + row < length, x, 0.0)


def _rows_to_tile(x):
    """``[n, D]`` -> ``[8, D]``: the sum of its 8-row slabs (register
    adds; the last eight rows are added up outside)."""
    return sum(x[i:i + _SUB] for i in range(0, x.shape[0], _SUB))


def _rows_at(both, start, n):
    """Rows ``start .. start + n`` of ``both`` (``n`` whole tiles, ``start``
    any row): where that is not a tile's edge, a sublane rotation."""
    if start % _SUB == 0:
        return both[start:start + n]
    return pltpu.roll(both, both.shape[0] - start, 0)[:n]


def _lead_rows(lead_ref, ln):
    """The eight rows before a token block, float32: the end of the
    ``_HALO`` rows it is handed, zeros before the sequence."""
    rows = lead_ref[0, :, ln].astype(_F32)[_HALO - _SUB:]
    return jnp.where(pl.program_id(2) > 0, rows, 0.0)


def _add_sums(ref, heads, sums):
    """Adds each head's tiles of partial sums ``[8, D]`` to the resident
    block ``[1, k, 8, lanes]``, which a batch row's first token block
    starts from zero."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ref[...] = jnp.zeros(ref.shape, _F32)

    for ln, tiles in zip(heads, sums):
        for j, tile in enumerate(tiles):
            ref[0, j, :, ln] += tile


def _softplus_and_sigmoid(z):
    """Both from one exponential (the passes are bound by the
    transcendental unit before they are by HBM): ``e = exp(-|z|)``,
    ``softplus = max(z, 0) + log1p(e)``, ``sigmoid = (z >= 0 ? 1 : e) /
    (1 + e)``."""
    e = jnp.exp(-jnp.abs(z))
    return jnp.maximum(z, 0.0) + jnp.log1p(e), jnp.where(z >= 0.0, 1.0, e) / (1.0 + e)


def _conv_silu(prev, cur, taps, normalize, eps):
    """A chunk's forward pass: ``c_t = sum_j w_j x_{t-(K-1)+j}``, ``s =
    silu(c)`` and, normalized, ``y = s (sum_head s^2 + eps)^-1/2``."""
    K, n = len(taps), cur.shape[0]
    both = jnp.concatenate([prev, cur], axis=0)
    xs = [_rows_at(both, _SUB - (K - 1 - j), n) for j in range(K)]
    c = sum(w * x for w, x in zip(taps, xs))
    sig = jax.nn.sigmoid(c)
    y = c * sig
    r = None
    if normalize:
        r = lax.rsqrt(jnp.sum(y * y, axis=1, keepdims=True) + eps)
        y = y * r
    return types.SimpleNamespace(xs=xs, c=c, sig=sig, r=r, y=y)


def _conv_silu_cotangent(x, dy):
    """``dc`` of a chunk from its forward terms ``x`` and ``dy``."""
    if x.r is not None:
        dy = x.r * (dy - x.y * jnp.sum(dy * x.y, axis=1, keepdims=True))
    return dy * (x.sig * (1.0 + x.c * (1.0 - x.sig)))


def _conv_fwd_kernel(x_ref, lead_ref, w_ref, y_ref, *, length, head_dim, rows, normalize, eps):
    del length  # what overhangs is written nowhere and reaches no row before it
    block, K = x_ref.shape[1], w_ref.shape[0]
    heads = _heads_of(x_ref, head_dim)
    taps = [[_per_lane(w_ref, j, ln, rows) for j in range(K)] for ln in heads]

    def chunk(i, prev):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        cur = [x_ref[0, at, ln].astype(_F32) for ln in heads]
        for a, ln in enumerate(heads):
            y = _conv_silu(prev[a], cur[a], taps[a], normalize, eps).y
            y_ref[0, at, ln] = y.astype(y_ref.dtype)
        return [x[rows - _SUB:] for x in cur]

    lax.fori_loop(0, block // rows, chunk, [_lead_rows(lead_ref, ln) for ln in heads])


def _conv_bwd_kernel(
    x_ref, dy_ref, lead_ref, x_trail_ref, dy_trail_ref, w_ref, dx_ref, dw_ref,
    *, length, head_dim, rows, normalize, eps,
):
    block, K = x_ref.shape[1], w_ref.shape[0]
    first = pl.program_id(2) * block
    ragged = length % block != 0
    live = (lambda x, at: _live(x, first + at, length)) if ragged else (lambda x, at: x)
    heads = _heads_of(x_ref, head_dim)
    taps = [[_per_lane(w_ref, j, ln, rows) for j in range(K)] for ln in heads]

    lead, dc_after = [_lead_rows(lead_ref, ln) for ln in heads], []
    for ln, w in zip(heads, taps):
        # The cotangent of the first rows of the block after this one
        # (zero beyond the sequence), made again from its inputs.
        tail = live(x_ref[0, block - _HALO:, ln].astype(_F32), block - _HALO)[_HALO - _SUB:]
        x_after = _live(x_trail_ref[0, :, ln].astype(_F32)[:_SUB], first + block, length)
        dy_after = _live(dy_trail_ref[0, :, ln].astype(_F32)[:_SUB], first + block, length)
        after = _conv_silu(tail, x_after, [wj[:_SUB] for wj in w], normalize, eps)
        dc_after.append(_conv_silu_cotangent(after, dy_after))

    def chunk(n, carry):
        dc_after, sums = carry
        i = block // rows - 1 - n
        start = pl.multiple_of(i * rows, rows)
        at = pl.ds(start, rows)
        before = pl.ds(pl.multiple_of(jnp.maximum(start - _HALO, 0), _HALO), _HALO)
        dc_first, new_sums = [], []
        for a, ln in enumerate(heads):
            prev = live(x_ref[0, before, ln].astype(_F32), start - _HALO)[_HALO - _SUB:]
            prev = jnp.where(i > 0, prev, lead[a])
            x = _conv_silu(
                prev, live(x_ref[0, at, ln].astype(_F32), start), taps[a], normalize, eps
            )
            dc = _conv_silu_cotangent(x, live(dy_ref[0, at, ln].astype(_F32), start))
            ahead = jnp.concatenate([dc, dc_after[a]], axis=0)
            dx = sum(
                w * _rows_at(ahead, K - 1 - j, rows) for j, w in enumerate(taps[a])
            )
            dx_ref[0, at, ln] = dx.astype(dx_ref.dtype)
            new_sums.append([sums[a][j] + _rows_to_tile(dc * x.xs[j]) for j in range(K)])
            dc_first.append(dc[:_SUB])
        return dc_first, new_sums

    zero = jnp.zeros((_SUB, head_dim), _F32)
    _, sums = lax.fori_loop(
        0, block // rows, chunk, (dc_after, [[zero] * K for _ in heads])
    )
    _add_sums(dw_ref, heads, sums)


def _decay_fwd_kernel(f_ref, p_ref, g_ref, *, length, head_dim, rows):
    del length
    heads = _heads_of(f_ref, head_dim)
    params = [[_per_lane(p_ref, j, ln, rows) for j in range(2)] for ln in heads]

    def chunk(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for ln, (bias, a) in zip(heads, params):
            z = f_ref[0, at, ln].astype(_F32) + bias
            g_ref[0, at, ln] = a * _softplus_and_sigmoid(z)[0]
        return 0

    lax.fori_loop(0, f_ref.shape[1] // rows, chunk, 0)


def _decay_bwd_kernel(f_ref, dg_ref, p_ref, df_ref, dp_ref, *, length, head_dim, rows):
    t, block = pl.program_id(2), f_ref.shape[1]
    ragged = length % block != 0
    heads = _heads_of(f_ref, head_dim)
    params = [[_per_lane(p_ref, j, ln, rows) for j in range(2)] for ln in heads]

    def chunk(i, sums):
        start = pl.multiple_of(i * rows, rows)
        at = pl.ds(start, rows)
        out = []
        for h, ln in enumerate(heads):
            (bias, a), (s_bias, s_a) = params[h], sums[h]
            z = f_ref[0, at, ln].astype(_F32) + bias
            dg = dg_ref[0, at, ln]
            softplus, sigmoid = _softplus_and_sigmoid(z)
            dz = dg * a * sigmoid
            df_ref[0, at, ln] = dz.astype(df_ref.dtype)
            da = dg * softplus
            if ragged:
                dz, da = (_live(x, t * block + start, length) for x in (dz, da))
            out.append((s_bias + _rows_to_tile(dz), s_a + _rows_to_tile(da)))
        return out

    zero = jnp.zeros((_SUB, head_dim), _F32)
    sums = lax.fori_loop(0, block // rows, chunk, [(zero, zero)] * len(heads))
    _add_sums(dp_ref, heads, sums)


def _gate_terms(o, gate, scale, eps):
    """``n = o (mean_head o^2 + eps)^-1/2 scale`` and ``sigmoid(gate)``."""
    r = lax.rsqrt(jnp.mean(o * o, axis=1, keepdims=True) + eps)
    return r, o * (r * scale), jax.nn.sigmoid(gate)


def _gate_fwd_kernel(o_ref, gate_ref, s_ref, z_ref, *, length, head_dim, rows, eps):
    del length
    heads = _heads_of(o_ref, head_dim)
    scales = [_per_lane(s_ref, 0, ln, rows) for ln in heads]

    def chunk(i, _):
        at = pl.ds(pl.multiple_of(i * rows, rows), rows)
        for ln, scale in zip(heads, scales):
            _, n, sg = _gate_terms(
                o_ref[0, at, ln].astype(_F32), gate_ref[0, at, ln].astype(_F32), scale, eps
            )
            z_ref[0, at, ln] = (n * sg).astype(z_ref.dtype)
        return 0

    lax.fori_loop(0, o_ref.shape[1] // rows, chunk, 0)


def _gate_bwd_kernel(
    o_ref, gate_ref, dz_ref, s_ref, do_ref, dgate_ref, ds_ref,
    *, length, head_dim, rows, eps,
):
    t, block = pl.program_id(2), o_ref.shape[1]
    ragged = length % block != 0
    heads = _heads_of(o_ref, head_dim)
    scales = [_per_lane(s_ref, 0, ln, rows) for ln in heads]

    def chunk(i, sums):
        start = pl.multiple_of(i * rows, rows)
        at = pl.ds(start, rows)
        out = []
        for h, ln in enumerate(heads):
            o = o_ref[0, at, ln].astype(_F32)
            r, n, sg = _gate_terms(o, gate_ref[0, at, ln].astype(_F32), scales[h], eps)
            dz = dz_ref[0, at, ln].astype(_F32)
            dn = dz * sg
            dgate_ref[0, at, ln] = (dn * n * (1.0 - sg)).astype(dgate_ref.dtype)
            m = dn * scales[h]
            do = r * (m - o * (r * r) * jnp.mean(m * o, axis=1, keepdims=True))
            do_ref[0, at, ln] = do.astype(do_ref.dtype)
            ds = dn * o * r
            if ragged:
                ds = _live(ds, t * block + start, length)
            out.append(sums[h] + _rows_to_tile(ds))
        return out

    zero = jnp.zeros((_SUB, head_dim), _F32)
    sums = lax.fori_loop(0, block // rows, chunk, [zero] * len(heads))
    _add_sums(ds_ref, heads, [[total] for total in sums])


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def short_conv_silu(
    x, w, head_dim, normalize=False, eps=1e-6, block=_PASS_BLOCK, interpret=False
):
    """``silu(conv(x))`` in one pass: ``x`` ``[B, T, H * head_dim]``, the
    causal depthwise convolution's taps ``w`` ``[K, H * head_dim]`` (``c_t
    = sum_j w_j x_{t-(K-1)+j}``, zeros before the sequence; ``2 <= K <=
    9``), and with ``normalize`` each head's channels divided by their
    l2 norm (``eps`` inside the root).  Float32 inside, the result in the
    dtype of ``x``; the backward is one pass too and makes ``dx`` and
    ``dw``."""
    return _conv_fwd(x, w, head_dim, normalize, eps, block, interpret)[0]


@functools.partial(jax.jit, static_argnums=(2, 3, 4, 5, 6))
def _conv_fwd(x, w, head_dim, normalize, eps, block, interpret):
    (y,) = _pass_call(
        _conv_fwd_kernel, [x], [w], [x.dtype], head_dim=head_dim, block=block,
        before=[x], interpret=interpret, normalize=normalize, eps=eps,
    )
    return y, (x, w)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3, 4))
def _conv_bwd(head_dim, normalize, eps, block, interpret, res, dy):
    x, w = res
    dx, dw = _pass_call(
        _conv_bwd_kernel, [x, dy], [w], [x.dtype], head_dim=head_dim,
        block=block, before=[x], after=[x, dy], sums=w.shape[0],
        interpret=interpret, normalize=normalize, eps=eps,
    )
    return dx, jnp.sum(dw, axis=(0, 2)).astype(w.dtype)


short_conv_silu.defvjp(_conv_fwd, _conv_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _decay(f, p, head_dim, block, interpret):
    """``p[1] * softplus(f + p[0])`` float32, ``p`` ``[2, W]`` per lane."""
    return _decay_fwd(f, p, head_dim, block, interpret)[0]


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _decay_fwd(f, p, head_dim, block, interpret):
    (g,) = _pass_call(
        _decay_fwd_kernel, [f], [p], [_F32], head_dim=head_dim, block=block,
        interpret=interpret,
    )
    return g, (f, p)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _decay_bwd(head_dim, block, interpret, res, dg):
    f, p = res
    df, dp = _pass_call(
        _decay_bwd_kernel, [f, dg], [p], [f.dtype], head_dim=head_dim,
        block=block, sums=2, interpret=interpret,
    )
    return df, jnp.sum(dp, axis=(0, 2))


_decay.defvjp(_decay_fwd, _decay_bwd)


def kda_decay(f, dt_bias, a_log, *, block=_PASS_BLOCK, interpret=False):
    """KDA's log decay in one pass: ``g = -exp(A_log) * softplus(f +
    dt_bias)`` float32 ``[B, T, H * D]`` from ``f`` of that shape (any
    float dtype), ``dt_bias`` ``[H * D]`` and ``A_log`` ``[H]``."""
    D = f.shape[-1] // a_log.shape[0]
    p = jnp.stack([dt_bias.astype(_F32), jnp.repeat(-jnp.exp(a_log.astype(_F32)), D)])
    return _decay(f, p, D, block, interpret)


def kda_prologue(
    xq, xk, xv, f, conv_q, conv_k, conv_v, dt_bias, a_log, *,
    eps: float = 1e-6, block: int = _PASS_BLOCK, interpret: bool = False,
):
    """What the KDA mixer does between its projections and the chunk-wise
    core, one pass an array (section comment above): ``q, k =
    l2norm(silu(conv(.)))`` and ``v = silu(conv(.))`` in the projections'
    dtype, ``g`` float32, all on the flat ``[B, T, H * D]`` views."""
    D = xq.shape[-1] // a_log.shape[0]
    q = short_conv_silu(xq, conv_q, D, True, eps, block, interpret)
    k = short_conv_silu(xk, conv_k, D, True, eps, block, interpret)
    v = short_conv_silu(xv, conv_v, D, False, eps, block, interpret)
    return q, k, v, kda_decay(f, dt_bias, a_log, block=block, interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _gated_norm(o, gate, scale, head_dim, eps, block, interpret):
    return _gated_norm_fwd(o, gate, scale, head_dim, eps, block, interpret)[0]


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _gated_norm_fwd(o, gate, scale, head_dim, eps, block, interpret):
    (z,) = _pass_call(
        _gate_fwd_kernel, [o, gate], [scale], [o.dtype], head_dim=head_dim,
        block=block, interpret=interpret, eps=eps,
    )
    return z, (o, gate, scale)


@functools.partial(jax.jit, static_argnums=(0, 1, 2, 3))
def _gated_norm_bwd(head_dim, eps, block, interpret, res, dz):
    o, gate, scale = res
    do, dgate, ds = _pass_call(
        _gate_bwd_kernel, [o, gate, dz], [scale], [o.dtype, gate.dtype],
        head_dim=head_dim, block=block, sums=1, interpret=interpret, eps=eps,
    )
    return do, dgate, jnp.sum(ds, axis=(0, 2))


_gated_norm.defvjp(_gated_norm_fwd, _gated_norm_bwd)


def kda_epilogue(
    o, gate, scale, *, eps: float, block: int = _PASS_BLOCK,
    interpret: bool = False,
):
    """What the KDA mixer does between the core and its output
    projection, in one pass: ``rmsnorm_head(o) * sigmoid(gate)`` on the
    flat views, ``scale`` ``[D]`` the norm's (float32 inside, the result
    in the dtype of ``o``)."""
    D = scale.shape[0]
    lanes = jnp.tile(scale.astype(_F32), o.shape[-1] // D)[None]
    return _gated_norm(o, gate, lanes, D, eps, block, interpret)


def kda_mixer_route(xq, xk, xv, *, heads: int, taps: int) -> str:
    """What ``models/mixers.py::KDAMixer`` runs around its core for these
    projections ``[B, T, heads * D]``: ``"fused"`` (the passes above and
    the core's kernels on the flat views) where the core itself takes its
    kernel route (a TPU, a Mosaic kernel can lower, heads of whole lane
    blocks, one dtype) and the taps fit the halo; else ``"plain"``."""
    B, T, W = xq.shape
    like = lambda d, dtype: jax.ShapeDtypeStruct((B, T, heads, d), dtype)
    D = W // heads
    if (
        xq.shape == xk.shape == xv.shape
        and xq.dtype == xk.dtype == xv.dtype
        and W == heads * D
        and 2 <= taps <= _SUB + 1
        and kda_route(
            like(D, xq.dtype), like(D, xk.dtype), like(D, xv.dtype),
            like(D, _F32), jax.ShapeDtypeStruct((B, T, heads), _F32),
            chunk=_KERNEL_CHUNK, sub=_KERNEL_SUB,
        ) == "kernel"
    ):
        return "fused"
    return "plain"


@jax.named_scope(KDA_CORE_SCOPE)
def chunked_kda_flat(
    q, k, v, g, beta, *, scale: Optional[float] = None, interpret: bool = False,
    keep=lambda results: None,
):
    """:func:`chunked_kda` for a caller on the fused route
    (:func:`kda_mixer_route` said so: the kernels take the call), on the
    flat views ``[B, T, H * d]`` and ``beta`` ``[B, T, H]``, under the
    same scope and counted as the same route.  ``keep`` is shown the
    shapes of :func:`kernel_kda_results` and says under which
    ``checkpoint_name`` the forward rule hands them on, or None (the
    default) for as they are: a caller whose ``jax.checkpoint`` saves that
    name (``models/remat.py::kept_core``) holds the forward kernel once in
    its differentiated program and not twice.  It is asked here, where the
    caller is being traced: the rule is traced when the call is
    differentiated, after the caller's function has returned."""
    get_registry().counter(KDA_ROUTE_KERNEL).inc()
    kept_as = keep(kernel_kda_results(q, v, beta))
    return kernel_kda_flat(q, k, v, g, beta, scale, _KERNEL_CHUNK, interpret, kept_as)


# --- One decay a head and step: the gated delta rule -----------------------


@jax.named_scope(GDN_CORE_SCOPE)
def chunked_gdn(
    q, k, v, g, beta, *, scale: Optional[float] = None, chunk: int = 64,
    sub: int = 16,
):
    """The gated delta rule (Yang, Kautz, Hatamizadeh 2024,
    arXiv:2412.06464) chunk-wise: :func:`recurrent_kda` with ``g`` ``[B,
    T, H]`` spread over the key channels; ``q``, ``k`` ``[B, T, H, dk]``,
    ``v`` ``[B, T, H, dv]`` (any widths), ``beta`` ``[B, T, H]`` in (0, 2)
    (at ``b > 1`` the transition ``I - b k k^T`` has a negative
    eigenvalue); the result in the dtype of ``v``.  One route,
    :func:`plain_gdn`, counted once per traced call
    (``gdn/route_plain``).  No kernel for a scalar decay is written: the
    per-channel kernels fed padded inputs (keys to 128 lanes, values to
    256, ``g`` spread over the key channels) run 4.9 ms forward and 9.9
    with the backward pass at Olmo-Hybrid's ``[1, 8192, 15, 96 | 192]`` on
    a v5e against :func:`plain_gdn`'s 6.3 and 15.4 (PERF.md, PR 32), and
    still walk a pair loop this decay does not need."""
    get_registry().counter(GDN_ROUTE_PLAIN).inc()
    return plain_gdn(q, k, v, g, beta, scale=scale, chunk=chunk, sub=sub)
