"""The state-space dual (SSD) scan of Mamba-2, chunk-wise.

A Mamba-2 layer (Dao and Gu 2024, "Transformers are SSMs",
arXiv:2405.21060) keeps, per head of ``P`` channels, a state ``S`` ``[N,
P]`` that every token decays by one number, writes and reads::

    S_t = a_t S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t + D x_t

with ``a_t = exp(-exp(A_log) dt_t)`` in (0, 1), ``dt_t > 0`` one number a
head and token, ``D`` one number a head, and ``B_t``, ``C_t`` ``[N]``
**one vector for all the heads** (one group).  In the words of
:mod:`.linear_attention` it is linear attention with queries ``C``, keys
``B``, values ``dt x``, one log decay ``g = -exp(A_log) dt`` a head and
step, scale 1 and **no delta rule**: nothing is read back before a write,
so there is no triangular system and ``_chunkwise`` there does not serve
it.  :func:`recurrent_ssd` is the recurrence token by token (a
``lax.scan`` over time, float32; the oracle of the tests);
:func:`chunked_ssd` is what the model runs.

The chunk-wise form.  With ``G_t = sum_{r<=t} g_r`` inside a chunk of
``L`` tokens that starts from ``S_0``, and ``v_t = dt_t x_t``::

    y_t = e^{G_t} S_0^T C_t + sum_{s<=t} (C_t . B_s) e^{G_t - G_s} v_s + D x_t
    S_L = e^{G_L} S_0 + sum_s e^{G_L - G_s} B_s v_s^T

``C B^T`` is **one** ``[L, L]`` product a chunk, whatever the number of
heads (the delta rules make one a head); a head's own part is the mask of
``e^{G_t - G_s}`` (``s <= t``) laid over it.  The two products with the
state take all the heads at once, ``[L, N] x [N, H P]`` and ``[N, L] x [L,
H P]``.  Only the states need the chunks in order: ``S_{c+1} = e^{G_L} S_c
+ U_c`` is a ``lax.scan`` over the chunks of one multiply-add on ``[H, N,
P]``.

Decays near 0.  ``e^{G_t - G_s}`` cannot be split as ``e^{G_t} e^{-G_s}``:
with ``dt`` large ``e^{-G_s}`` is beyond float32 while the quotient is an
ordinary number.  Every exponent taken here is of a difference ``G_later
- G_earlier <= 0`` (as in :func:`.linear_attention.plain_gdn`, whose mask
this is).

Precision.  ``dt``, ``g``, ``G``, every exponential, the states and all
accumulations are float32.  The matrix products take their operands in
the dtype of ``x`` (bfloat16 in a bf16 model, float32 otherwise) and
accumulate in float32.

One route, :func:`plain_ssd`, in ``jax.numpy`` on every backend, counted
once per traced call (``ssd/route_plain``); whoever writes the kernel
adds the route function and ``ssd/route_kernel``, as ``kda_route`` has.
The chunk-wise body is bound under ``jax.jit``, so a stack's identical
layers trace and lower it once.  The backward pass is autodiff through
all of it; what it keeps is per chunk (the state at each chunk's start),
never a state per token.

A module of its own beside :mod:`.linear_attention`: it shares that
module's chunking (``_in_chunks``) and the scalar decay's mask, and
nothing of the delta rule (``T``, ``U``, the pair loop, the kernels).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from distributed_tensorflow_models_tpu.ops.linear_attention import _in_chunks
from distributed_tensorflow_models_tpu.telemetry.registry import (
    SSD_ROUTE_PLAIN,
    get_registry,
)

# ``jax.named_scope`` of the chunk-wise scan, forward and backward: a path
# element of every instruction's ``op_name`` in the compiled step (PERF.md
# section 3).  The mixer's projections, its convolution, the gate and the
# norm stay outside it (``models/mixers.py::SSM_SCOPE`` holds them all).
SSD_CORE_SCOPE = "ssd_core"

_F32 = jnp.float32
_HI = lax.Precision.HIGHEST


def recurrent_ssd(x, dt, a_log, b, c, d_skip=None):
    """The recurrence token by token, float32.  ``x`` ``[B, T, H, P]``,
    ``dt`` ``[B, T, H]`` (> 0, after the softplus), ``a_log`` ``[H]``,
    ``b``, ``c`` ``[B, T, N]`` (one group), ``d_skip`` ``[H]`` or None;
    returns ``[B, T, H, P]``."""
    B, T, H, P = x.shape
    x, dt, b, c = (y.astype(_F32) for y in (x, dt, b, c))
    decay = jnp.exp(-jnp.exp(a_log.astype(_F32)) * dt)

    def step(S, at):
        x_t, dt_t, a_t, b_t, c_t = at
        write = jnp.einsum("bn,bhp->bhnp", b_t, dt_t[..., None] * x_t, precision=_HI)
        S = a_t[..., None, None] * S + write
        return S, jnp.einsum("bn,bhnp->bhp", c_t, S, precision=_HI)

    time_first = lambda y: jnp.moveaxis(y, 1, 0)
    S0 = jnp.zeros((B, H, b.shape[-1], P), _F32)
    _, out = lax.scan(step, S0, tuple(map(time_first, (x, dt, decay, b, c))))
    out = jnp.moveaxis(out, 0, 1)
    if d_skip is not None:
        out = out + d_skip.astype(_F32)[:, None] * x
    return out


@functools.partial(jax.jit, static_argnames=("chunk",))
@jax.named_scope(SSD_CORE_SCOPE)
def plain_ssd(x, dt, a_log, b, c, d_skip=None, *, chunk: int = 256):
    """The chunk-wise form in plain ``jax.numpy`` (module docstring); same
    arguments as :func:`recurrent_ssd`, the result in the dtype of ``x``.
    A length the chunk does not divide is padded with tokens that leave
    the state alone (``dt`` 0: no decay, nothing written).  Jitted (and
    so bound under its scope again inside: XLA names an instruction after
    the innermost element of its ``op_name``)."""
    B, T, H, P = x.shape
    dtype = x.dtype
    dt = dt.astype(_F32)
    g = -jnp.exp(a_log.astype(_F32)) * dt  # log decay, <= 0
    v = (dt[..., None] * x.astype(_F32)).astype(dtype)
    v, g = _in_chunks(v, chunk), _in_chunks(g, chunk)  # [n, B, H, L, P], [n, B, H, L]
    # One group: B and C as one "head", [n, B, L, N].
    bc, cc = (_in_chunks(y[:, :, None].astype(dtype), chunk)[:, :, 0] for y in (b, c))
    G = jnp.cumsum(g, axis=-1)
    G_end = G[..., -1:]

    # Inside a chunk: one C B^T for all the heads under each head's mask.
    cb = jnp.einsum("nbtk,nbsk->nbts", cc, bc, preferred_element_type=_F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    mask = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    scores = (cb[:, :, None] * mask).astype(dtype)  # [n, B, H, L, L]
    y = jnp.einsum("nbhts,nbhsp->nbhtp", scores, v, preferred_element_type=_F32)

    # What a chunk adds to the state, all the heads at once.
    v_end = (jnp.exp(G_end - G)[..., None] * v.astype(_F32)).astype(dtype)
    wrote = jnp.einsum("nbsk,nbhsp->nbhkp", bc, v_end, preferred_element_type=_F32)

    def step(S, at):
        decay_c, wrote_c = at
        return decay_c * S + wrote_c, S.astype(dtype)

    S0 = jnp.zeros((B, H, b.shape[-1], P), _F32)
    _, S_at = lax.scan(step, S0, (jnp.exp(G_end)[..., None], wrote))
    carried = jnp.einsum("nbtk,nbhkp->nbhtp", cc, S_at, preferred_element_type=_F32)
    y = y + jnp.exp(G)[..., None] * carried
    # [n, B, H, L, P] -> [B, T, H, P]
    y = jnp.moveaxis(jnp.moveaxis(y, 2, 3), 0, 1).reshape(B, -1, H, P)[:, :T]
    if d_skip is not None:
        y = y + d_skip.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(dtype)


def chunked_ssd(x, dt, a_log, b, c, d_skip=None, *, chunk: int = 256):
    """:func:`recurrent_ssd` computed chunk-wise (module docstring); same
    arguments, the result in the dtype of ``x``.  One route,
    :func:`plain_ssd`, counted once per traced call (``ssd/route_plain``).
    ``chunk`` is the program's way to compute the recurrence and no part
    of the model (``mamba_chunk_size`` 256 is the published kernel's
    block).  What it trades is memory: a float32 copy of the per-chunk
    states is ``T / chunk x H x N x P`` (268 MB at 8,192 tokens of
    Granite's 64 x 128 x 64 in chunks of 64, 67 MB at 256), a head's masks
    ``T x chunk`` (PERF.md, PR 38)."""
    if not (
        x.shape[:3] == dt.shape and b.shape == c.shape
        and b.shape[:2] == x.shape[:2] and a_log.shape == x.shape[2:3]
    ):
        raise ValueError(
            f"chunked_ssd wants x [B, T, H, P], dt [B, T, H], a_log [H] and "
            f"b, c [B, T, N] (one group); got {x.shape}, {dt.shape}, "
            f"{a_log.shape}, {b.shape}, {c.shape}"
        )
    get_registry().counter(SSD_ROUTE_PLAIN).inc()
    return plain_ssd(x, dt, a_log, b, c, d_skip, chunk=chunk)
