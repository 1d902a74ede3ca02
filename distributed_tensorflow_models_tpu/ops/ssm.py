"""The state-space dual (SSD) scan of Mamba-2, chunk-wise.

A Mamba-2 layer (Dao and Gu 2024, "Transformers are SSMs",
arXiv:2405.21060) keeps, per head of ``P`` channels, a state ``S`` ``[N,
P]`` that every token decays by one number, writes and reads::

    S_t = a_t S_{t-1} + dt_t B_t x_t^T
    y_t = S_t^T C_t + D x_t

with ``a_t = exp(-exp(A_log) dt_t)`` in (0, 1), ``dt_t > 0`` one number a
head and token, ``D`` one number a head, and ``B_t``, ``C_t`` ``[N]``
**one vector a group of heads**: with ``G`` groups over ``H`` heads, head
``h`` reads group ``h // (H / G)`` (Granite 4.0-H has one group for its 64
heads, Nemotron 3 Nano eight).  In the words of
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

``C B^T`` is **one** ``[L, L]`` product a chunk and group, whatever the
number of heads in the group (the delta rules make one a head); a head's
own part is the mask of ``e^{G_t - G_s}`` (``s <= t``) laid over its
group's.  The two products with the state take a group's heads at once,
``[L, N] x [N, (H / G) P]`` and ``[N, L] x [L, (H / G) P]``.  Only the states need the chunks in order: ``S_{c+1} = e^{G_L} S_c
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

Two routes, chosen by :func:`ssd_route` from what a call shows (backend,
shapes, dtype) and counted once per traced call (``ssd/route_kernel``,
``ssd/route_plain``), as ``kda_route`` does it.  On a TPU, for heads that
fill whole lane tiles, :func:`kernel_ssd`: Pallas (Mosaic) kernels, forward
and backward under one ``custom_vjp``, that keep ``C B^T``, every head's
mask and the carried state in VMEM (the section comment above them).
Everywhere else :func:`plain_ssd`, ``jax.numpy``, whose backward pass is
autodiff through all of it.  Both are bound under ``jax.jit``, so a
stack's identical layers trace and lower one body, and both keep for the
backward pass what is per chunk (the state at each chunk's start), never
a state per token.

A module of its own beside :mod:`.linear_attention`: it shares that
module's chunking (``_in_chunks``), the scalar decay's mask and the
kernels' product helper (``_mm``), and nothing of the delta rule (``T``,
``U``, the pair loop, its kernels).
"""

from __future__ import annotations

import functools
import types

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_tensorflow_models_tpu.ops.attention import (
    _LANES,
    _vma,
    mosaic_can_lower,
)
from distributed_tensorflow_models_tpu.ops.linear_attention import (
    _NT,
    _TN,
    _in_chunks,
    _mm,
    _padded,
)
from distributed_tensorflow_models_tpu.telemetry.registry import (
    SSD_ROUTE_KERNEL,
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


def _by_group(b):
    """``B`` or ``C`` as ``[B, T, G, N]``: ``[B, T, N]`` is one group (a
    reshape, whose transpose is one too)."""
    return b.reshape(*b.shape[:2], 1, b.shape[2]) if b.ndim == 3 else b


def _groups(b) -> int:
    return 1 if b.ndim == 3 else b.shape[2]


def recurrent_ssd(x, dt, a_log, b, c, d_skip=None):
    """The recurrence token by token, float32.  ``x`` ``[B, T, H, P]``,
    ``dt`` ``[B, T, H]`` (> 0, after the softplus), ``a_log`` ``[H]``,
    ``b``, ``c`` ``[B, T, G, N]`` (``[B, T, N]``: one group), ``d_skip``
    ``[H]`` or None; returns ``[B, T, H, P]``."""
    B, T, H, P = x.shape
    x, dt, b, c = (y.astype(_F32) for y in (x, dt, _by_group(b), _by_group(c)))
    b, c = (jnp.repeat(y, H // y.shape[2], axis=2) for y in (b, c))  # a head its group's
    decay = jnp.exp(-jnp.exp(a_log.astype(_F32)) * dt)

    def step(S, at):
        x_t, dt_t, a_t, b_t, c_t = at
        write = jnp.einsum("bhn,bhp->bhnp", b_t, dt_t[..., None] * x_t, precision=_HI)
        S = a_t[..., None, None] * S + write
        return S, jnp.einsum("bhn,bhnp->bhp", c_t, S, precision=_HI)

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
    # A group's B and C as one "head", [n, B, G, L, N].
    bc, cc = (_in_chunks(_by_group(y).astype(dtype), chunk) for y in (b, c))
    groups, N = bc.shape[2], bc.shape[-1]
    # [n, B, H, ...] <-> [n, B, G, H / G, ...]: a group's heads side by side.
    grouped = lambda y: y.reshape(y.shape[:2] + (groups, H // groups) + y.shape[3:])
    per_head = lambda y: y.reshape(y.shape[:2] + (H,) + y.shape[4:])
    G = jnp.cumsum(g, axis=-1)
    G_end = G[..., -1:]

    # Inside a chunk: one C B^T a group under each of its heads' masks.
    cb = jnp.einsum("nbgtk,nbgsk->nbgts", cc, bc, preferred_element_type=_F32)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    mask = jnp.exp(jnp.where(lower, G[..., :, None] - G[..., None, :], -jnp.inf))
    scores = per_head(cb[:, :, :, None] * grouped(mask)).astype(dtype)  # [n, B, H, L, L]
    y = jnp.einsum("nbhts,nbhsp->nbhtp", scores, v, preferred_element_type=_F32)

    # What a chunk adds to the state, a group's heads at once.
    v_end = (jnp.exp(G_end - G)[..., None] * v.astype(_F32)).astype(dtype)
    wrote = per_head(
        jnp.einsum("nbgsk,nbgjsp->nbgjkp", bc, grouped(v_end), preferred_element_type=_F32)
    )

    def step(S, at):
        decay_c, wrote_c = at
        return decay_c * S + wrote_c, S.astype(dtype)

    S0 = jnp.zeros((B, H, N, P), _F32)
    _, S_at = lax.scan(step, S0, (jnp.exp(G_end)[..., None], wrote))
    carried = per_head(
        jnp.einsum("nbgtk,nbgjkp->nbgjtp", cc, grouped(S_at), preferred_element_type=_F32)
    )
    y = y + jnp.exp(G)[..., None] * carried
    # [n, B, H, L, P] -> [B, T, H, P]
    y = jnp.moveaxis(jnp.moveaxis(y, 2, 3), 0, 1).reshape(B, -1, H, P)[:, :T]
    if d_skip is not None:
        y = y + d_skip.astype(_F32)[:, None] * x.astype(_F32)
    return y.astype(dtype)


# --- The chunk-wise form as Pallas (Mosaic) kernels -------------------------
#
# What :func:`plain_ssd` computes, with nothing a token or a head wide ever
# in HBM but the inputs, the output and the state at each chunk's start:
# ``C B^T``, every head's mask, the masked scores and the carried state
# live in VMEM.  Both kernels read the flat ``[B, T, H * P]`` view that the
# mixer's projection writes (a head is ``P`` lanes; ``[B, T, H, P]`` on the
# chip's ``(8, 128)`` tiles would be a relayout on either side: PERF.md, PR
# 33) and walk a grid (batch, chunk, set of heads), the sets innermost.  A
# set is the heads of one grid step: sixteen at most, and never of two of
# ``B`` and ``C``'s groups (Granite's one group is four sets of sixteen
# heads, each of Nemotron's eight groups one set of eight).  A group's
# ``B`` and ``C`` tiles stay resident while its sets go by, and its first
# set's step makes ``C B^T`` once for all of them.  A step
# takes the set's decays ``[L, heads]`` (``dt`` is handed over set by
# set, so a head's column is a static lane), makes ``G`` as a column and
# as a row a head by two small products with a matrix of ones (float32
# exactly: the three bfloat16 pieces of ``g``), and then, 128 lanes of
# ``x`` at a time: the read of the carried state for those lanes, each
# head's mask and scores and their product with the head's lanes of ``v``
# (128 rows of the ``[L, L]`` mask at a time, up to their diagonal: what
# lies above it is never made; the other heads' lanes of ``v`` zeroed: the
# 128-wide output costs the MXU what a 64-wide one would), the ``D`` skip,
# and the lanes' part of the state's update.  The state of every head is scratch ``[sets, N,
# lanes]`` float32 (2 MB at 64 heads of 64 over a state of 128), carried from chunk to chunk.
#
# The backward walks the chunks in reverse with the state's cotangent in
# that scratch and makes ``C B^T``, ``G`` and the masks again.  With ``W =
# C B^T . M`` a head's scores, ``dW = dy v^T``: ``dv = W^T dy``, the
# cotangent of a group's ``C B^T`` is the sum over its heads of ``dW . M``
# (a value of the step, then scratch across the group's sets; ``dB`` and
# ``dC`` take it through two products when the group's last set is done,
# and the state's part of them sums over the lanes inside the products:
# both sum over a group's heads and no further), and ``dG_t`` is the
# row sum less the column sum of ``dW . W`` plus what the three factors
# ``e^{G_t}``, ``e^{G_L - G_s}`` and ``e^{G_L}`` owe; ``dg`` is the reverse
# running sum of ``dG`` inside the chunk, again by products with ones (the
# column sums arrive as rows and go through the transposed product), and
# ``ddt = -e^{A_log} dg + x . dv``.  The sums a step owes ``A_log`` and
# ``D`` leave as one small row a step and are added up outside.  No
# exponent taken is positive here either; precision is the plain route's
# (the cotangents ``dy`` and ``dS`` enter a product in the dtype of ``x``,
# as XLA's default precision has them on the plain route).

_KERNEL_CHUNKS = (256, 512)
_KERNEL_HEAD_DIMS = (64, 128)
_KERNEL_HEADS = (16, 8, 4, 2)  # heads a grid step, the most that divides
_KERNEL_VMEM_BYTES = 64 * 1024 * 1024  # of v5e's 128 MiB


def _kernel_heads(per_group: int, P: int):
    """Heads a grid step (a set) for groups of ``per_group`` heads: whole
    128-lane tiles of ``x`` inside one group, or None."""
    return next(
        (m for m in _KERNEL_HEADS if per_group % m == 0 and (m * P) % _LANES == 0),
        None,
    )


def kernel_admissible(x, b, c, *, chunk: int) -> bool:
    """Whether the kernels take this call: heads of 64 or 128 channels
    that fill whole 128-lane tiles side by side within a group of ``B``
    and ``C``, a state of whole lane tiles, a chunk of 256 or 512, one
    dtype for ``x``, ``B`` and ``C``.  Visible at trace time; the backend
    is the caller's question."""
    H, P = x.shape[2:]
    groups = _groups(b)
    return (
        x.dtype == b.dtype == c.dtype
        and P in _KERNEL_HEAD_DIMS
        and H % groups == 0
        and _kernel_heads(H // groups, P) is not None
        and b.shape[-1] % _LANES == 0
        and chunk in _KERNEL_CHUNKS
    )


def _pieces(x):
    """The three bfloat16 pieces of float32 ``x``: they add up to it
    exactly, so a product of theirs with a matrix of zeros and ones,
    summed in float32, is the float32 product at half the passes."""
    out = []
    for _ in range(3):
        out.append(x.astype(jnp.bfloat16))
        x = x - out[-1].astype(_F32)
    return out


def _step_terms(dt_ref, a_ref):
    """What the heads of a grid step (a set) share: ``dt`` and ``g`` ``[L,
    heads]``, the running sum ``G`` as columns ``[L, heads]`` and as rows
    ``[heads, L]``, the two triangles of ones."""
    dt = dt_ref[0, 0].astype(_F32)
    L = dt.shape[0]
    rate = jnp.exp(a_ref[0].astype(_F32))  # [1, heads]
    g = -rate * dt
    row = lax.broadcasted_iota(jnp.int32, (L, L), 0)
    col = lax.broadcasted_iota(jnp.int32, (L, L), 1)
    lower, upper = col <= row, row <= col
    ones_l, ones_u = lower.astype(jnp.bfloat16), upper.astype(jnp.bfloat16)
    parts = _pieces(g)
    G = sum(_mm(ones_l, p) for p in parts)
    Gt = sum(_mm(p, ones_u, _TN) for p in parts)
    return types.SimpleNamespace(
        dt=dt, g=g, rate=rate, G=G, Gt=Gt, lower=lower, ones_u=ones_u
    )


def _spread(cols, first: int, P: int):
    """Columns ``first`` on of ``cols`` ``[L, heads]``, each over its
    head's ``P`` lanes of a ``[L, 128]`` tile."""
    L = cols.shape[0]
    k = _LANES // P
    out = jnp.broadcast_to(cols[:, first + k - 1:first + k], (L, _LANES))
    lane = lax.broadcasted_iota(jnp.int32, (L, _LANES), 1)
    for i in range(k - 2, -1, -1):
        out = jnp.where(lane < (i + 1) * P, cols[:, first + i:first + i + 1], out)
    return out


def _own_lanes(y, i: int, P: int):
    """``y`` ``[L, 128]`` with every lane but head ``i``'s of the tile
    zeroed."""
    if P == _LANES:
        return y
    lane = lax.broadcasted_iota(jnp.int32, y.shape, 1)
    return jnp.where((lane >= i * P) & (lane < (i + 1) * P), y, jnp.zeros_like(y))


def _tile_terms(x_ref, s, j: int, P: int):
    """Tile ``j`` (128 lanes) of a step's ``x``: ``v = dt x`` rounded, and
    the three decays a token of each head's lanes."""
    dtype = x_ref.dtype
    lanes = slice(j * _LANES, (j + 1) * _LANES)
    first = j * (_LANES // P)
    x32 = x_ref[0, :, lanes].astype(_F32)
    dtx = _spread(s.dt, first, P)
    Gx = _spread(s.G, first, P)
    v = (dtx * x32).astype(dtype)
    G_end = Gx[Gx.shape[0] - 1:, :]
    to_end = jnp.exp(G_end - Gx)
    v_end32 = to_end * v.astype(_F32)
    return types.SimpleNamespace(
        lanes=lanes, first=first, x32=x32, dtx=dtx, v=v, from_start=jnp.exp(Gx),
        decay=jnp.exp(G_end), to_end=to_end, v_end32=v_end32,
        v_end=v_end32.astype(dtype),
    )


def _row_blocks(L: int):
    """``(rows, cols)`` of the mask's blocks of 128 rows: what is not
    above the diagonal, so that a quarter of a chunk of 256 (three eighths
    at 512) is never made."""
    return [
        (slice(r, r + _LANES), slice(0, r + _LANES)) for r in range(0, L, _LANES)
    ]


def _head_mask(s, a: int, rows, cols):
    """``e^{G_t - G_s}`` for ``s <= t``, 0 above: rows ``rows`` of head
    ``a`` of the step, over the columns ``cols`` up to their diagonal."""
    return jnp.exp(
        jnp.where(
            s.lower[rows, cols], s.G[rows, a:a + 1] - s.Gt[a:a + 1, cols], -jnp.inf
        )
    )


def _ssd_fwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, o_ref, *rest, head_dim, group_sets,
    keep_states,
):
    """Grid (B, chunks, sets of heads), ``group_sets`` sets a group of
    ``B`` and ``C``.  ``S_scr`` ``[sets, N, lanes]`` holds every head's
    state, ``cb_scr`` the chunk's ``C B^T`` of the group being worked on;
    ``s_ref`` (kept for a backward pass) takes the state at the chunk's
    start."""
    s_ref = rest[0] if keep_states else None
    S_scr, cb_scr = rest[-2:]
    h = pl.program_id(2)
    P, dtype = head_dim, x_ref.dtype
    b, c = b_ref[0], c_ref[0]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        S_scr[h] = jnp.zeros(S_scr.shape[1:], _F32)

    @pl.when(lax.rem(h, group_sets) == 0)
    def _scores():
        cb_scr[...] = _mm(c, b, _NT)

    s = _step_terms(dt_ref, a_ref)
    cb = cb_scr[...]
    blocks = _row_blocks(cb.shape[0])
    if keep_states:
        s_ref[0, 0] = S_scr[h]
    for j in range(x_ref.shape[2] // _LANES):
        u = _tile_terms(x_ref, s, j, P)
        S = S_scr[h, :, u.lanes]
        y = u.from_start * _mm(c, S.astype(dtype))
        for i in range(_LANES // P):
            v_own = _own_lanes(u.v, i, P)
            y = y + jnp.concatenate(
                [
                    _mm(
                        (cb[rows, cols] * _head_mask(s, u.first + i, rows, cols)).astype(dtype),
                        v_own[cols],
                    )
                    for rows, cols in blocks
                ],
                axis=0,
            )
        y = y + d_ref[:, u.lanes].astype(_F32) * u.x32
        o_ref[0, :, u.lanes] = y.astype(dtype)
        S_scr[h, :, u.lanes] = u.decay * S + _mm(b, u.v_end, _TN)


def _ssd_bwd_kernel(
    x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, s_ref, dy_ref,
    dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref,
    dS_scr, cb_scr, dcb_scr, db_scr, dc_scr, dGt_scr, *, head_dim, group_sets,
):
    """Grid (B, chunks in reverse, sets of heads), ``group_sets`` sets a
    group of ``B`` and ``C``.  ``dS_scr`` holds the cotangent of every
    head's state at the end of the chunk being worked on; ``dcb_scr``,
    ``db_scr`` and ``dc_scr`` add up over a group's sets what its heads
    owe ``C B^T``, ``B`` and ``C``; ``dGt_scr`` ``[heads, L]`` takes the
    column sums a head owes ``G``, as rows."""
    h = pl.program_id(2)
    P, dtype = head_dim, x_ref.dtype
    L, width = x_ref.shape[1:]
    heads = width // P
    b, c = b_ref[0], c_ref[0]

    @pl.when(pl.program_id(1) == 0)
    def _start():
        dS_scr[h] = jnp.zeros(dS_scr.shape[1:], _F32)

    @pl.when(lax.rem(h, group_sets) == 0)
    def _scores():
        cb_scr[...] = _mm(c, b, _NT)
        dcb_scr[...] = jnp.zeros(dcb_scr.shape, _F32)
        db_scr[...] = jnp.zeros(db_scr.shape, _F32)
        dc_scr[...] = jnp.zeros(dc_scr.shape, _F32)

    s = _step_terms(dt_ref, a_ref)
    cb = cb_scr[...]
    blocks = _row_blocks(L)
    dcb = [jnp.zeros((_LANES, cols.stop), _F32) for _, cols in blocks]
    db_acc = jnp.zeros(b.shape, _F32)
    dc_acc = jnp.zeros(c.shape, _F32)
    dG = jnp.zeros((L, heads), _F32)  # what G owes, a column a head
    ddt = jnp.zeros((L, heads), _F32)  # dt's share through v = dt x
    head_lane = lax.broadcasted_iota(jnp.int32, (L, heads), 1)
    last_row = lax.broadcasted_iota(jnp.int32, (L, _LANES), 0) == L - 1
    for j in range(width // _LANES):
        u = _tile_terms(x_ref, s, j, P)
        dy = dy_ref[0, :, u.lanes]
        dy32 = dy.astype(_F32)
        S0 = s_ref[0, 0, :, u.lanes]
        S0b = S0.astype(dtype)
        dS = dS_scr[h, :, u.lanes]
        dSb = dS.astype(dtype)
        # The read of the carried state, y += e^{G_t} C S0.
        dread32 = u.from_start * dy32
        dread = dread32.astype(dtype)
        owed = dread32 * _mm(c, S0b)  # to G_t, lane by lane
        dc_acc = dc_acc + _mm(dread, S0b, _NT)
        # The chunk's write, S_L = e^{G_L} S0 + B^T (e^{G_L - G_s} v).
        dv_end = _mm(b, dSb)
        db_acc = db_acc + _mm(u.v_end, dSb, _NT)
        to_end = dv_end * u.v_end32  # to G_L - G_s
        at_end = jnp.sum(to_end, axis=0, keepdims=True) + u.decay * jnp.sum(
            dS * S0, axis=0, keepdims=True
        )
        owed = owed - to_end + jnp.where(last_row, at_end, 0.0)
        dv = u.to_end * dv_end
        dS_scr[h, :, u.lanes] = u.decay * dS + _mm(c, dread, _TN)
        # The heads' scores.
        row_sums = []
        for i in range(_LANES // P):
            a = u.first + i
            dy_own = _own_lanes(dy, i, P)
            rows_owed, cols_owed = [], jnp.zeros((1, L), _F32)
            for r, (rows, cols) in enumerate(blocks):
                mask = _head_mask(s, a, rows, cols)
                scores32 = cb[rows, cols] * mask
                dscores = _mm(dy_own[rows], u.v[cols], _NT)
                dcb[r] = dcb[r] + dscores * mask
                both = dscores * scores32
                rows_owed.append(jnp.sum(both, axis=1, keepdims=True))
                cols_owed = cols_owed + jnp.pad(
                    jnp.sum(both, axis=0, keepdims=True), ((0, 0), (0, L - cols.stop))
                )
                dv = dv + jnp.pad(
                    _mm(scores32.astype(dtype), dy_own[rows], _TN),
                    ((0, L - cols.stop), (0, 0)),
                )
            row_sums.append(jnp.concatenate(rows_owed, axis=0))
            dGt_scr[a:a + 1, :] = -cols_owed
        dx_ref[0, :, u.lanes] = (
            u.dtx * dv + d_ref[:, u.lanes].astype(_F32) * dy32
        ).astype(dtype)
        dd_ref[0, 0, :, u.lanes] = jnp.sum(dy32 * u.x32, axis=0, keepdims=True)
        through_v = dv * u.x32
        for i in range(_LANES // P):
            a = u.first + i
            lane_sum = lambda y: jnp.sum(_own_lanes(y, i, P), axis=1, keepdims=True)
            dG = jnp.where(head_lane == a, row_sums[i] + lane_sum(owed), dG)
            ddt = jnp.where(head_lane == a, lane_sum(through_v), ddt)
    # dg: the reverse running sum of dG inside the chunk.
    dg = sum(_mm(s.ones_u, p) for p in _pieces(dG)) + sum(
        _mm(s.ones_u, p, _NT) for p in _pieces(dGt_scr[...])
    )
    ddt_ref[0, 0] = (ddt - s.rate * dg).astype(ddt_ref.dtype)
    da_ref[0, 0, 0] = jnp.sum(dg * s.g, axis=0, keepdims=True)
    for (rows, cols), part in zip(blocks, dcb):
        dcb_scr[rows, cols] += part
    db_scr[...] += db_acc
    dc_scr[...] += dc_acc

    @pl.when(lax.rem(h, group_sets) == group_sets - 1)
    def _shared():
        dcbb = dcb_scr[...].astype(dtype)
        dc_ref[0] = (dc_scr[...] + _mm(dcbb, b)).astype(dc_ref.dtype)
        db_ref[0] = (db_scr[...] + _mm(dcbb, c, _TN)).astype(db_ref.dtype)


def _kernel_specs(chunk, heads, width, N, group_sets, order):
    """Block specs over the grid (batch, chunk, set of heads): the
    ``[chunk, lanes]`` tile of ``x`` (and of whatever has its shape), the
    set's ``dt`` ``[chunk, heads]``, ``A_log`` and ``D`` for the set,
    the chunk's ``B`` or ``C`` of the set's group (``N`` lanes of the
    flat ``[B, T, G * N]``), the set's state at the chunk's start,
    and the rows a backward step owes ``D`` and ``A_log``; ``order`` maps
    the grid's chunk to the array's (the backward sweeps in reverse)."""
    return types.SimpleNamespace(
        x=pl.BlockSpec((1, chunk, width), lambda i, t, h: (i, order(t), h)),
        dt=pl.BlockSpec((1, 1, chunk, heads), lambda i, t, h: (i, h, order(t), 0)),
        a=pl.BlockSpec((1, 1, heads), lambda i, t, h: (h, 0, 0)),
        b=pl.BlockSpec(
            (1, chunk, N), lambda i, t, h: (i, order(t), lax.div(h, group_sets))
        ),
        d=pl.BlockSpec((1, width), lambda i, t, h: (0, h)),
        state=pl.BlockSpec((1, 1, N, width), lambda i, t, h: (i, order(t), 0, h)),
        d_row=pl.BlockSpec((1, 1, 1, width), lambda i, t, h: (i, order(t), 0, h)),
        a_row=pl.BlockSpec((1, 1, 1, 1, heads), lambda i, t, h: (i, order(t), h, 0, 0)),
    )


def _kernel_geometry(x, dt, b, chunk):
    """From ``x`` ``[B, T, H * P]``, ``dt`` by sets ``[B, sets, T,
    heads]`` and ``b`` ``[B, T, G, N]``."""
    B, T, lanes = x.shape
    sets, heads = dt.shape[1], dt.shape[3]
    return types.SimpleNamespace(
        B=B, n=T // chunk, sets=sets, heads=heads, width=lanes // sets,
        N=b.shape[-1], group_sets=sets // b.shape[2],
    )


def _group_lanes(b):
    """``B`` or ``C`` ``[B, T, G, N]`` as the kernels read it: a group is
    ``N`` lanes of ``[B, T, G * N]``."""
    return b.reshape(*b.shape[:2], -1)


def _kernel_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=_KERNEL_VMEM_BYTES,
    )


def _kernel_forward(x, dt, a_log, b, c, d, *, chunk, keep_states, interpret):
    """``(y [B, T, H * P], kept)``, ``kept`` the states at the chunks'
    starts ``[B, T / chunk, N, H * P]`` (float32) or ``()``; ``T`` a
    multiple of ``chunk``."""
    k = _kernel_geometry(x, dt, b, chunk)
    spec = _kernel_specs(chunk, k.heads, k.width, k.N, k.group_sets, lambda t: t)
    vma = _vma(x)
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype, vma=vma)]
    out_specs = [spec.x]
    if keep_states:
        out_shape.append(
            jax.ShapeDtypeStruct((k.B, k.n, k.N, x.shape[2]), _F32, vma=vma)
        )
        out_specs.append(spec.state)
    call = pl.pallas_call(
        functools.partial(
            _ssd_fwd_kernel, head_dim=k.width // k.heads,
            group_sets=k.group_sets, keep_states=keep_states,
        ),
        grid=(k.B, k.n, k.sets),
        in_specs=[spec.x, spec.dt, spec.a, spec.b, spec.b, spec.d],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((k.sets, k.N, k.width), _F32),
            pltpu.VMEM((chunk, chunk), _F32),
        ],
        compiler_params=_kernel_params(),
        interpret=interpret,
    )
    with jax.named_scope(SSD_CORE_SCOPE):
        res = call(x, dt, a_log, _group_lanes(b), _group_lanes(c), d)
    return res[0], tuple(res[1:])


def _kernel_backward(x, dt, a_log, b, c, d, states, dy, *, chunk, interpret):
    """The cotangents of ``x``, ``dt`` (by sets, like ``dt``), ``b`` and
    ``c`` (as lanes, ``[B, T, G * N]``), and a row a chunk and set of what
    ``A_log`` ``[B, T / chunk, sets, 1, heads]`` and ``D`` ``[B, T /
    chunk, 1, H * P]`` are owed."""
    k = _kernel_geometry(x, dt, b, chunk)
    spec = _kernel_specs(
        chunk, k.heads, k.width, k.N, k.group_sets, lambda t: k.n - 1 - t
    )
    b, c = _group_lanes(b), _group_lanes(c)
    vma = _vma(x)
    like = lambda y: jax.ShapeDtypeStruct(y.shape, y.dtype, vma=vma)
    rows = lambda *shape: jax.ShapeDtypeStruct((k.B, k.n) + shape, _F32, vma=vma)
    call = pl.pallas_call(
        functools.partial(
            _ssd_bwd_kernel, head_dim=k.width // k.heads, group_sets=k.group_sets
        ),
        grid=(k.B, k.n, k.sets),
        in_specs=[
            spec.x, spec.dt, spec.a, spec.b, spec.b, spec.d, spec.state, spec.x,
        ],
        out_specs=[spec.x, spec.dt, spec.a_row, spec.b, spec.b, spec.d_row],
        out_shape=[
            like(x), like(dt), rows(k.sets, 1, k.heads), like(b), like(c),
            rows(1, x.shape[2]),
        ],
        scratch_shapes=[
            pltpu.VMEM((k.sets, k.N, k.width), _F32),
            pltpu.VMEM((chunk, chunk), _F32),
            pltpu.VMEM((chunk, chunk), _F32),
            pltpu.VMEM((chunk, k.N), _F32),
            pltpu.VMEM((chunk, k.N), _F32),
            pltpu.VMEM((k.heads, chunk), _F32),
        ],
        compiler_params=_kernel_params(),
        interpret=interpret,
    )
    with jax.named_scope(SSD_CORE_SCOPE):
        return call(x, dt, a_log, b, c, d, states, dy)


def _by_sets(dt, heads):
    """``[B, T, H]`` -> ``[B, H / heads, T, heads]``: a grid step's heads
    as the lanes of its tile."""
    B, T, H = dt.shape
    return jnp.swapaxes(dt.reshape(B, T, H // heads, heads), 1, 2)


def _kernel_operands(x, dt, a_log, b, c, d, chunk):
    """The arguments as the kernels read them, the length padded to whole
    chunks with tokens that leave the state alone (``dt`` 0)."""
    H = dt.shape[-1]
    P = x.shape[-1] // H
    heads = _kernel_heads(H // b.shape[2], P)
    pad = -x.shape[1] % chunk
    x, dt, b, c = (_padded(y, pad) for y in (x, dt, b, c))
    return (
        x, _by_sets(dt, heads), a_log.reshape(H // heads, 1, heads), b, c,
        jnp.repeat(d, P)[None],
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def kernel_ssd_flat(x, dt, a_log, b, c, d, chunk=256, interpret=False):
    """The chunk-wise scan as Pallas kernels (section comment above),
    forward and backward, on the flat view the kernels read: ``x`` ``[B,
    T, H * P]``, ``dt`` ``[B, T, H]``, ``a_log`` and ``d`` ``[H]``, ``b``,
    ``c`` ``[B, T, G, N]``; returns ``[B, T, H * P]``.  ``interpret=True``
    runs the same kernels on the CPU for tests."""
    return _kernel_fwd(x, dt, a_log, b, c, d, chunk, interpret, False)[0]


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
@jax.named_scope(SSD_CORE_SCOPE)
def _kernel_fwd(x, dt, a_log, b, c, d, chunk, interpret, keep_states=True):
    operands = _kernel_operands(x, dt, a_log, b, c, d, chunk)
    y, kept = _kernel_forward(
        *operands, chunk=chunk, keep_states=keep_states, interpret=interpret
    )
    return y[:, :x.shape[1]], operands + kept


@functools.partial(jax.jit, static_argnums=(0, 1))
@jax.named_scope(SSD_CORE_SCOPE)
def _kernel_bwd(chunk, interpret, res, dy):
    a_log, d = res[2], res[5]
    T, H = dy.shape[1], a_log.size
    dx, ddt, da, db, dc, dd = _kernel_backward(
        *res, _padded(dy, -T % chunk), chunk=chunk, interpret=interpret
    )
    ddt = jnp.swapaxes(ddt, 1, 2).reshape(ddt.shape[0], -1, H)
    by_group = lambda y: y.reshape(res[3].shape)[:, :T]
    return (
        dx[:, :T], ddt[:, :T],
        jnp.sum(da, axis=(0, 1)).reshape(H).astype(a_log.dtype),
        by_group(db), by_group(dc),
        jnp.sum(dd, axis=(0, 1, 2)).reshape(H, -1).sum(axis=1).astype(d.dtype),
    )


kernel_ssd_flat.defvjp(_kernel_fwd, _kernel_bwd)


def kernel_ssd(x, dt, a_log, b, c, d_skip=None, chunk=256, interpret=False):
    """:func:`kernel_ssd_flat` for the arguments of :func:`chunked_ssd`,
    what it runs on a TPU for the calls :func:`kernel_admissible` admits:
    the heads' channels are folded into the lanes on the way in and out
    again on the way back (the mixer's own reshapes beside them, so XLA
    drops both)."""
    B, T, H, P = x.shape
    d = jnp.zeros((H,), _F32) if d_skip is None else d_skip
    y = kernel_ssd_flat(
        x.reshape(B, T, H * P), dt, a_log, _by_group(b), _by_group(c), d, chunk,
        interpret,
    )
    return y.reshape(B, T, H, P)


def ssd_route(x, dt, a_log, b, c, *, chunk: int) -> str:
    """What :func:`chunked_ssd` runs for this call: ``"kernel"`` on a TPU
    for the calls the kernels admit, where a Mosaic kernel can lower;
    else ``"plain"``."""
    if (
        jax.default_backend() == "tpu"
        and kernel_admissible(x, b, c, chunk=chunk)
        and mosaic_can_lower()
    ):
        return "kernel"
    return "plain"


def chunked_ssd(x, dt, a_log, b, c, d_skip=None, *, chunk: int = 256):
    """:func:`recurrent_ssd` computed chunk-wise (module docstring); same
    arguments (``b``, ``c`` ``[B, T, G, N]`` with ``G`` dividing ``H``, or
    ``[B, T, N]`` for one group), the result in the dtype of ``x``.  On a
    TPU, for whole tiles, the Pallas kernels (:func:`kernel_ssd`: 1.37 ms forward and
    4.45 with the backward pass at ``[1, 8192, 64, 64]`` over a 128-wide
    state on a v5e, chunks of 256, against :func:`plain_ssd`'s 2.87 and
    10.73 beside them; in ``granite_h_train``'s step, where the mixer's
    reshapes cancel the 4-D view's relayout, two forward passes and the
    backward of a layer are 2.55 ms, 10.25 plain; PERF.md, PR 39; with
    eight groups of eight heads in ``nemotron_h_train`` 2.98 ms; PR 40), else
    :func:`plain_ssd`; the choice is counted once per traced
    call.  ``chunk`` is the program's way to compute the recurrence and no
    part of the model (``mamba_chunk_size`` 256 is the published kernel's
    block).  What it trades on the plain route is memory: a float32 copy
    of the per-chunk states is ``T / chunk x H x N x P`` (268 MB at 8,192
    tokens of Granite's 64 x 128 x 64 in chunks of 64, 67 MB at 256), a
    head's masks ``T x chunk`` (PERF.md, PR 38); the kernels keep the
    states alone."""
    if not (
        x.shape[:3] == dt.shape and b.shape == c.shape and b.ndim in (3, 4)
        and b.shape[:2] == x.shape[:2] and a_log.shape == x.shape[2:3]
        and x.shape[2] % _groups(b) == 0
    ):
        raise ValueError(
            f"chunked_ssd wants x [B, T, H, P], dt [B, T, H], a_log [H] and "
            f"b, c [B, T, G, N] with G dividing H ([B, T, N]: one group); "
            f"got {x.shape}, {dt.shape}, {a_log.shape}, {b.shape}, {c.shape}"
        )
    route = ssd_route(x, dt, a_log, b, c, chunk=chunk)
    get_registry().counter(
        SSD_ROUTE_KERNEL if route == "kernel" else SSD_ROUTE_PLAIN
    ).inc()
    if route == "kernel":
        return kernel_ssd(x, dt, a_log, b, c, d_skip, chunk)
    return plain_ssd(x, dt, a_log, b, c, d_skip, chunk=chunk)
