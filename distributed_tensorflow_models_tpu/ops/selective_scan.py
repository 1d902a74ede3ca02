"""The selective scan of Mamba-1.

A Mamba-1 layer (Gu and Dao 2023, "Mamba: Linear-Time Sequence Modeling
with Selective State Spaces", arXiv:2312.00752) keeps, per channel ``d`` of
``D``, a state ``h`` ``[N]`` that every token decays, writes and reads::

    h_t[d, n] = exp(dt_t[d] A[d, n]) h_{t-1}[d, n] + dt_t[d] x_t[d] B_t[n]
    y_t[d]    = sum_n h_t[d, n] C_t[n] + D[d] x_t[d]

with ``A = -exp(A_log) < 0`` ``[D, N]``, ``dt_t > 0`` one number a channel
and token (after the softplus), ``B_t`` and ``C_t`` ``[N]`` one vector a
token for all channels.  **A decay for every (channel, state) pair**:
Mamba-2 (:mod:`.ssm`) has one a head, which is what gives it a ``[L, L]``
product form; here there is none, and the work is element-wise (the VPU
and the exponential unit), ``D x N`` recurrences the sequence long.  The
hazard is memory: ``h`` for a sequence is ``T x D x N`` float32 (2.68 GB at
8,192 x 5,120 x 16) and must never exist in HBM, forward or backward.

Three routes, one mathematics:

- :func:`recurrent_selective_scan`: token by token (a ``lax.scan`` over
  time, float32): the oracle of the tests.
- :func:`plain_selective_scan`: chunk-wise in plain ``jax.numpy``, for the
  CPU.  Within a chunk an associative scan over the pairs ``(decay,
  write)`` of ``[L, D, N]`` (84 MB at 256 x 5,120 x 16); the chunks a
  ``lax.scan`` carrying ``[D, N]``, its body under ``jax.checkpoint``, so
  that a backward pass keeps the state at each chunk's start (32 x 5,120 x
  16 float32 = 10.5 MB a sequence of 8,192) and builds ``h`` again inside
  one chunk at a time.
- :func:`kernel_selective_scan`: Pallas (Mosaic) kernels, forward and
  backward under one ``custom_vjp``, for a TPU (the section comment above
  them): ``h`` for a block of 1,024 channels lives in vector registers and
  VMEM while the kernel walks the sequence; HBM holds ``x``, ``dt``, ``B``,
  ``C``, ``y``, their cotangents and the state at each chunk's start.

No exponent taken is positive: every one is ``dt A <= 0``, and the
associative scan multiplies decays, never divides by one.  ``dt``, the
decays, the state and every sum are float32 on all three routes; the
result comes back in the dtype of ``x``.

:func:`selective_scan` chooses between the last two from what a call
shows (backend, shapes), as :func:`.ssm.chunked_ssd` does, and counts the
choice once per traced call (``sscan/route_kernel``, ``sscan/route_plain``).
"""

from __future__ import annotations

import functools

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
from distributed_tensorflow_models_tpu.ops.linear_attention import _NT, _mm, _padded
from distributed_tensorflow_models_tpu.ops.ssm import _pieces
from distributed_tensorflow_models_tpu.telemetry.registry import (
    SSCAN_ROUTE_KERNEL,
    SSCAN_ROUTE_PLAIN,
    get_registry,
)

# ``jax.named_scope`` of the scan, forward and backward: a path element of
# every instruction's ``op_name`` in the compiled step (PERF.md section 3).
# The mixer's projections, its convolution and the gate stay outside it
# (``models/mixers.py::SSM_SCOPE`` holds them all).
SSCAN_CORE_SCOPE = "sscan_core"

_F32 = jnp.float32
_SUBLANES = 8


def _check(x, dt, a_log, b, c, d_skip):
    ok = (
        x.ndim == 3 and x.shape == dt.shape and a_log.ndim == 2
        and a_log.shape[0] == x.shape[2] and b.shape == c.shape
        and b.shape == x.shape[:2] + a_log.shape[1:]
        and (d_skip is None or d_skip.shape == x.shape[2:])
    )
    if not ok:
        raise ValueError(
            "selective_scan wants x, dt [B, T, D], a_log [D, N], b, c "
            f"[B, T, N] and d_skip [D]; got {x.shape}, {dt.shape}, "
            f"{a_log.shape}, {b.shape}, {c.shape}, "
            f"{None if d_skip is None else d_skip.shape}"
        )


def recurrent_selective_scan(x, dt, a_log, b, c, d_skip=None):
    """The recurrence token by token, float32.  ``x``, ``dt`` ``[B, T,
    D]`` (``dt`` > 0, after the softplus), ``a_log`` ``[D, N]``, ``b``,
    ``c`` ``[B, T, N]``, ``d_skip`` ``[D]`` or None; returns ``[B, T, D]``
    float32."""
    _check(x, dt, a_log, b, c, d_skip)
    x, dt, b, c = (y.astype(_F32) for y in (x, dt, b, c))
    A = -jnp.exp(a_log.astype(_F32))

    def step(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * b_t[:, None]
        return h, jnp.sum(h * c_t[:, None], axis=-1)

    time_first = lambda y: jnp.moveaxis(y, 1, 0)
    h0 = jnp.zeros(x.shape[:1] + a_log.shape, _F32)
    _, out = lax.scan(step, h0, tuple(map(time_first, (x, dt, b, c))))
    out = jnp.moveaxis(out, 0, 1)
    if d_skip is not None:
        out = out + d_skip.astype(_F32) * x
    return out


def _chunks_first(y, chunk: int):
    """``[B, T, ...]`` -> ``[T / chunk, B, chunk, ...]``, the length padded
    to whole chunks with zeros: tokens that leave the state alone (``dt``
    0: no decay, nothing written)."""
    y = _padded(y, -y.shape[1] % chunk)
    return jnp.moveaxis(y.reshape(y.shape[0], -1, chunk, *y.shape[2:]), 1, 0)


@functools.partial(jax.jit, static_argnames=("chunk",))
@jax.named_scope(SSCAN_CORE_SCOPE)
def plain_selective_scan(x, dt, a_log, b, c, d_skip=None, *, chunk: int = 256):
    """The chunk-wise form in plain ``jax.numpy`` (module docstring); same
    arguments as :func:`recurrent_selective_scan`, the result in the dtype
    of ``x``."""
    B, T, D = x.shape
    A = -jnp.exp(a_log.astype(_F32))

    def combine(left, right):
        (a1, w1), (a2, w2) = left, right
        return a1 * a2, a2 * w1 + w2

    @jax.checkpoint
    def body(h, at):
        x_c, dt_c, b_c, c_c = (y.astype(_F32) for y in at)
        decay = jnp.exp(dt_c[..., None] * A)  # [B, L, D, N]
        wrote = (dt_c * x_c)[..., None] * b_c[:, :, None]
        through, own = lax.associative_scan(combine, (decay, wrote), axis=1)
        hs = own + through * h[:, None]
        return hs[:, -1], jnp.sum(hs * c_c[:, :, None], axis=-1)

    h0 = jnp.zeros((B,) + a_log.shape, _F32)
    _, ys = lax.scan(body, h0, tuple(_chunks_first(y, chunk) for y in (x, dt, b, c)))
    y = jnp.moveaxis(ys, 0, 1).reshape(B, -1, D)[:, :T]
    if d_skip is not None:
        y = y + d_skip.astype(_F32) * x.astype(_F32)
    return y.astype(x.dtype)


# --- The scan as Pallas (Mosaic) kernels ------------------------------------
#
# The recurrence is sequential in time and independent across channels and
# states, so the kernels put the *channels* in a vector register's two
# dimensions and walk the tokens: ``x``, ``dt`` and ``y`` are read as ``[B,
# T, 8, D / 8]`` float32 (a reshape of the channel axis; XLA makes the
# relayout and the cast on the way in and out, at HBM speed), a grid step
# takes ``[chunk, 8, 128]`` of them (1,024 channels: sublane ``s``, lane
# ``l`` of block ``j`` is channel ``s D / 8 + 128 j + l``), and a token of a
# block is one whole register.  The state of the block is ``N`` registers,
# one a state index, carried through a ``fori_loop`` over the chunk's tokens
# and from chunk to chunk in VMEM scratch; ``A`` is ``[N, 8, 128]``
# beside it.  ``B_t[n]`` and ``C_t[n]`` are *scalars* to every channel: the
# chunk's ``B`` and ``C`` sit in SMEM and each is read as a scalar and
# broadcast by the multiply (no register ever holds a transposed ``B``).  A
# token of a block costs ``N`` exponentials and about ``6 N`` multiply-adds
# on whole registers and nothing else; the MXU idles.
#
# Grid (batch, channel block, chunk), the chunks innermost and in order.
# The forward also writes the state at each chunk's start (``[B, T / chunk,
# N, 8, D / 8]`` float32: 21 MB at 8,192 x 5,120 x 16 in chunks of 128),
# which is all a backward pass keeps beside the inputs.
#
# The backward walks the chunks in reverse.  A grid step first runs the
# chunk forward again from its kept start, writing ``h_t`` for its tokens
# to VMEM scratch (``(chunk + 1) x N`` registers: 8.3 MB), then walks the
# tokens in reverse with ``a_{t+1} g_{t+1}`` carried (``g_t = dy_t C_t +
# a_{t+1} g_{t+1}`` the state's cotangent)::
#
#     dC_t[n] = sum_d dy_t h_t          dB_t[n] = sum_d g_t u_t     (u = dt x)
#     du_t    = sum_n g_t B_t[n]        da_t    = g_t h_{t-1} a_t   (to dt A)
#     ddt_t   = sum_n da_t A + du_t x_t dA     += da_t dt_t
#     dx_t    = du_t dt_t + D dy_t      dD     += dy_t x_t
#
# ``dB`` and ``dC`` sum over channels: a step reduces each register over
# its sublanes, lays the ``N`` rows of a token into one ``[N, 128]`` tile
# of scratch, and after the loop sums the lanes of the whole chunk by one
# product with ones (float32 exactly: the three bfloat16 pieces), leaving
# lane-dense as ``[1, chunk x N]``; the channel blocks' parts are added up
# outside.  ``dA`` and ``dD`` accumulate in their output blocks over the
# chunks.

_KERNEL_CHUNK = 128
_KERNEL_CHANNELS = _SUBLANES * _LANES  # of one grid step
_KERNEL_MAX_STATE = 32  # the state index is unrolled
_KERNEL_VMEM_BYTES = 64 * 1024 * 1024  # of v5e's 128 MiB


def kernel_admissible(x, a_log) -> bool:
    """Whether the kernels take this call: channels in whole blocks of
    1,024, a state small enough to unroll.  Visible at trace time; the
    backend is the caller's question."""
    return (
        x.shape[2] % _KERNEL_CHANNELS == 0 and a_log.shape[1] <= _KERNEL_MAX_STATE
    )


def _token_forward(x_t, dt_t, A_ref, b_ref, c_ref, at, h, N):
    """One token of a block: the new state (a tuple of ``N`` registers)
    and ``sum_n h_t C_t[n]``.  ``at`` is the token's offset into the
    chunk's ``B`` and ``C`` in SMEM."""
    u = dt_t * x_t
    y, new = None, []
    for n in range(N):
        hn = jnp.exp(dt_t * A_ref[n]) * h[n] + u * b_ref[at + n]
        read = hn * c_ref[at + n]
        y = read if y is None else y + read
        new.append(hn)
    return tuple(new), y


def _sscan_fwd_kernel(
    b_ref, c_ref, x_ref, dt_ref, A_ref, d_ref, y_ref, *rest, N, L, keep_states
):
    """Grid (B, channel blocks, chunks).  ``h_scr`` ``[N, 8, 128]`` carries
    the block's state from chunk to chunk; ``s_ref`` (kept for a backward
    pass) takes it at the chunk's start."""
    h_scr = rest[-1]

    @pl.when(pl.program_id(2) == 0)
    def _start():
        h_scr[...] = jnp.zeros(h_scr.shape, _F32)

    if keep_states:
        rest[0][0, 0] = h_scr[...]
    skip = d_ref[...]

    def token(t, h):
        x_t = x_ref[0, t]
        h, y = _token_forward(x_t, dt_ref[0, t], A_ref, b_ref, c_ref, t * N, h, N)
        y_ref[0, t] = y + skip * x_t
        return h

    h = lax.fori_loop(0, L, token, tuple(h_scr[n] for n in range(N)))
    for n in range(N):
        h_scr[n] = h[n]


def _sscan_bwd_kernel(
    b_ref, c_ref, x_ref, dt_ref, A_ref, d_ref, s_ref, dy_ref,
    dx_ref, ddt_ref, dA_ref, dD_ref, db_ref, dc_ref,
    ag_scr, h_scr, db_scr, dc_scr, *, N, L,
):
    """Grid (B, channel blocks, chunks in reverse).  ``ag_scr`` ``[N, 8,
    128]`` carries ``a_{t+1} g_{t+1}`` across chunks; ``h_scr`` ``[(L + 1)
    N, 8, 128]`` holds the chunk's states, slot ``(t + 1) N + n`` being
    ``h_t[n]`` and the first ``N`` the kept start; ``db_scr``, ``dc_scr``
    ``[L, N, 128]`` take a token's rows of what ``B`` and ``C`` are owed,
    the lanes still to be summed."""

    @pl.when(pl.program_id(2) == 0)
    def _start():
        ag_scr[...] = jnp.zeros(ag_scr.shape, _F32)
        dA_ref[...] = jnp.zeros(dA_ref.shape, _F32)
        dD_ref[...] = jnp.zeros(dD_ref.shape, _F32)

    for n in range(N):
        h_scr[n] = s_ref[0, 0, n]

    def again(t, h):
        h, _ = _token_forward(
            x_ref[0, t], dt_ref[0, t], A_ref, b_ref, c_ref, t * N, h, N
        )
        for n in range(N):
            h_scr[(t + 1) * N + n] = h[n]
        return h

    lax.fori_loop(0, L, again, tuple(s_ref[0, 0, n] for n in range(N)))

    skip = d_ref[...]
    row = lax.broadcasted_iota(jnp.int32, (N, _LANES), 0)
    over_sublanes = lambda y: jnp.broadcast_to(
        jnp.sum(y, axis=0, keepdims=True), (N, _LANES)
    )

    def token(i, carry):
        ag, dD = carry
        t = L - 1 - i
        x_t, dt_t, dy_t = x_ref[0, t], dt_ref[0, t], dy_ref[0, t]
        u = dt_t * x_t
        du = jnp.zeros_like(u)
        ddt = jnp.zeros_like(u)
        db_rows = jnp.zeros((N, _LANES), _F32)
        dc_rows = jnp.zeros((N, _LANES), _F32)
        new = []
        for n in range(N):
            A_n = A_ref[n]
            decay = jnp.exp(dt_t * A_n)
            g = dy_t * c_ref[t * N + n] + ag[n]
            dc_rows = jnp.where(row == n, over_sublanes(dy_t * h_scr[(t + 1) * N + n]), dc_rows)
            db_rows = jnp.where(row == n, over_sublanes(g * u), db_rows)
            du = du + g * b_ref[t * N + n]
            da = g * h_scr[t * N + n] * decay
            ddt = ddt + da * A_n
            dA_ref[0, n] += da * dt_t
            new.append(decay * g)
        db_scr[t] = db_rows
        dc_scr[t] = dc_rows
        dx_ref[0, t] = du * dt_t + skip * dy_t
        ddt_ref[0, t] = ddt + du * x_t
        return tuple(new), dD + dy_t * x_t

    ag, dD = lax.fori_loop(
        0, L, token,
        (tuple(ag_scr[n] for n in range(N)), jnp.zeros((_SUBLANES, _LANES), _F32)),
    )
    for n in range(N):
        ag_scr[n] = ag[n]
    dD_ref[0] += dD
    ones = jnp.ones((_SUBLANES, _LANES), jnp.bfloat16)
    lanes_summed = lambda scr: sum(
        _mm(ones, piece, _NT) for piece in _pieces(scr[...].reshape(L * N, _LANES))
    )
    db_ref[0, 0, 0] = lanes_summed(db_scr)
    dc_ref[0, 0, 0] = lanes_summed(dc_scr)


def _tiled(y):
    """``[..., D]`` -> ``[..., 8, D / 8]`` float32: the channels over a
    register's sublanes and lanes."""
    return y.astype(_F32).reshape(*y.shape[:-1], _SUBLANES, -1)


def _kernel_operands(x, dt, a_log, b, c, d, chunk):
    """The arguments as the kernels read them, the length padded to whole
    chunks with tokens that leave the state alone (``dt`` 0)."""
    pad = -x.shape[1] % chunk
    x, dt, b, c = (_padded(y, pad) for y in (x, dt, b, c))
    A = -jnp.exp(a_log.astype(_F32))
    return (
        b.astype(_F32).reshape(-1), c.astype(_F32).reshape(-1), _tiled(x), _tiled(dt),
        _tiled(A.T), _tiled(d),
    )


def _kernel_specs(chunk, N, n_chunks, order):
    """Block specs over the grid (batch, channel block, chunk): the
    chunk's ``B`` or ``C`` in SMEM (flat, ``chunk x N`` scalars), the
    ``[chunk, 8, 128]`` tile of ``x`` (and of whatever has its shape),
    ``A`` ``[N, 8, 128]`` and ``D`` ``[8, 128]`` of the block, the block's
    state at the chunk's start, and a chunk's lane-dense row of what ``B``
    or ``C`` is owed; ``order`` maps the grid's chunk to the array's (the
    backward sweeps in reverse)."""
    return dict(
        bc=pl.BlockSpec(
            (chunk * N,), lambda i, j, t: (i * n_chunks + order(t),),
            memory_space=pltpu.SMEM,
        ),
        x=pl.BlockSpec(
            (1, chunk, _SUBLANES, _LANES), lambda i, j, t: (i, order(t), 0, j)
        ),
        A=pl.BlockSpec((N, _SUBLANES, _LANES), lambda i, j, t: (0, 0, j)),
        d=pl.BlockSpec((_SUBLANES, _LANES), lambda i, j, t: (0, j)),
        state=pl.BlockSpec(
            (1, 1, N, _SUBLANES, _LANES), lambda i, j, t: (i, order(t), 0, 0, j)
        ),
        dA=pl.BlockSpec((1, N, _SUBLANES, _LANES), lambda i, j, t: (i, 0, 0, j)),
        dD=pl.BlockSpec((1, _SUBLANES, _LANES), lambda i, j, t: (i, 0, j)),
        owed=pl.BlockSpec(
            (1, 1, 1, _SUBLANES, chunk * N), lambda i, j, t: (i, j, order(t), 0, 0)
        ),
    )


def _kernel_params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_KERNEL_VMEM_BYTES,
    )


def _kernel_forward(b, c, x, dt, A, d, *, chunk, keep_states, interpret):
    """``(y [B, T, 8, D / 8] float32, kept)``, ``kept`` the states at the
    chunks' starts ``[B, T / chunk, N, 8, D / 8]`` or ``()``."""
    B, T, _, lanes = x.shape
    N, n_chunks, blocks = A.shape[0], T // chunk, lanes // _LANES
    spec = _kernel_specs(chunk, N, n_chunks, lambda t: t)
    vma = _vma(x)
    out_shape = [jax.ShapeDtypeStruct(x.shape, _F32, vma=vma)]
    out_specs = [spec["x"]]
    if keep_states:
        out_shape.append(
            jax.ShapeDtypeStruct((B, n_chunks, N, _SUBLANES, lanes), _F32, vma=vma)
        )
        out_specs.append(spec["state"])
    res = pl.pallas_call(
        functools.partial(_sscan_fwd_kernel, N=N, L=chunk, keep_states=keep_states),
        grid=(B, blocks, n_chunks),
        in_specs=[spec["bc"], spec["bc"], spec["x"], spec["x"], spec["A"], spec["d"]],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((N, _SUBLANES, _LANES), _F32)],
        compiler_params=_kernel_params(),
        interpret=interpret,
    )(b, c, x, dt, A, d)
    return res[0], tuple(res[1:])


def _kernel_backward(b, c, x, dt, A, d, states, dy, *, chunk, interpret):
    """The cotangents of ``x`` and ``dt`` (tiled like them), of ``A`` ``[B,
    N, 8, D / 8]`` and ``D`` ``[B, 8, D / 8]`` a batch row, and of ``B``
    and ``C`` a channel block ``[B, blocks, T / chunk, 8, chunk x N]``
    (eight equal rows)."""
    B, T, _, lanes = x.shape
    N, n_chunks, blocks = A.shape[0], T // chunk, lanes // _LANES
    spec = _kernel_specs(chunk, N, n_chunks, lambda t: n_chunks - 1 - t)
    vma = _vma(x)
    shape = lambda *s: jax.ShapeDtypeStruct(s, _F32, vma=vma)
    owed = shape(B, blocks, n_chunks, _SUBLANES, chunk * N)
    return pl.pallas_call(
        functools.partial(_sscan_bwd_kernel, N=N, L=chunk),
        grid=(B, blocks, n_chunks),
        in_specs=[
            spec["bc"], spec["bc"], spec["x"], spec["x"], spec["A"], spec["d"],
            spec["state"], spec["x"],
        ],
        out_specs=[
            spec["x"], spec["x"], spec["dA"], spec["dD"], spec["owed"], spec["owed"]
        ],
        out_shape=[
            shape(*x.shape), shape(*x.shape), shape(B, N, _SUBLANES, lanes),
            shape(B, _SUBLANES, lanes), owed, owed,
        ],
        scratch_shapes=[
            pltpu.VMEM((N, _SUBLANES, _LANES), _F32),
            pltpu.VMEM(((chunk + 1) * N, _SUBLANES, _LANES), _F32),
            pltpu.VMEM((chunk, N, _LANES), _F32),
            pltpu.VMEM((chunk, N, _LANES), _F32),
        ],
        compiler_params=_kernel_params(),
        interpret=interpret,
    )(b, c, x, dt, A, d, states, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def kernel_selective_scan(x, dt, a_log, b, c, d_skip, chunk=_KERNEL_CHUNK, interpret=False):
    """The scan as Pallas kernels (section comment above), forward and
    backward: the arguments of :func:`recurrent_selective_scan` with
    ``d_skip`` an array, the result in the dtype of ``x``.
    ``interpret=True`` runs the same kernels on the CPU for tests."""
    return _kernel_fwd(x, dt, a_log, b, c, d_skip, chunk, interpret, False)[0]


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
@jax.named_scope(SSCAN_CORE_SCOPE)
def _kernel_fwd(x, dt, a_log, b, c, d, chunk, interpret, keep_states=True):
    y, kept = _kernel_forward(
        *_kernel_operands(x, dt, a_log, b, c, d, chunk),
        chunk=chunk, keep_states=keep_states, interpret=interpret,
    )
    y = y.reshape(x.shape[0], -1, x.shape[2])[:, : x.shape[1]].astype(x.dtype)
    return y, (x, dt, a_log, b, c, d) + kept


@functools.partial(jax.jit, static_argnums=(0, 1))
@jax.named_scope(SSCAN_CORE_SCOPE)
def _kernel_bwd(chunk, interpret, res, dy):
    x, dt, a_log, b, c, d, states = res
    B, T, D = x.shape
    operands = _kernel_operands(x, dt, a_log, b, c, d, chunk)
    dx, ddt, dA, dD, db, dc = _kernel_backward(
        *operands, states, _tiled(_padded(dy, -T % chunk)),
        chunk=chunk, interpret=interpret,
    )
    flat = lambda y, like: y.reshape(B, -1, D)[:, :T].astype(like.dtype)
    # [B, blocks, chunks, 8, chunk N] -> [B, T, N]: the blocks' parts added.
    owed = lambda y, like: (
        jnp.sum(y[:, :, :, 0], axis=1).reshape(B, -1, b.shape[2])[:, :T].astype(like.dtype)
    )
    # A = -exp(A_log): dA_log = dA A.
    A_t = operands[4].reshape(-1, D)
    da_log = (jnp.sum(dA, axis=0).reshape(-1, D) * A_t).T
    return (
        flat(dx, x), flat(ddt, dt), da_log.astype(a_log.dtype),
        owed(db, b), owed(dc, c), jnp.sum(dD, axis=0).reshape(D).astype(d.dtype),
    )


kernel_selective_scan.defvjp(_kernel_fwd, _kernel_bwd)


def selective_scan_route(x, a_log) -> str:
    """What :func:`selective_scan` runs for this call: ``"kernel"`` on a
    TPU for the calls the kernels admit, where a Mosaic kernel can lower;
    else ``"plain"``."""
    if (
        jax.default_backend() == "tpu"
        and kernel_admissible(x, a_log)
        and mosaic_can_lower()
    ):
        return "kernel"
    return "plain"


def selective_scan(x, dt, a_log, b, c, d_skip=None, *, chunk: int = 256):
    """:func:`recurrent_selective_scan` as the model runs it (module
    docstring); same arguments, the result in the dtype of ``x``.  On a
    TPU, for channels in whole blocks of 1,024, the Pallas kernels
    (:func:`kernel_selective_scan`, whose chunk is their own), else
    :func:`plain_selective_scan` in chunks of ``chunk``; the choice is
    counted once per traced call.  ``chunk`` is the program's way to
    compute the recurrence and no part of the model."""
    _check(x, dt, a_log, b, c, d_skip)
    route = selective_scan_route(x, a_log)
    get_registry().counter(
        SSCAN_ROUTE_KERNEL if route == "kernel" else SSCAN_ROUTE_PLAIN
    ).inc()
    if route == "kernel":
        d = jnp.zeros(x.shape[2:], _F32) if d_skip is None else d_skip
        return kernel_selective_scan(x, dt, a_log, b, c, d)
    return plain_selective_scan(x, dt, a_log, b, c, d_skip, chunk=chunk)
