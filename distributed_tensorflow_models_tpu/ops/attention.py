"""Attention ops: the reference, blockwise (memory-efficient), the fused
kernels that ``attention(impl="auto")`` runs on a TPU, and the ring path's
chunk kernels.

The reference framework predates attention entirely (its only sequence model
is the PTB LSTM, SURVEY.md §2.1 R8) — this module is part of the framework's
long-context mandate.  Scaled-dot-product attention has three
implementations behind :func:`attention`, which chooses between the last
two from what a call shows (:func:`auto_route`: backend, shapes, mesh):

- :func:`reference_attention` — O(T²) materialized scores; the numerics
  oracle for everything else.
- :func:`blockwise_attention` — one ``lax.scan`` over KV blocks with running
  (max, sum, acc) renormalization (Rabe & Staats / FlashAttention
  recurrence).  O(T·block) memory, differentiable end-to-end (scan is
  reverse-AD-able), runs on any backend: the CPU, and on the chip every
  call the fused kernels do not take (odd lengths, sliding windows, a
  ``jit`` over several devices outside ``shard_map``).
- :func:`fused_attention` — that recurrence as two kernels shaped by a
  chip measurement (PERF.md section 6, PR 26): lane-filling column blocks
  of ``[B, T, H*D]``, only the block pairs a causal mask leaves, one
  backward kernel.  What ``auto`` runs on a TPU (grouped KV heads repeated).

Plus one: :func:`flash_attention_chunk`, an older Pallas kernel pair (a
forward that also emits the log-sum-exp, a FlashAttention-2 dK/dV + dQ
backward) that takes global offsets and returns ``(out, lse)``.  It is kept
ONLY as the chunk step of :func:`...parallel.ring.ring_attention`
(``impl="flash"`` there), which the fused kernels cannot serve yet: they
take no offsets and return no LSE.  As a whole-sequence route it won nowhere
on the chip (PERF.md section 6, PR 26) and was deleted as one (PR 27);
ROADMAP R5/D14 measures the ring on four chips and then either gives the
fused kernels offsets and an LSE output and deletes the pair, or re-tiles it.

Layout convention everywhere: ``[batch, seq, heads, head_dim]`` (BTHD).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

from distributed_tensorflow_models_tpu.telemetry.registry import (
    ATTN_ROUTE_BLOCKWISE,
    ATTN_ROUTE_FUSED,
    get_registry,
)

NEG_INF = -1e30  # finite "-inf": keeps exp(s - m) well-defined in masked rows

# ``jax.named_scope`` of the attention core (:func:`attention`): a path
# element of every instruction's ``op_name`` in the compiled step, which
# ``step_scopes_p<i>.json`` carries to the device trace (PERF.md section 3).
ATTENTION_CORE_SCOPE = "attention_core"
# Inside it, the core of a call with a sliding window, whatever the route.
SWA_CORE_SCOPE = "swa_core"


def _scale(q, scale: Optional[float]) -> float:
    return scale if scale is not None else q.shape[-1] ** -0.5


def _check_window(window: Optional[int]) -> Optional[int]:
    """A window must cover at least the query itself.  window <= 0 would
    mask every position — and because NEG_INF is finite, softmax over an
    all-masked row silently returns UNIFORM attention (garbage that looks
    plausible), so reject instead of letting impls disagree."""
    if window is not None and window < 1:
        raise ValueError(f"attention window must be >= 1, got {window}")
    return window


def _group_size(q, k) -> int:
    """Grouped-query attention is shape-inferred: q ``[B,T,H,D]`` against
    k/v ``[B,T,H_kv,D]`` with ``H % H_kv == 0`` means each group of
    ``H/H_kv`` query heads shares one KV head (H_kv == 1 is MQA).
    Returns the group size g (1 = standard MHA)."""
    H, Hkv = q.shape[2], k.shape[2]
    if H % Hkv:
        raise ValueError(
            f"query heads {H} not divisible by kv heads {Hkv}"
        )
    return H // Hkv


def _kv_row(H: int, Hkv: int, g: int):
    """Grid-dim-0 (b·H + h) -> the KV head row (b·H_kv + h//g) for the
    Pallas index maps.  ONE definition shared by forward and both
    backward kernels: they must agree on the query-head-to-KV-row
    mapping or gradients silently diverge from the forward's math."""
    return lambda b: (b // H) * Hkv + (b % H) // g


def _expand_kv(q, k, v):
    """Repeat KV heads to match q's head count (the simple-oracle GQA
    path for the XLA impls; the Pallas kernels map groups in their
    index_maps instead and never materialize this)."""
    g = _group_size(q, k)
    if g == 1:
        return k, v
    return (
        jnp.repeat(k, g, axis=2),
        jnp.repeat(v, g, axis=2),
    )


def reference_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Materialized-scores attention. BTHD in, BTHD out.

    ``q_offset``/``kv_offset`` are the global positions of the first query /
    key row — how causal masking stays correct when q and kv are *chunks* of
    a longer sequence (the ring-attention case).
    """
    s = _scale(q, scale)
    window = _check_window(window)
    k, v = _expand_kv(q, k, v)
    logits = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * s
    if causal or window is not None:
        qi = q_offset + jnp.arange(q.shape[1])[:, None]
        kj = kv_offset + jnp.arange(k.shape[1])[None, :]
        valid = qi >= kj if causal else qi == qi
        if window is not None:
            # Sliding window: each query sees the last `window` positions
            # (inclusive of itself) — Mistral-style local attention.
            valid = valid & (qi - kj < window)
        logits = jnp.where(valid, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum(
        "bhqk,bkhd->bqhd", p.astype(v.dtype), v
    )


# --------------------------------------------------------------- blockwise


def _block_update(carry, s_block, v_block):
    """One step of the streaming-softmax recurrence.

    carry = (m, l, acc): running row-max [..., q, 1], running normalizer
    [..., q, 1], unnormalized output accumulator [..., q, d] — all fp32.
    """
    m, l, acc = carry
    m_new = jnp.maximum(m, jnp.max(s_block, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s_block - m_new)
    l_new = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    # p·V in the value dtype with f32 accumulation (p ∈ [0,1]; bf16
    # round-off here is the standard flash-kernel tradeoff) — f32 values
    # keep exact f32 math.
    acc_new = alpha * acc + jnp.einsum(
        "...qk,...kd->...qd", p.astype(v_block.dtype), v_block,
        preferred_element_type=jnp.float32,
    )
    return m_new, l_new, acc_new


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_kv: int = 512,
    q_offset: int | jax.Array = 0,
    kv_offset: int | jax.Array = 0,
    window: Optional[int] = None,
) -> jax.Array:
    """Memory-efficient attention: scan over KV blocks, BTHD in/out.

    Peak memory O(B·H·T_q·block_kv) instead of O(B·H·T_q·T_kv) in *both*
    passes (the scan body is remat-ed, so backward recomputes per-block
    scores instead of storing them); exact same math as
    :func:`reference_attention` (tested to fp32 tolerance).  KV lengths
    that don't divide ``block_kv`` are padded and masked.  Every (query,
    kv-block) pair is computed, the masked ones included; on the chip the
    fused kernels' pair list is what skips them.
    """
    B, Tq, H, D = q.shape
    Dv = v.shape[-1]  # MLA: 192 query/key channels, 128 value channels
    window = _check_window(window)
    k, v = _expand_kv(q, k, v)
    Tkv = k.shape[1]
    block_kv = min(block_kv, Tkv)
    # Arbitrary lengths: pad KV up to a block multiple and mask the tail.
    pad = (-Tkv) % block_kv
    nblocks = (Tkv + pad) // block_kv
    s = _scale(q, scale)

    # Scores run in the INPUT dtype with f32 accumulation (the flash
    # kernel's scheme, _masked_scores): upcasting q/k to f32 first would
    # push the score matmul to the MXU's f32 rate — measured ~4x slower
    # on v5e — and double the scanned KV bytes.  f32 inputs keep full
    # f32 math, so CPU oracle tests are unchanged; the scale folds in
    # AFTER the dot, in f32.
    qf = jnp.swapaxes(q, 1, 2)  # [B,H,Tq,D]
    kf = jnp.swapaxes(k, 1, 2)  # [B,H,Tkv,D]
    vf = jnp.swapaxes(v, 1, 2)
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, 0), (0, pad), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kb = kf.reshape(B, H, nblocks, block_kv, D).transpose(2, 0, 1, 3, 4)
    vb = vf.reshape(B, H, nblocks, block_kv, Dv).transpose(2, 0, 1, 3, 4)

    qi = q_offset + jnp.arange(Tq)[:, None]  # [Tq, 1]

    @jax.checkpoint
    def body(carry, inp):
        # remat: recompute s_block/p in backward instead of stacking
        # score-sized residuals per step — this is what keeps the backward
        # pass O(T·block) too.
        j, k_j, v_j = inp
        s_block = jnp.einsum(
            "bhqd,bhkd->bhqk", qf, k_j,
            preferred_element_type=jnp.float32,
        ) * s
        lk = j * block_kv + jnp.arange(block_kv)[None, :]  # local kv index
        valid = lk < Tkv
        if causal:
            valid = valid & (qi >= kv_offset + lk)
        if window is not None:
            valid = valid & (qi - (kv_offset + lk) < window)
        if causal or pad or window is not None:
            s_block = jnp.where(valid, s_block, NEG_INF)
        return _block_update(carry, s_block, v_j), None

    # Carries derive from qf to inherit its device-varying axis type, so
    # this scan also works nested inside shard_map (Ulysses path) — but
    # are pinned to f32 (qf now keeps the input dtype, and the softmax
    # state must not accumulate in bf16).
    m0 = jnp.zeros_like(qf[..., :1], dtype=jnp.float32) + NEG_INF
    l0 = jnp.zeros_like(qf[..., :1], dtype=jnp.float32)
    a0 = l0 + jnp.zeros((Dv,), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (jnp.arange(nblocks), kb, vb)
    )
    out = acc / jnp.maximum(l, 1e-30)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


# ----------------------------------------- pallas flash pair (ring chunks)


def _masked_scores(
    qb, kb, i, j, q_base, kv_base, *, scale, causal, block_q, block_kv,
    window=None, apply_mask=True,
):
    """Shared score block for the Pallas kernels: S = (Q_i K_j^T) *
    scale in the INPUT dtype with f32 accumulation (upcasting q/k to f32
    first would push the MXU to its f32 rate — measured ~4x slower on
    v5e), causal-masked in GLOBAL positions: ``q_base``/``kv_base`` are
    the global offsets of the first local row (0 for self-attention;
    chunk origins on the ring path).  Forward and backward MUST mask
    identically or gradients silently diverge from the forward's math.

    ``apply_mask=False`` is the interior-block fast path: the caller has
    proven (via :func:`_block_fully_valid`, a scalar predicate) that every
    (q, k) pair in the block is valid, so the iota/compare/select field
    ops are skipped.  These kernels are VPU-bound at model head dims (a
    round-3 sweep read 1.87 TFLOP/s at D=64, ~1% of MXU peak, with HBM and
    per-step overheads under 15%; old access layer, not re-measured — the
    [bq, bkv] elementwise field work is the roofline), so shaving ~6 of
    the ~14 field passes on the majority interior blocks is the
    first-order lever."""
    s = jax.lax.dot_general(
        qb, kb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * scale  # [bq, bkv] f32
    if (causal or window is not None) and apply_mask:
        qi = q_base + i * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 0
        )
        kj = kv_base + j * block_kv + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_kv), 1
        )
        valid = qi >= kj if causal else qi == qi
        if window is not None:
            valid = valid & (qi - kj < window)
        s = jnp.where(valid, s, NEG_INF)
    return s


def _dispatch_masked(
    pl, _step, should_run, i, j, q_base, kv_base,
    *, causal, block_q, block_kv, window=None,
):
    """Shared interior/boundary dispatch for the flash and fused kernels:
    runs ``_step(apply_mask=False)`` on blocks proven fully valid by
    :func:`_block_fully_valid`, ``_step(apply_mask=True)`` on boundary
    blocks, in disjoint ``pl.when`` branches.  One definition so the
    kernels cannot desynchronize their masking."""
    if causal or window is not None:
        full = _block_fully_valid(
            i, j, q_base, kv_base, causal=causal,
            block_q=block_q, block_kv=block_kv, window=window,
        )

        @pl.when(should_run & full)
        def _interior():
            _step(False)

        @pl.when(should_run & jnp.logical_not(full))
        def _boundary():
            _step(True)
    else:

        @pl.when(should_run)
        def _compute():
            _step(True)


def _block_should_run(
    i, j, q_base, kv_base, *, causal, block_q, block_kv, window=None
):
    """Scalar predicate: True iff ANY (q, k) pair in block (i, j) passes
    the causal/window mask — the block-skip test shared by the forward
    kernel and both backward kernels.  ONE definition: a forward that
    skips a block its backward visits (or the reverse) silently diverges
    the gradients from the forward's math."""
    should = True
    if causal:
        # Q block i ends before KV block j starts -> block is all-masked.
        should = (
            q_base + i * block_q + block_q - 1 >= kv_base + j * block_kv
        )
    if window is not None:
        # Whole KV block older than every query's window -> skip.
        should = should & (
            q_base + i * block_q
            - (kv_base + (j + 1) * block_kv - 1)
            < window
        )
    return should


def _block_fully_valid(
    i, j, q_base, kv_base, *, causal, block_q, block_kv, window=None
):
    """Scalar predicate: True iff EVERY (q, k) position pair in block
    (i, j) passes the causal/window mask, i.e. the elementwise mask would
    be all-True and can be skipped.  Causal: the block's minimum query
    position must reach its maximum key position.  Window: the block's
    maximum query/minimum key spread must stay inside the window.  Must
    stay the exact complement structure of :func:`_masked_scores`'s
    per-element test or interior blocks would silently diverge."""
    full = True
    if causal:
        full = (
            q_base + i * block_q
            >= kv_base + (j + 1) * block_kv - 1
        )
    if window is not None:
        full = full & (
            q_base + i * block_q + block_q - 1
            - (kv_base + j * block_kv)
            < window
        )
    return full


def _flash_kernel(
    qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_kv: int,
    window=None,
):
    """Grid = (B*H, Tq/block_q, Tkv/block_kv); KV innermost, softmax state
    carried across KV steps in VMEM scratch, output written on the last.
    Also emits the per-row log-sum-exp (the FlashAttention-2 backward
    residual — :func:`_flash_backward` rebuilds P from it without a second
    softmax pass).  ``qoff_ref``/``kvoff_ref`` are SMEM scalars: global
    offsets of the local chunk (the ring-attention case)."""
    import jax.experimental.pallas as pl  # deferred: TPU-path only

    i = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    q_base, kv_base = qoff_ref[0], kvoff_ref[0]

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    should_run = _block_should_run(
        i, j, q_base, kv_base, causal=causal,
        block_q=block_q, block_kv=block_kv, window=window,
    )

    def _step(apply_mask):
        s = _masked_scores(
            q_ref[0], k_ref[0], i, j, q_base, kv_base,
            scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, window=window,
            apply_mask=apply_mask,
        )
        m_prev, l_prev, acc_prev = m_scr[:], l_scr[:], acc_scr[:]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        # p·V in the value dtype (p ∈ [0,1], bf16 round-off here is the
        # standard flash-kernel tradeoff), f32 accumulate.
        acc = alpha * acc_prev + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:], l_scr[:], acc_scr[:] = m_new, l_new, acc

    _dispatch_masked(
        pl, _step, should_run, i, j, q_base, kv_base,
        causal=causal, block_q=block_q, block_kv=block_kv, window=window,
    )

    @pl.when(j == n_j - 1)
    def _finish():
        o_ref[0] = (
            acc_scr[:] / jnp.maximum(l_scr[:], 1e-30)
        ).astype(o_ref.dtype)
        # [block_q, 1] write: LSE rides with a trailing unit lane dim —
        # Mosaic requires block second-minor dims divisible by 8, which a
        # [1, block_q] 2-D block violates (b-h rows are blocked at 1).
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l_scr[:], 1e-30))


def _auto_block(T: int) -> int:
    """Forward default tile: 256 where the length divides it (a round-3
    v5e sweep at B4 T2048 H8 D64 causal bf16 read 7.78 ms at 256x256
    against 9.21 ms at 128x128; old access layer, not re-measured), 128,
    the Mosaic-aligned floor, elsewhere.  PR 26's chip micro-benchmark read
    512x512 three to four times faster than these defaults (PERF.md
    section 6); re-tiling waits for the ring's own measurement (ROADMAP
    D14)."""
    return 256 if T % 256 == 0 else 128


def _auto_block_bwd(T: int) -> int:
    """Backward default tile, resolved INDEPENDENTLY of the forward's:
    the round-3 sweep behind :func:`_auto_block` timed the forward only,
    so carrying 256 into the backward would be an untested assumption on
    the grad path.  Constant 128 for every T the kernels accept (the
    Mosaic-aligned floor both _check_blocks fallbacks share); the T
    parameter stays so the ring's measurement (ROADMAP D14) can make this
    length-dependent like _auto_block without touching call sites."""
    return 128 if T >= 128 else T


def _check_blocks(Tq, Tkv, block_q, block_kv):
    block_q = min(block_q if block_q is not None else _auto_block(Tq), Tq)
    block_kv = min(
        block_kv if block_kv is not None else _auto_block(Tkv), Tkv
    )
    if Tq % block_q or Tkv % block_kv:
        raise ValueError(
            f"seq lens ({Tq},{Tkv}) not divisible by blocks "
            f"({block_q},{block_kv})"
        )
    return block_q, block_kv


def _heads_first(x):
    """BTHD -> (B*H, T, D): contiguous per-head rows for clean 2D tiles."""
    B, T, H, D = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(B * H, T, D)


def _offset_scalars(q_offset, kv_offset):
    """Offsets as (1,)-shaped int32 SMEM operands (dynamic — traced ring
    axis indices flow through here)."""
    as1 = lambda x: jnp.asarray(x, jnp.int32).reshape(1)
    return as1(q_offset), as1(kv_offset)


def _smem_scalar_spec(pl, pltpu):
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _flash_forward(
    q, k, v, *, causal, scale, block_q, block_kv, interpret,
    q_offset=0, kv_offset=0, window=None,
):
    """Returns ``(out [B,T,H,D], lse [B,T,H] f32)``: the LSE layout
    broadcasts against BTHD outputs with one trailing-axis expand (the
    ring-merge shape)."""
    window = _check_window(window)
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    g = _group_size(q, k)
    Hkv = H // g
    block_q, block_kv = _check_blocks(Tq, Tkv, block_q, block_kv)
    s = _scale(q, scale)
    qh, kh, vh = _heads_first(q), _heads_first(k), _heads_first(v)
    qoff, kvoff = _offset_scalars(q_offset, kv_offset)
    # GQA: grid dim 0 runs over B*H query heads; each maps to its group's
    # KV head row — the kernel never materializes repeated KV.
    kv_row = _kv_row(H, Hkv, g)

    kernel = functools.partial(
        _flash_kernel,
        scale=s, causal=causal, block_q=block_q, block_kv=block_kv,
        window=window,
    )
    out, lse = pl.pallas_call(
        kernel,
        grid=(B * H, Tq // block_q, Tkv // block_kv),
        in_specs=[
            _smem_scalar_spec(pl, pltpu),
            _smem_scalar_spec(pl, pltpu),
            pl.BlockSpec(
                (1, block_q, D), lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_kv, D), lambda b, i, j: (kv_row(b), j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_kv, D), lambda b, i, j: (kv_row(b), j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=[
            pl.BlockSpec(
                (1, block_q, D), lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, block_q, 1), lambda b, i, j: (b, i, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        # batch·head and q-block revisits are independent; only the KV dim
        # carries the scratch state.  Declaring that lets Mosaic pipeline
        # the next (b, i)'s DMAs across the carried-dim boundary.
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qoff, kvoff, qh, kh, vh)
    out = jnp.swapaxes(out.reshape(B, H, Tq, D), 1, 2)
    return out, jnp.swapaxes(lse.reshape(B, H, Tq), 1, 2)


def _p_and_ds(
    qb, kb, vb, dob, lse_row, delta_row, i, j, q_base, kv_base,
    *, scale, causal, block_q, block_kv, window=None, apply_mask=True,
):
    """Shared backward recurrence for both gradient kernels:
    P_ij = exp(S_ij - LSE_i), dS_ij = P_ij ∘ (dO_i V_j^T - delta_i).
    ``delta_row`` is the *effective* delta — rowsum(dO ∘ O) minus the LSE
    cotangent when the caller differentiates through the (out, lse) pair
    (d lse_i / d S_ij = P_ij folds in as an additive term).
    ``apply_mask=False`` is the interior-block fast path (see
    :func:`_masked_scores`); callers gate it on
    :func:`_block_fully_valid`."""
    s = _masked_scores(
        qb, kb, i, j, q_base, kv_base,
        scale=scale, causal=causal, block_q=block_q, block_kv=block_kv,
        window=window, apply_mask=apply_mask,
    )
    p = jnp.exp(s - lse_row[:, None])  # [bq, bkv] f32
    dp = jax.lax.dot_general(
        dob, vb, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # [bq, bkv]
    ds = p * (dp - delta_row[:, None])  # f32
    return p, ds


def _flash_dkv_kernel(
    qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dk_ref, dv_ref, dk_scr, dv_scr,
    *, scale: float, causal: bool, block_q: int, block_kv: int,
    window=None,
):
    """dK/dV kernel: grid = (B*H, Tkv/block_kv, Tq/block_q), Q innermost;
    dK_j / dV_j accumulate in VMEM scratch across the Q sweep.

    FlashAttention-2 backward recurrence, P rebuilt from the forward LSE:
      P_ij  = exp(Q_i K_j^T * scale - LSE_i)
      dV_j += P_ij^T dO_i
      dS_ij = P_ij ∘ (dO_i V_j^T - delta_i)
      dK_j += scale * dS_ij^T Q_i
    """
    import jax.experimental.pallas as pl

    j = pl.program_id(1)
    i = pl.program_id(2)
    n_i = pl.num_programs(2)
    q_base, kv_base = qoff_ref[0], kvoff_ref[0]

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    should_run = _block_should_run(
        i, j, q_base, kv_base, causal=causal,
        block_q=block_q, block_kv=block_kv, window=window,
    )

    def _step(apply_mask):
        qb, kb, vb, dob = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        p, ds = _p_and_ds(
            qb, kb, vb, dob, lse_ref[0, :, 0], delta_ref[0, :, 0], i, j,
            q_base, kv_base,
            scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, window=window,
            apply_mask=apply_mask,
        )
        dv_scr[:] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bkv, D]
        dk_scr[:] += scale * jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [bkv, D]

    _dispatch_masked(
        pl, _step, should_run, i, j, q_base, kv_base,
        causal=causal, block_q=block_q, block_kv=block_kv, window=window,
    )

    @pl.when(i == n_i - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_dq_kernel(
    qoff_ref, kvoff_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dq_scr,
    *, scale: float, causal: bool, block_q: int, block_kv: int,
    window=None,
):
    """dQ kernel: grid = (B*H, Tq/block_q, Tkv/block_kv), KV innermost;
    dQ_i accumulates in VMEM scratch across the KV sweep:
      dQ_i += scale * dS_ij K_j   (dS as in :func:`_flash_dkv_kernel`)."""
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    n_j = pl.num_programs(2)
    q_base, kv_base = qoff_ref[0], kvoff_ref[0]

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    should_run = _block_should_run(
        i, j, q_base, kv_base, causal=causal,
        block_q=block_q, block_kv=block_kv, window=window,
    )

    def _step(apply_mask):
        qb, kb, vb, dob = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        _, ds = _p_and_ds(
            qb, kb, vb, dob, lse_ref[0, :, 0], delta_ref[0, :, 0], i, j,
            q_base, kv_base,
            scale=scale, causal=causal,
            block_q=block_q, block_kv=block_kv, window=window,
            apply_mask=apply_mask,
        )
        dq_scr[:] += scale * jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    _dispatch_masked(
        pl, _step, should_run, i, j, q_base, kv_base,
        causal=causal, block_q=block_q, block_kv=block_kv, window=window,
    )

    @pl.when(j == n_j - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_backward(
    q, k, v, out, lse, g, g_lse, *, causal, scale, block_q, block_kv,
    interpret, q_offset=0, kv_offset=0, window=None,
):
    """``lse`` here is the kernel-internal [B*H, Tq, 1] layout.  ``g_lse``
    (same layout) is the LSE cotangent of the (out, lse) pair — it folds
    into delta (see :func:`_p_and_ds`)."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, Tq, H, D = q.shape
    Tkv = k.shape[1]
    grp = _group_size(q, k)
    Hkv = H // grp
    block_q, block_kv = _check_blocks(Tq, Tkv, block_q, block_kv)
    s = _scale(q, scale)
    qh, kh, vh = _heads_first(q), _heads_first(k), _heads_first(v)
    doh = _heads_first(g)
    qoff, kvoff = _offset_scalars(q_offset, kv_offset)
    kv_row = _kv_row(H, Hkv, grp)
    # delta_i = rowsum(dO ∘ O) - dLSE_i: elementwise, XLA fuses it fine
    # outside.
    delta = jnp.sum(
        doh.astype(jnp.float32)
        * _heads_first(out).astype(jnp.float32),
        axis=-1,
        keepdims=True,
    ) - g_lse.astype(jnp.float32)  # [B*H, Tq, 1] f32

    qspec = lambda im: pl.BlockSpec(
        (1, block_q, D), im, memory_space=pltpu.VMEM
    )
    kvspec = lambda im: pl.BlockSpec(
        (1, block_kv, D), im, memory_space=pltpu.VMEM
    )
    # Per-row residuals (LSE, delta) carry a trailing unit lane dim so
    # the block's last two dims are (block_q, 1) — Mosaic-legal where a
    # [1, block_q] block is not (second-minor must divide by 8).
    rowspec = lambda im: pl.BlockSpec(
        (1, block_q, 1), im, memory_space=pltpu.VMEM
    )

    dkv_kernel = functools.partial(
        _flash_dkv_kernel,
        scale=s, causal=causal, block_q=block_q, block_kv=block_kv,
        window=window,
    )
    # GQA note: the kernel computes PER-QUERY-HEAD dK/dV ([B*H, Tkv, D])
    # — each query head reads its group's KV row but writes its own
    # gradient row, keeping grid dim 0 parallel (no cross-head output
    # revisiting); the group-sum down to H_kv heads happens outside.
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(B * H, Tkv // block_kv, Tq // block_q),
        in_specs=[
            _smem_scalar_spec(pl, pltpu),
            _smem_scalar_spec(pl, pltpu),
            qspec(lambda b, j, i: (b, i, 0)),
            kvspec(lambda b, j, i: (kv_row(b), j, 0)),
            kvspec(lambda b, j, i: (kv_row(b), j, 0)),
            qspec(lambda b, j, i: (b, i, 0)),
            rowspec(lambda b, j, i: (b, i, 0)),
            rowspec(lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            kvspec(lambda b, j, i: (b, j, 0)),
            kvspec(lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tkv, D), k.dtype),
            jax.ShapeDtypeStruct((B * H, Tkv, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_kv, D), jnp.float32),
            pltpu.VMEM((block_kv, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qoff, kvoff, qh, kh, vh, doh, lse, delta)
    dq_kernel = functools.partial(
        _flash_dq_kernel,
        scale=s, causal=causal, block_q=block_q, block_kv=block_kv,
        window=window,
    )
    dq = pl.pallas_call(
        dq_kernel,
        grid=(B * H, Tq // block_q, Tkv // block_kv),
        in_specs=[
            _smem_scalar_spec(pl, pltpu),
            _smem_scalar_spec(pl, pltpu),
            qspec(lambda b, i, j: (b, i, 0)),
            kvspec(lambda b, i, j: (kv_row(b), j, 0)),
            kvspec(lambda b, i, j: (kv_row(b), j, 0)),
            qspec(lambda b, i, j: (b, i, 0)),
            rowspec(lambda b, i, j: (b, i, 0)),
            rowspec(lambda b, i, j: (b, i, 0)),
        ],
        out_specs=qspec(lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qoff, kvoff, qh, kh, vh, doh, lse, delta)

    unflat = lambda x, nh, T: jnp.swapaxes(
        x.reshape(B, nh, T, D), 1, 2
    )
    if grp > 1:
        # Group-sum per-query-head KV grads down to the H_kv heads (in
        # f32: g bf16 addends lose bits exactly where GQA makes KV grads
        # g-way hotter).
        gsum = lambda x: x.astype(jnp.float32).reshape(
            B, Hkv, grp, Tkv, D
        ).sum(2).reshape(B * Hkv, Tkv, D)
        dk = gsum(dk).astype(k.dtype)
        dv = gsum(dv).astype(v.dtype)
    return (
        unflat(dq, H, Tq),
        unflat(dk, Hkv, Tkv),
        unflat(dv, Hkv, Tkv),
    )


def _lse_rows(lse):
    """[B, T, H] public LSE layout -> the kernels' [B*H, T, 1]."""
    B, T, H = lse.shape
    return jnp.swapaxes(lse, 1, 2).reshape(B * H, T, 1)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def flash_attention_chunk(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_offset: jax.Array = 0,
    kv_offset: jax.Array = 0,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Chunk-of-a-longer-sequence flash attention: returns ``(out, lse)``
    with lse ``[B, T, H]`` so a caller can exactly merge partial results
    from several KV chunks (the ring-attention inner step —
    :func:`...parallel.ring.ring_attention` with ``impl='flash'``, this
    function's one caller in the package).

    The forward is :func:`_flash_kernel`; the backward is the
    FlashAttention-2 pair (:func:`_flash_dkv_kernel` /
    :func:`_flash_dq_kernel`) rebuilding P from the saved LSE — the O(T²)
    score matrix is never materialized in either pass.  ``None`` tiles
    resolve per direction (:func:`_auto_block`, :func:`_auto_block_bwd`);
    explicit tiles apply to both.  ``interpret=True`` runs the same
    kernels on the CPU for tests.

    ``q_offset``/``kv_offset`` are the *global* positions of the first
    local row — dynamic (traced) values; causal masking happens in global
    coordinates inside the kernel.  Differentiable in q/k/v including
    through the lse output (the LSE cotangent folds into the backward's
    delta term).
    """
    return _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        q_offset=q_offset, kv_offset=kv_offset, window=window,
    )


def _flash_chunk_fwd(
    q, k, v, q_offset, kv_offset, causal, scale, block_q, block_kv,
    interpret, window,
):
    out, lse = _flash_forward(
        q, k, v, causal=causal, scale=scale,
        block_q=block_q, block_kv=block_kv, interpret=interpret,
        q_offset=q_offset, kv_offset=kv_offset, window=window,
    )
    return (out, lse), (q, k, v, out, lse, q_offset, kv_offset)


def _flash_chunk_bwd(
    causal, scale, block_q, block_kv, interpret, window, res, cotangents
):
    q, k, v, out, lse, q_offset, kv_offset = res
    g_out, g_lse = cotangents
    bq = block_q if block_q is not None else _auto_block_bwd(q.shape[1])
    bkv = (
        block_kv if block_kv is not None else _auto_block_bwd(k.shape[1])
    )
    dq, dk, dv = _flash_backward(
        q, k, v, out, _lse_rows(lse), g_out, _lse_rows(g_lse),
        causal=causal, scale=scale, block_q=bq, block_kv=bkv,
        interpret=interpret, q_offset=q_offset, kv_offset=kv_offset,
        window=window,
    )
    # Offsets are integer positions: no gradient.
    return dq, dk, dv, None, None


flash_attention_chunk.defvjp(_flash_chunk_fwd, _flash_chunk_bwd)


# ------------------------------------------------------- fused (auto on TPU)
#
# What ``attention(impl="auto")`` runs on the chip: one forward kernel and
# one backward kernel whose score tiles never leave VMEM.  They differ from
# the flash pair above in what the chip measurement of PERF.md (PR 26)
# asked for:
#
# - operands stay ``[B, T, H*D]`` (a free reshape of BTHD) and a grid step
#   takes a 128-lane column block of it: one head at D=128, two at D=64, so
#   every tile fills the lanes and no ``BTHD <-> (B*H, T, D)`` copy exists;
#   at D=64 a head is picked out of the pair by zeroing the other head's
#   lanes of one matmul operand (exact: the zeros add nothing to the f32
#   sums, and a 64-deep contraction fills the MXU no better) and the
#   products are joined by a lane select;
# - the grid's last axis walks a static list of (q block, kv block) pairs,
#   prefetched as scalars: the blocks a causal mask rules out are not grid
#   steps at all, so nothing is fetched or skipped for them;
# - the backward is ONE kernel in the transposed orientation
#   (S^T = K Q^T, so LSE and delta are lane-dense rows and dV, dK are plain
#   products): five matmuls a pair where the dKV/dQ pair needs seven, dQ
#   accumulated in a VMEM-resident ``[T, 128]`` f32 buffer per head block;
# - tiles of 512 (measured; the flash pair's backward runs at 128);
# - latent attention (MLA: 192 query/key channels a head, 128 value
#   channels) runs the same two kernels: a head is one 128-lane value
#   block, and its query/key block is as many whole lane blocks as the
#   channels need (256 for 192; ``attention`` pads with zeros, which add
#   nothing to a score).  The scores, dK and dQ products then run 256 deep
#   where the mathematics needs 192: 1408 where 1152 multiply-adds a score
#   would do, 22% more, none of it more than a 128-wide MXU spends on a
#   192-deep contraction anyway.
#
# Same mathematics as ``_block_update``/``_masked_scores``: scores from the
# input dtype with f32 accumulation, the scale in f32 after the product,
# (m, l, acc) in f32, P cast to the value dtype for P.V, exact exp and
# division.  f32 inputs keep f32 products.
#
# What the forward rule hands the backward one beside the inputs is what
# the forward kernel writes (:func:`fused_attention_results`: the output,
# and the log-sum-exp at 4 bytes a head and token; nothing the size of a
# score).  A caller that recomputes the call's surroundings keeps those
# two under a name (``attention``'s ``keep``, ``fused_attention``'s
# ``kept_as``; ``models/remat.py::kept_core``) and makes ``q``, ``k``,
# ``v`` again: the recomputed copy of the forward ``pallas_call`` then has
# every result kept and is gone from the differentiated program, which
# holds the forward kernel once (ISSUE 47).  Nothing in the kernels knows.

_LANES = 128
_FUSED_TILES = (512, 256, 128)  # largest the length divides, measured first
_FUSED_VMEM_BYTES = 64 * 1024 * 1024  # of v5e's 128 MiB; tiles need ~10 MiB


def _fused_tile(T: int) -> Optional[int]:
    """The largest tile the length divides, under a window too: at 8,192
    positions and 40 heads a window of 512 took 7.37 ms forward and
    backward at tiles of 512 (2 tiles a query row, half of them masked),
    9.49 at 256 (3 a row) and 18.93 at 128 (5 a row), the full causal
    layer 20.83 (my chip run, PR 44): the steps cost more than the masked
    halves."""
    return next((t for t in _FUSED_TILES if T % t == 0), None)


def fused_admissible(
    q, k, v, *, window: Optional[int] = None,
    q_offset: int | jax.Array = 0, kv_offset: int | jax.Array = 0,
    causal: bool = True,
) -> bool:
    """Whether the fused route takes this call: self-attention shapes
    (grouped KV heads too: ``attention`` repeats them over their groups
    before the kernels, which want equal head counts), head size 64 (an
    even number of heads) or 128, or 128 value channels under another
    query/key width (latent and differential attention; ``attention`` pads
    those), a length some tile divides, static zero offsets; a sliding
    window only with the causal mask (the caller's: the kernels drop the
    block pairs outside it).  All of it is visible at trace time; the
    backend is the caller's question."""
    if window is not None and not causal:
        return False
    if not (isinstance(q_offset, int) and isinstance(kv_offset, int)):
        return False
    if q_offset != 0 or kv_offset != 0:
        return False
    B, T, H, D = q.shape
    if not (
        k.shape == (B, T, k.shape[2], D) and H % k.shape[2] == 0
        and k.shape[:3] == v.shape[:3] and q.dtype == k.dtype == v.dtype
    ):
        return False
    if D != v.shape[-1]:
        if v.shape[-1] != _LANES:
            return False
    elif D not in (64, _LANES) or (H * D) % _LANES:
        return False
    return _fused_tile(T) is not None


def _fused_pairs(n_q, n_kv, block_q, block_kv, causal, kv_major, window=None):
    """The (q block, kv block) pairs in which some query sees some key,
    as two int32 vectors: q-major for the forward (a q block's kv blocks
    are consecutive, ascending), kv-major for the backward.  Under a
    window the pairs wholly older than it are left out as the pairs above
    the diagonal are: :func:`_block_should_run`'s test, on numbers."""
    pairs = [
        (i, j) for i in range(n_q) for j in range(n_kv)
        if _block_should_run(
            i, j, 0, 0, causal=causal, block_q=block_q, block_kv=block_kv,
            window=window,
        )
    ]
    if kv_major:
        pairs.sort(key=lambda ij: (ij[1], ij[0]))
    return (
        np.asarray([i for i, _ in pairs], np.int32),
        np.asarray([j for _, j in pairs], np.int32),
    )


def _head_lanes(rows: int, D: int, a: int):
    """Lanes of head ``a`` in a 128-lane block that holds 128/D heads."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    return (lane >= a * D) & (lane < (a + 1) * D)


def _join_heads(parts, D):
    """Per-head ``[rows, 128]`` results, each valid in its own head's
    lanes, as one block."""
    out = parts[0]
    for a in range(1, len(parts)):
        out = jnp.where(_head_lanes(out.shape[0], D, a), parts[a], out)
    return out


def _fused_fwd_kernel(
    i_ref, j_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, scale, causal, block_q, block_kv, head_dim, n_kv, window=None,
):
    """Grid (B, H*D/128, pairs).  ``m_scr``/``l_scr`` hold one
    lane-replicated ``[block_q, 128]`` row statistic per head of the
    block; LSE leaves as lane-dense rows ``[heads, block_q]``.  Under a
    ``window`` a q block's first kv block is the oldest that some query
    of it still sees (:func:`_fused_pairs` lists no older one)."""
    import jax.experimental.pallas as pl

    hp = _LANES // head_dim
    p_idx = pl.program_id(2)
    i, j = i_ref[p_idx], j_ref[p_idx]
    j_first = 0
    if window is not None:
        j_first = jnp.maximum(i * block_q - window + 1, 0) // block_kv

    @pl.when(j == j_first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _step(apply_mask):
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        alphas, pvs = [], []
        for a in range(hp):
            qa = q
            if hp > 1:
                qa = jnp.where(_head_lanes(block_q, head_dim, a), q, 0)
            s = _masked_scores(
                qa, k, i, j, 0, 0, scale=scale, causal=causal,
                block_q=block_q, block_kv=block_kv, apply_mask=apply_mask,
                window=window,
            )  # [bq, bkv] f32
            m_prev, l_prev = m_scr[a], l_scr[a]  # [bq, 128], replicated
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - jnp.tile(m_new, (1, block_kv // _LANES)))
            l_scr[a] = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            m_scr[a] = m_new
            alphas.append(alpha)
            pvs.append(jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # [bq, 128]; head a's lanes are its P.V
        acc_scr[...] = (
            _join_heads(alphas, head_dim) * acc_scr[...]
            + _join_heads(pvs, head_dim)
        )

    _dispatch_masked(
        pl, _step, True, i, j, 0, 0,
        causal=causal, block_q=block_q, block_kv=block_kv, window=window,
    )
    j_last = n_kv - 1
    if causal:
        j_last = jnp.minimum(j_last, ((i + 1) * block_q - 1) // block_kv)

    @pl.when(j == j_last)
    def _finish():
        ls = [jnp.maximum(l_scr[a], 1e-30) for a in range(hp)]
        o_ref[0] = (acc_scr[...] / _join_heads(ls, head_dim)).astype(
            o_ref.dtype
        )
        for a in range(hp):
            lse = m_scr[a] + jnp.log(ls[a])  # [bq, 128], replicated
            lse_ref[0, 0, a:a + 1, :] = lse.T[:1, :]


def _fused_bwd_kernel(
    i_ref, j_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr,
    *, scale, causal, block_q, block_kv, head_dim, n_q, n_pairs, window=None,
):
    """Grid (B, H*D/128, pairs), kv-major.  Transposed orientation:
      S^T = K Q^T * scale,  P^T = exp(S^T - LSE),  dP^T = V dO^T,
      dS^T = P^T o (dP^T - delta),
      dV_j += P^T dO,  dK_j += dS^T Q,  dQ_i += dS K   (scale at the end).
    dK/dV accumulate over a kv block's q sweep; dQ over the whole pair
    list, in ``dq_scr`` ``[T, 128]``.  Under a ``window`` a kv block's
    sweep ends at the last q block that still sees it."""
    import jax.experimental.pallas as pl

    hp = _LANES // head_dim
    p_idx = pl.program_id(2)
    i, j = i_ref[p_idx], j_ref[p_idx]
    i_first = (j * block_kv) // block_q if causal else 0
    i_last = n_q - 1
    if window is not None:
        i_last = jnp.minimum(i_last, (window + (j + 1) * block_kv - 2) // block_q)

    @pl.when(p_idx == 0)
    def _init_dq():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(i == i_first)
    def _init_dkv():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _step(apply_mask):
        q, k, v, do = q_ref[0], k_ref[0], v_ref[0], do_ref[0]
        lse, delta = lse_ref[0, 0], delta_ref[0, 0]  # [hp, bq]
        dvs, dks, dqs = [], [], []
        for a in range(hp):
            ka, va = k, v
            if hp > 1:
                mine = _head_lanes(block_kv, head_dim, a)
                ka, va = jnp.where(mine, k, 0), jnp.where(mine, v, 0)
            st = jax.lax.dot_general(
                ka, q, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale  # [bkv, bq] f32
            if causal and apply_mask:
                kj = j * block_kv + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 0
                )
                qi = i * block_q + jax.lax.broadcasted_iota(
                    jnp.int32, st.shape, 1
                )
                valid = qi >= kj
                if window is not None:
                    valid = valid & (qi - kj < window)
                st = jnp.where(valid, st, NEG_INF)
            pt = jnp.exp(st - lse[a:a + 1, :])
            dpt = jax.lax.dot_general(
                va, do, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            dst = pt * (dpt - delta[a:a + 1, :])
            dvs.append(jax.lax.dot_general(
                pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # [bkv, 128]
            dks.append(jax.lax.dot_general(
                dst.astype(q.dtype), q, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # [bkv, 128]
            dqs.append(jax.lax.dot_general(
                dst.T.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))  # [bq, 128]
        dv_scr[...] += _join_heads(dvs, head_dim)
        dk_scr[...] += _join_heads(dks, head_dim)
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_scr[rows, :] += _join_heads(dqs, head_dim)

    _dispatch_masked(
        pl, _step, True, i, j, 0, 0,
        causal=causal, block_q=block_q, block_kv=block_kv, window=window,
    )

    @pl.when(i == i_last)
    def _finish_dkv():
        dk_ref[0] = (scale * dk_scr[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(p_idx == n_pairs - 1)
    def _finish_dq():
        dq_ref[0] = (scale * dq_scr[...]).astype(dq_ref.dtype)


def _fused_geometry(q, v, block_q, block_kv):
    """``(B, T, H, D, hp, HB, QL, block_q, block_kv)``: ``D`` the value
    width of a head, ``hp`` heads in each of the ``HB`` 128-lane value
    blocks, ``QL`` the lanes of a head block's queries and keys (128, or
    the query/key width where it is another than the values')."""
    B, T, H, D = v.shape
    if q.shape[-1] != D and (D != _LANES or q.shape[-1] % _LANES):
        raise ValueError(
            f"fused attention: {q.shape[-1]} query/key channels against "
            f"{D} value channels; another width than the values' has to "
            f"be whole blocks of {_LANES} lanes over {_LANES} value "
            "channels (attention() pads)"
        )
    tile = _fused_tile(T)
    block_q = block_q if block_q is not None else tile
    block_kv = block_kv if block_kv is not None else tile
    if (
        block_q is None or T % block_q or T % block_kv
        or block_kv % _LANES or block_q % _LANES
    ):
        raise ValueError(
            f"fused attention: length {T} against tiles "
            f"({block_q}, {block_kv}); tiles are multiples of {_LANES} "
            "that divide the length"
        )
    hp = _LANES // D
    return B, T, H, D, hp, (H * D) // _LANES, q.shape[-1] * hp, block_q, block_kv


def _vma(x):
    """The varying mesh axes of ``x`` inside ``shard_map`` (empty outside):
    a ``pallas_call``'s outputs take theirs from ``out_shape``."""
    return getattr(jax.typeof(x), "vma", None) or frozenset()


def _fused_specs(pl, block_q, block_kv, hp, ql):
    """Block specs over the grid (batch, head block, pair), the pair's
    (q block, kv block) read from the two prefetched vectors: a
    ``[block_q, ql]`` tile of the ``[B, T, H*Dqk]`` queries, the same for
    keys, ``[block_kv, 128]`` of the values, ``[block_q, 128]`` of the
    output, and the ``[heads, block_q]`` rows of LSE or delta."""

    def at(shape, index):
        return pl.BlockSpec(
            shape, lambda b, h, p, ii, jj: index(b, h, ii[p], jj[p])
        )

    return (
        at((1, block_q, ql), lambda b, h, i, j: (b, i, h)),
        at((1, block_kv, ql), lambda b, h, i, j: (b, j, h)),
        at((1, block_kv, _LANES), lambda b, h, i, j: (b, j, h)),
        at((1, block_q, _LANES), lambda b, h, i, j: (b, i, h)),
        at((1, 1, hp, block_q), lambda b, h, i, j: (b, h, 0, i)),
    )


def fused_attention_results(q, v):
    """The shapes of what the forward kernel writes, and its ``out_shape``:
    the output ``[B, T, H D]`` and the scores' log-sum-exp ``[B, H D /
    128, 128 / D, T]`` float32.  What the backward kernel needs beside the
    inputs and the output's cotangent, and so what a caller that
    recomputes the call's surroundings keeps to have no forward kernel in
    its backward pass."""
    B, T, H, D = v.shape
    like = functools.partial(jax.ShapeDtypeStruct, vma=_vma(q))
    return (
        like((B, T, H * D), q.dtype),
        like((B, H * D // _LANES, _LANES // D, T), jnp.float32),
    )


def _fused_forward(
    q, k, v, *, causal, scale, block_q, block_kv, interpret, window=None
):
    """Returns ``(out [B,T,H,D], lse [B, H*D/128, 128/D, T] f32)``."""
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D, hp, HB, ql, block_q, block_kv = _fused_geometry(
        q, v, block_q, block_kv
    )
    n_q, n_kv = T // block_q, T // block_kv
    i_idx, j_idx = _fused_pairs(n_q, n_kv, block_q, block_kv, causal, False, window)
    flat = lambda x: x.reshape(B, T, -1)
    qspec, kspec, vspec, ospec, rowspec = _fused_specs(
        pl, block_q, block_kv, hp, ql
    )
    out, lse = pl.pallas_call(
        functools.partial(
            _fused_fwd_kernel, scale=_scale(q, scale), causal=causal,
            block_q=block_q, block_kv=block_kv, head_dim=D, n_kv=n_kv,
            window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, HB, len(i_idx)),
            in_specs=[qspec, kspec, vspec],
            out_specs=[ospec, rowspec],
            scratch_shapes=[
                pltpu.VMEM((hp, block_q, _LANES), jnp.float32),
                pltpu.VMEM((hp, block_q, _LANES), jnp.float32),
                pltpu.VMEM((block_q, _LANES), jnp.float32),
            ],
        ),
        out_shape=list(fused_attention_results(q, v)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FUSED_VMEM_BYTES,
        ),
        interpret=interpret,
    )(i_idx, j_idx, flat(q), flat(k), flat(v))
    return out.reshape(B, T, H, D), lse


def _fused_backward(
    q, k, v, out, lse, g, *, causal, scale, block_q, block_kv, interpret,
    window=None,
):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, T, H, D, hp, HB, ql, block_q, block_kv = _fused_geometry(
        q, v, block_q, block_kv
    )
    n_q, n_kv = T // block_q, T // block_kv
    i_idx, j_idx = _fused_pairs(n_q, n_kv, block_q, block_kv, causal, True, window)
    flat = lambda x: x.reshape(B, T, -1)
    # delta_i = rowsum(dO o O), as lane-dense rows beside the LSE's.
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1
    )  # [B, T, H]
    delta = jnp.swapaxes(delta, 1, 2).reshape(B, HB, hp, T)
    qspec, kspec, vspec, ospec, rowspec = _fused_specs(
        pl, block_q, block_kv, hp, ql
    )
    vma = _vma(q)
    qk_shape = jax.ShapeDtypeStruct((B, T, HB * ql), q.dtype, vma=vma)
    v_shape = jax.ShapeDtypeStruct((B, T, H * D), q.dtype, vma=vma)
    dq, dk, dv = pl.pallas_call(
        functools.partial(
            _fused_bwd_kernel, scale=_scale(q, scale), causal=causal,
            block_q=block_q, block_kv=block_kv, head_dim=D, n_q=n_q,
            n_pairs=len(i_idx), window=window,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, HB, len(i_idx)),
            in_specs=[qspec, kspec, vspec, ospec, rowspec, rowspec],
            out_specs=[
                # dQ of the whole head block stays resident; written once.
                pl.BlockSpec(
                    (1, T, ql), lambda b, h, p, ii, jj: (b, 0, h)
                ),
                kspec,
                vspec,
            ],
            scratch_shapes=[
                pltpu.VMEM((T, ql), jnp.float32),
                pltpu.VMEM((block_kv, ql), jnp.float32),
                pltpu.VMEM((block_kv, _LANES), jnp.float32),
            ],
        ),
        out_shape=[qk_shape, qk_shape, v_shape],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_FUSED_VMEM_BYTES,
        ),
        interpret=interpret,
    )(i_idx, j_idx, flat(q), flat(k), flat(v), flat(g), lse, delta)
    unflat = lambda x: x.reshape(B, T, H, -1)
    return unflat(dq), unflat(dk), unflat(dv)


def mosaic_can_lower() -> bool:
    """Whether a Mosaic kernel traced here will lower: such a kernel
    cannot be partitioned automatically, so its program has to span one
    device, or the call has to sit inside a ``shard_map`` that makes every
    mesh axis manual (Ulysses, the pipeline stages).  Under plain ``jit``
    the devices a program will span are its arguments' and not visible at
    trace time; what is visible is a process that has only one."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return set(mesh.manual_axes) == set(mesh.axis_names)


def auto_route(
    q, k, v, *, window: Optional[int] = None,
    q_offset: int | jax.Array = 0, kv_offset: int | jax.Array = 0,
    causal: bool = True,
) -> str:
    """What ``attention(impl="auto")`` runs for this call: ``"fused"`` on
    a TPU for the calls the fused kernels admit, where a Mosaic kernel
    can lower; else ``"blockwise"``."""
    if (
        jax.default_backend() == "tpu"
        and fused_admissible(
            q, k, v, window=window, q_offset=q_offset, kv_offset=kv_offset,
            causal=causal,
        )
        and mosaic_can_lower()
    ):
        return "fused"
    return "blockwise"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def fused_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_kv: Optional[int] = None,
    interpret: bool = False,
    window: Optional[int] = None,
    kept_as: Optional[str] = None,
) -> jax.Array:
    """The fused self-attention kernels (see the section comment), BTHD
    in and out; what ``attention(impl="auto")`` runs on a TPU for the
    calls :func:`fused_admissible` admits.  ``None`` tiles resolve to the
    largest of 512/256/128 the length divides;
    ``window`` (with ``causal``): a query sees the last ``window``
    positions, itself among them, and the block pairs wholly outside that
    are never run; ``interpret=True`` runs the same kernels on the CPU for
    tests.  ``kept_as``: the ``checkpoint_name`` the forward rule gives
    :func:`fused_attention_results`, None for none (:func:`attention`'s
    ``keep``)."""
    return _fused_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret, window=window,
    )[0]


def _fused_fwd(q, k, v, causal, scale, block_q, block_kv, interpret, window, kept_as):
    out, lse = _fused_forward(
        q, k, v, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret, window=window,
    )
    if kept_as is not None:
        out, lse = checkpoint_name(out, kept_as), checkpoint_name(lse, kept_as)
    return out, (q, k, v, out, lse)


def _fused_bwd(causal, scale, block_q, block_kv, interpret, window, kept_as, res, g):
    del kept_as  # the forward rule's
    q, k, v, out, lse = res
    return _fused_backward(
        q, k, v, out, lse, g, causal=causal, scale=scale, block_q=block_q,
        block_kv=block_kv, interpret=interpret, window=window,
    )


fused_attention.defvjp(_fused_fwd, _fused_bwd)


# Scope, not module: the core is a function, so flax names no part of it.
# Every route (reference, blockwise, fused) sits under the one name; the
# q/k/v/out projections stay outside.
@jax.named_scope(ATTENTION_CORE_SCOPE)
def attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    impl: str = "auto",
    window: Optional[int] = None,
    keep=lambda results: None,
) -> jax.Array:
    """Dispatching entry point: ``impl`` in {auto, reference, blockwise}.

    ``auto`` chooses from what the call can observe, at trace time: on a
    TPU, a call that :func:`fused_admissible` admits (self-attention
    shapes, head size 64 or 128, a length 128 divides, a sliding window
    under the causal mask alone) runs the fused kernels
    (:func:`fused_attention`; measured on the chip, PERF.md PR 26),
    grouped key/value heads repeated over their groups first; every other
    call (the CPU, odd lengths, and a ``jit`` over several devices outside
    ``shard_map``, where a Mosaic kernel cannot be partitioned) runs
    :func:`blockwise_attention`.  The choice is counted once per traced
    call (``attention/route_fused`` / ``attention/route_blockwise``).  A
    named ``impl`` means what it says.  A call with a ``window`` runs
    under ``swa_core`` inside the core's scope, whatever the route.

    ``keep`` is shown the shapes of :func:`fused_attention_results` where
    the fused kernels run and says under which ``checkpoint_name`` their
    forward rule hands them on, or None (the default) for as they are: a
    caller whose ``jax.checkpoint`` saves that name
    (``models/remat.py::kept_core``) holds the forward kernel once in its
    differentiated program and not twice.  It is asked here, where the
    caller is being traced: the rule is traced when the call is
    differentiated, after the caller's function has returned.  The other
    routes have no rule of their own whose residual could be named and do
    not ask."""
    windowed = contextlib.nullcontext() if window is None else jax.named_scope(SWA_CORE_SCOPE)
    with windowed:
        return _attention(q, k, v, causal, scale, impl, window, keep)


def _attention(q, k, v, causal, scale, impl, window, keep):
    if impl == "auto":
        impl = auto_route(q, k, v, window=window, causal=causal)
        get_registry().counter(
            ATTN_ROUTE_FUSED if impl == "fused" else ATTN_ROUTE_BLOCKWISE
        ).inc()
        if impl == "fused":
            k, v = _expand_kv(q, k, v)
            if q.shape[-1] != v.shape[-1]:
                # Latent attention: zero channels up to whole lane blocks.
                scale = _scale(q, scale)
                widen = ((0, 0),) * 3 + ((0, -q.shape[-1] % _LANES),)
                q, k = jnp.pad(q, widen), jnp.pad(k, widen)
            kept_as = keep(fused_attention_results(q, v))
            return fused_attention(q, k, v, causal, scale, window=window, kept_as=kept_as)
    if impl == "reference":
        return reference_attention(
            q, k, v, causal=causal, scale=scale, window=window
        )
    if impl == "blockwise":
        return blockwise_attention(
            q, k, v, causal=causal, scale=scale, window=window
        )
    raise ValueError(f"unknown attention impl {impl!r}")
