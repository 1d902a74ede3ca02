"""Implicit-GEMM 2-D convolution as a Pallas TPU kernel.

Why a third lowering exists (beside ``impl="xla"`` and ``impl="patches"``,
ops/conv.py): the conv models are the reference's headline benchmarks
(SURVEY.md §2.1 R3-R7).  ``patches`` keeps the program matmul-shaped but
materializes the im2col tensor — a kh*kw-fold HBM blow-up that capped
ResNet-50 near 4% MFU (round 3, old access layer, not re-measured).
This module computes the same contraction *inside* a Pallas kernel: the
input tile is DMA'd to VMEM once, the kh*kw shifted windows are read from
VMEM (free), and the only HBM traffic is one read of x, one read of the
kernel per output-channel tile, and one write of y — the implicit-GEMM
scheme every native conv engine uses on a systolic array, here built
directly on the MXU.

Structure:

- ``_core`` — stride-1 VALID conv ``[B,Hp,Wp,Cin] x [kh,kw,Cin,Cout]``,
  the only Pallas entry point.  Grid ``(B/bb, OH/boh, Cout/bco)``; each
  step manually DMAs a ``[bb, boh+kh-1, Wp, Cin]`` halo slab (overlapping
  row windows are inexpressible as BlockSpec tiles), then accumulates
  kh*kw MXU matmuls ``[bb*boh*OW, Cin] @ [Cin, bco]`` in f32.
- strides are decomposed OUTSIDE the kernel into a sum of s_h*s_w
  decimated stride-1 convs (``y = sum_pq core(x[p::s, q::s], k[p::s,
  q::s])``) — exact, zero wasted FLOPs, and the surrounding HLO is only
  strided-slice/pad/add.
- 1x1 convs skip Pallas entirely: after decimation they ARE a single
  ``dot_general`` (the patches 1x1 path, which has no blow-up).
- low-utilization input channels fall back to ``patches``: the kernel's
  explicit cin→128 lane pad makes the MXU contraction pay
  ``ceil(cin/128)·128/cin``× zero-column MACs, so routing is by estimated
  lane utilization (``_use_mxu_kernel``; < 50% → patches, whose im2col
  concat lifts K to kh*kw*Cin with one pad for the whole concat) — the
  RGB stem and every cin < 64 class route to patches.
- ``custom_vjp``: dx re-enters the same kernel on the (kh-1,kw-1)-padded
  cotangent with the spatially-rotated, IO-transposed kernel; dw is kh*kw
  plain window-slice dots (weight-sized outputs — no large intermediate).
  Everything outside ``_core`` (padding, phase slices, sums) is plain
  differentiable jnp, so autodiff composes.

Numerics: pinned against ``lax.conv_general_dilated`` in
tests/test_conv_mxu.py (fwd + grads, every shape class in the model zoo).
"""

from __future__ import annotations

import functools
import os
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax

from .conv import _explicit_padding, conv2d_patches

Padding = Union[str, Sequence[tuple[int, int]]]

# Minimum useful-lane fraction for the Pallas route.  The kernel's
# explicit cin→128 lane padding (_core_fwd_impl) means the MXU contraction
# always runs at ceil(cin/128)*128 lanes: at cin=16 that is 8× zero-column
# MACs, at cin=64 exactly 2×.  The im2col path pays no such per-tap waste
# (its K dim is kh*kw*cin, one lane pad for the whole concat) but blows up
# HBM traffic kh*kw-fold, so the Pallas route stays the winner down to 50%
# utilization and loses below it — route on the estimated waste ratio, not
# a bare cin threshold (round-5 advisor: the old _MIN_CIN=16 floor sent
# 16 ≤ cin < 64 classes to the kernel at up to 8× wasted MACs).
_MXU_MIN_LANE_UTIL = 0.5
_LANES = 128
# VMEM budget for the manually-DMA'd input slab (bytes).  Conservative:
# the auto-pipelined kernel/output blocks and the f32 accumulator share
# the ~16 MiB VMEM with it.
_SLAB_BUDGET = 4 * 1024 * 1024
# Target rows for the GEMM M dimension per grid step.
_M_TARGET = 1024
# Whole-kernel VMEM budget for the tile search (bytes).  v5e has 16 MiB;
# leave headroom for Mosaic's own spills.  Calibrated empirically with
# the chipless r5 compile sweep: estimates ≤10.1 MiB all compile, the
# 11.4 MiB dx class (128,11,16,512)x(3,3,512,512) still OOMs — the
# budget sits between those observations.
_VMEM_BUDGET = int(10.5 * 1024 * 1024)


def _divisors_desc(n: int):
    out = [d for d in range(n, 0, -1) if n % d == 0]
    return out


def _vmem_estimate(bb, boh, bco, ow, wp, cin, kh, kw, itemsize, pipelined):
    """Upper-bound VMEM footprint of one grid step: the slab scratch
    (doubled when pipelined), the auto-pipelined kernel/output blocks
    (double-buffered by Pallas), and the stack transients the unrolled
    tap loop keeps live (the whole-slab load, one window, the f32
    accumulator plus one dot result).  Heuristic, but it separated the
    compiling from the OOMing shape classes exactly on hardware."""
    rows = boh + kh - 1
    slab = (2 if pipelined else 1) * bb * rows * wp * cin * itemsize
    kblk = 2 * kh * kw * cin * bco * itemsize
    oblk = 2 * bb * boh * ow * bco * itemsize
    m = bb * boh * ow
    transients = (
        bb * rows * wp * cin * itemsize  # xs: the slab loaded as a value
        + m * cin * itemsize             # one shifted window
        + 2 * m * bco * 4                # f32 accumulator + dot output
    )
    return slab + kblk + oblk + transients


def _pick_tiles(b, oh, ow, wp, cin, cout, kh, itemsize,
                slab_budget=_SLAB_BUDGET, kw=None, pipelined=False):
    """(bb, boh, bco): batch-fold, output-row tile, out-channel tile.

    boh: largest divisor of OH whose halo slab fits ``slab_budget`` with
    M = boh*OW not far past the target.  bb: fold batch images into the
    GEMM M dim when one image's rows leave the MXU starved (deep 7x7
    feature maps).  bco: largest divisor of Cout <= 256.  The pipelined
    kernel passes a HALVED budget: it allocates two slabs, and the 4 MiB
    default is already conservative because the auto-pipelined
    kernel/output blocks and the f32 accumulator share VMEM with it.
    """
    boh = 1
    for d in _divisors_desc(oh):
        slab = (d + kh - 1) * wp * cin * itemsize
        if slab <= slab_budget and d * ow <= 2 * _M_TARGET:
            boh = d
            break
    bb = 1
    for d in _divisors_desc(b):
        slab = d * (boh + kh - 1) * wp * cin * itemsize
        if slab <= slab_budget and d * boh * ow <= 2 * _M_TARGET:
            bb = d
            break
    # Mosaic block rule: the block's last dim must be a multiple of 128
    # or equal the full array dim.  Inception-style channel counts (384,
    # 320, 448...) have divisors ≤256 that satisfy neither, so restrict
    # the search and fall back to channel-full blocks (always legal).
    bcos = [d for d in _divisors_desc(cout)
            if d <= 256 and (d % 128 == 0 or d == cout)] or [cout]
    bco = bcos[0]
    # Whole-step VMEM check: the slab/M caps alone let the cin=512
    # classes (ResNet-50 c5) assemble a 12.6 MiB step that OOMs VMEM on
    # hardware.  Shrink in cheapness order — bco first (same total HBM
    # traffic, just more j steps over the persistent slab), then bb,
    # then boh (both cut the GEMM M) — and take the first combo that
    # fits.
    kw_eff = kw if kw is not None else kh
    for cboh in [d for d in _divisors_desc(oh) if d <= boh]:
        for cbb in [d for d in _divisors_desc(b) if d <= bb]:
            for cbco in bcos:
                if _vmem_estimate(cbb, cboh, cbco, ow, wp, cin, kh,
                                  kw_eff, itemsize,
                                  pipelined) <= _VMEM_BUDGET:
                    return cbb, cboh, cbco
    return 1, 1, bcos[-1]


def _accumulate_taps(xs, k_ref, y_ref, *, kh, kw, bb, boh, ow, cin, bco):
    """The kh*kw implicit-GEMM contraction + output write, shared by the
    synchronous and pipelined kernels (one definition so the A/B arms
    cannot diverge in the math they compare)."""
    acc = jnp.zeros((bb * boh * ow, bco), jnp.float32)
    for dy in range(kh):
        for dx in range(kw):
            win = lax.slice(
                xs, (0, dy, dx, 0), (bb, dy + boh, dx + ow, cin)
            ).reshape(bb * boh * ow, cin)
            acc += lax.dot_general(
                win, k_ref[dy, dx], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
    y_ref[...] = acc.reshape(bb, boh, ow, bco).astype(y_ref.dtype)


def _core_kernel(x_hbm, k_ref, y_ref, slab, sem, *, kh, kw, bb, boh, ow,
                 cin, bco, interpreted):
    import jax.experimental.pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    rows = boh + kh - 1

    # One halo slab per (b, i); j only cycles output-channel tiles over
    # the same input rows, so on hardware copy on its first visit only
    # (Mosaic scratch persists across sequential grid steps).  The
    # interpreter reinitializes scratch per grid point, so there the copy
    # runs every step — same data, so numerics are identical.
    @pl.when(jnp.logical_or(j == 0, interpreted))
    def _copy():
        from jax.experimental.pallas import tpu as pltpu

        b0 = pl.program_id(0) * bb
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(b0, bb), pl.ds(i * boh, rows)], slab, sem
        )
        cp.start()
        cp.wait()

    _accumulate_taps(
        slab[...], k_ref, y_ref,
        kh=kh, kw=kw, bb=bb, boh=boh, ow=ow, cin=cin, bco=bco,
    )


def _core_kernel_pipelined(
    x_hbm, k_ref, y_ref, slab2, sem2, *, kh, kw, bb, boh, ow, cin, bco,
    n_b, n_i, interpreted,
):
    """Double-buffered variant of :func:`_core_kernel` (opt-in via
    DTM_CONV_MXU_PIPELINE): the halo-slab DMA for block N+1 is started
    right after block N's slab arrives, so the copy overlaps block N's
    n_j compute steps instead of stalling block N+1's first step.  The
    plain kernel's copy is synchronous (start+wait inline), which for
    small-Cout stages (n_j == 1, e.g. every ResNet stage-1 conv) puts a
    full slab DMA on the critical path of EVERY grid step.

    Costs/constraints: 2x slab VMEM; ALL grid dims must be "arbitrary"
    (cross-block prefetch assumes strict sequential order — fine on
    single-TensorCore v5e, surrenders Megacore splitting elsewhere).
    ``slab2``/``sem2`` carry a leading parity dim of 2; blocks alternate
    slots by linear block index.  Under the interpreter scratch does not
    persist across grid points, so interpreted mode degrades to the
    synchronous copy-every-step scheme — numerics identical, pipelining
    itself is Mosaic-only behavior (validated by the hardware canary
    before the A/B arm runs).
    """
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bq = pl.program_id(0)
    i = pl.program_id(1)
    j = pl.program_id(2)
    rows = boh + kh - 1
    blk = bq * n_i + i  # linear (b, i) block index; j cycles inside it
    parity = jax.lax.rem(blk, 2)

    def copy_for(tblk, slot):
        tb = tblk // n_i
        ti = jax.lax.rem(tblk, n_i)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(tb * bb, bb), pl.ds(ti * boh, rows)],
            slab2.at[slot],
            sem2.at[slot],
        )

    if interpreted:
        # Degraded interpreter scheme: synchronous copy every step into
        # this block's slot (scratch does not persist across steps).
        cp = copy_for(blk, parity)
        cp.start()
        cp.wait()
    else:
        # First block of the whole grid: nothing prefetched it.
        @pl.when(jnp.logical_and(blk == 0, j == 0))
        def _prime():
            copy_for(0, 0).start()

        @pl.when(j == 0)
        def _arrive_and_prefetch():
            copy_for(blk, parity).wait()

            @pl.when(blk + 1 < n_b * n_i)
            def _prefetch_next():
                copy_for(blk + 1, 1 - parity).start()

    _accumulate_taps(
        slab2[parity], k_ref, y_ref,
        kh=kh, kw=kw, bb=bb, boh=boh, ow=ow, cin=cin, bco=bco,
    )


def _pipeline_enabled() -> bool:
    """DTM_CONV_MXU_PIPELINE resolves at trace time (the DTM_CONV_IMPL
    contract: invalid values fail loudly naming the knob).  Default off
    — the synchronous kernel is the hardware-validated baseline; flip
    only with a banked A/B artifact (measured-defaults principle)."""
    env = os.environ.get("DTM_CONV_MXU_PIPELINE", "0")
    if env not in ("0", "1"):
        raise ValueError(
            f"DTM_CONV_MXU_PIPELINE must be '0' or '1', got {env!r}"
        )
    return env == "1"


def _core_fwd_impl(xpad, kernel, interpret):
    import jax.experimental.pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, hp, wp, cin = xpad.shape
    kh, kw, _, cout = kernel.shape
    oh = hp - kh + 1
    ow = wp - kw + 1
    # Mosaic DMA slices must be 8-aligned along the sublane (W) dim; pad
    # W up to a multiple of 8.  The extra zero columns sit past the last
    # window (ow is computed from the true wp above) and are never read
    # into any output.
    wp8 = -(-wp // 8) * 8
    if wp8 != wp:
        xpad = jnp.pad(xpad, ((0, 0), (0, 0), (0, wp8 - wp), (0, 0)))
        wp = wp8
    # Mosaic tiles the (W, C) minor dims as (8, 128) and physically pads
    # the lane dim, for HBM and VMEM memrefs alike — so the halo DMA's
    # memref_slice is rejected whenever cin % 128 != 0, even though the
    # slice only cuts batch/row dims (first hardware canary, r5: "Slice
    # shape along dimension 3 must be aligned to tiling (128), but is
    # 64").  Pad cin explicitly: HBM traffic is unchanged (the tiled
    # buffer already stores those lanes), only the MXU contraction pays
    # zero-column MACs, and only for sub-multiple channel counts.
    cin128 = -(-cin // 128) * 128
    if cin128 != cin:
        xpad = jnp.pad(xpad, ((0, 0), (0, 0), (0, 0), (0, cin128 - cin)))
        kernel = jnp.pad(
            kernel, ((0, 0), (0, 0), (0, cin128 - cin), (0, 0))
        )
        cin = cin128
    pipelined = _pipeline_enabled()
    bb, boh, bco = _pick_tiles(
        b, oh, ow, wp, cin, cout, kh, xpad.dtype.itemsize,
        # Two slabs must fit where one did.
        slab_budget=_SLAB_BUDGET // 2 if pipelined else _SLAB_BUDGET,
        kw=kw, pipelined=pipelined,
    )
    rows = boh + kh - 1
    if pipelined:
        body = functools.partial(
            _core_kernel_pipelined, kh=kh, kw=kw, bb=bb, boh=boh, ow=ow,
            cin=cin, bco=bco, n_b=b // bb, n_i=oh // boh,
            interpreted=bool(interpret),
        )
        scratch = [
            pltpu.VMEM((2, bb, rows, wp, cin), xpad.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ]
        # Cross-block prefetch assumes strict sequential grid order: ALL
        # dims arbitrary (see _core_kernel_pipelined docstring).
        semantics = ("arbitrary", "arbitrary", "arbitrary")
    else:
        body = functools.partial(
            _core_kernel, kh=kh, kw=kw, bb=bb, boh=boh, ow=ow, cin=cin,
            bco=bco, interpreted=bool(interpret),
        )
        scratch = [
            pltpu.VMEM((bb, rows, wp, cin), xpad.dtype),
            pltpu.SemaphoreType.DMA,
        ]
        # j must be "arbitrary": the j==0 slab copy feeds later j steps
        # through persistent scratch, so the channel-tile dim can be
        # neither reordered nor split across Megacore cores.  bq/i stay
        # parallel — a core slice along them always opens at j==0.
        semantics = ("parallel", "parallel", "arbitrary")
    if interpret:
        # The generic interpreter doesn't model ANY-space refs, DMA or
        # semaphores; the TPU-flavored interpreter does.
        interpret = pltpu.InterpretParams()
    return pl.pallas_call(
        body,
        grid=(b // bb, oh // boh, cout // bco),
        in_specs=[
            # HBM, not ANY: with ANY, a small-enough x gets placed in
            # VMEM with lane-padded tiling (cin 64 -> 128), and the halo
            # DMA's memref_slice then violates Mosaic's 128-alignment
            # rule even though the slice only cuts batch/row dims (first
            # hardware canary, r5: "Slice shape along dimension 3 must
            # be aligned to tiling (128), but is 64").  The kernel's
            # whole design assumes x streams from HBM anyway.
            pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            pl.BlockSpec(
                (kh, kw, cin, bco), lambda bq, i, j: (0, 0, 0, j),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (bb, boh, ow, bco), lambda bq, i, j: (bq, i, 0, j),
            memory_space=pltpu.VMEM,
        ),
        out_shape=jax.ShapeDtypeStruct((b, oh, ow, cout), xpad.dtype),
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=semantics
        ),
        interpret=interpret,
    )(xpad, kernel)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _core(xpad, kernel, interpret):
    """Stride-1 VALID conv, NHWC x HWIO, via the Pallas kernel."""
    return _core_fwd_impl(xpad, kernel, interpret)


def _core_fwd(xpad, kernel, interpret):
    return _core_fwd_impl(xpad, kernel, interpret), (xpad, kernel)


def _core_bwd(interpret, res, g):
    xpad, kernel = res
    kh, kw, cin, cout = kernel.shape
    _, oh, ow, _ = g.shape
    # dw: one weight-sized dot per tap — contraction over (B, OH, OW).
    taps = []
    for dy in range(kh):
        row = []
        for dx in range(kw):
            win = lax.slice(
                xpad, (0, dy, dx, 0),
                (xpad.shape[0], dy + oh, dx + ow, cin),
            )
            row.append(
                lax.dot_general(
                    win, g, (((0, 1, 2), (0, 1, 2)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
            )
        taps.append(jnp.stack(row))
    dw = jnp.stack(taps).astype(kernel.dtype)
    # dx: full correlation = the same stride-1 kernel on the
    # (kh-1, kw-1)-padded cotangent with the rotated, IO-swapped kernel.
    gp = jnp.pad(g, ((0, 0), (kh - 1, kh - 1), (kw - 1, kw - 1), (0, 0)))
    krot = kernel[::-1, ::-1].transpose(0, 1, 3, 2)
    # Re-enter _core (not the raw pallas_call) so the backward pass is
    # itself differentiable — higher-order autodiff re-uses this VJP.
    dx = _core(gp, krot, interpret)
    return dx, dw


_core.defvjp(_core_fwd, _core_bwd)


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _mxu_lane_utilization(cin: int) -> float:
    """Fraction of MXU lanes doing useful work after the kernel's cin→128
    pad: ``cin / (ceil(cin/128)*128)``.  1.0 at lane multiples; 0.5 at
    cin=64; 0.125 at cin=16."""
    return cin / (-(-cin // _LANES) * _LANES)


def _use_mxu_kernel(kh: int, kw: int, cin: int) -> bool:
    """Padding-aware Pallas-vs-patches routing.

    1×1 convs are a bare dot in the patches path (no im2col blow-up
    exists, nothing for the kernel to win).  Otherwise route to the
    Pallas kernel only when its post-pad lane utilization clears
    ``_MXU_MIN_LANE_UTIL`` — below that the zero-column MACs the cin→128
    pad buys exceed what the halo-slab scheme saves over im2col's
    kh·kw-fold HBM blow-up.
    """
    if kh == kw == 1:
        return False
    return _mxu_lane_utilization(cin) >= _MXU_MIN_LANE_UTIL


def conv2d_mxu(x, kernel, strides=(1, 1), padding: Padding = "SAME",
               interpret: Optional[bool] = None):
    """``lax.conv_general_dilated`` (NHWC, HWIO) semantics on the Pallas
    implicit-GEMM kernel.  ``interpret=None`` auto-selects interpret mode
    off-TPU (the kernel is Mosaic-only; CPU runs use the interpreter)."""
    if interpret is None:
        interpret = not _on_tpu()
    kh, kw, cin, cout = kernel.shape
    sh, sw = strides
    if x.shape[-1] != cin:
        raise ValueError(
            f"input channels {x.shape[-1]} != kernel input channels {cin}"
        )
    if not _use_mxu_kernel(kh, kw, cin):
        # 1x1 is already a bare dot in the patches path (no im2col
        # blow-up exists); low-utilization Cin (the cin→128 lane pad's
        # zero-column MACs) wants the im2col K-dim lift — see
        # _use_mxu_kernel.
        return conv2d_patches(x, kernel, strides, padding)
    (ph0, ph1), (pw0, pw1) = _explicit_padding(
        padding, kh, kw, sh, sw, x.shape[1], x.shape[2]
    )
    if ph0 or ph1 or pw0 or pw1:
        x = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    b, hp, wp, _ = x.shape
    oh = (hp - kh) // sh + 1
    ow = (wp - kw) // sw + 1
    if sh == 1 and sw == 1:
        return _core(x, kernel, interpret)
    # Phase decomposition: y = sum_{p,q} core(x[p::s], k[p::s]) — each
    # phase is an exact stride-1 conv on a decimated image; taps
    # partition over phases so total MACs equal the strided conv's.
    y = None
    for p in range(min(sh, kh)):
        khp = len(range(p, kh, sh))
        for q in range(min(sw, kw)):
            kwq = len(range(q, kw, sw))
            xs = lax.slice(
                x,
                (0, p, q, 0),
                (b, p + (oh + khp - 2) * sh + 1, q + (ow + kwq - 2) * sw + 1,
                 cin),
                (1, sh, sw, 1),
            )
            kp = kernel[p::sh, q::sw]
            yp = _core(xs, kp, interpret)
            y = yp if y is None else y + yp
    return y
