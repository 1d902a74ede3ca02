"""2-D convolution with a selectable lowering: native XLA conv or im2col.

Reference context: the conv models (SURVEY.md §2.1 R3-R7) are the
reference's headline benchmarks, and the standard lowering is XLA's
``convolution`` HLO (this repo's default, ``impl="xla"`` — it compiles and
runs on a TPU v5e at ResNet-50's published shape, ``chip_smoke.py``).  The
alternative ``impl="patches"`` lowering expresses the same conv with
matmul-class HLO only:

    pad -> kh*kw strided slices -> concat -> one dot_general

so the only FLOP-carrying op XLA sees is a single large matmul
``[B*OH*OW, kh*kw*Cin] @ [kh*kw*Cin, Cout]`` — the op the MXU natively
consumes (XLA's own conv lowering is an implicit GEMM over the same
contraction), at the price of materializing the kh*kw-fold im2col tensor.
Autodiff through slices/concat/dot produces pads, slices and matmuls —
still no conv HLO in the backward.  Whether it (and the Pallas ``mxu``
lowering) keeps a job beside ``xla`` is ROADMAP S2/D2's question.

Numerics: the two lowerings are contraction-order-identical up to float
summation order inside the dot; tests pin them to tight tolerances against
``lax.conv_general_dilated`` (tests/test_conv_impl.py).

The ``patches`` pooling twins (:func:`max_pool` / :func:`avg_pool`) replace
``reduce_window`` with the same shifted-slice trick folded elementwise —
used so a patches-mode model contains no windowed HLO at all.

Layouts are fixed to the repo convention: NHWC activations, HWIO kernels
(XLA's preferred TPU conv layout).  Parameter names/shapes match
``flax.linen.Conv`` (``kernel`` HWIO, ``bias``), so checkpoints are
interchangeable between impls and with plain flax modules.
"""

from __future__ import annotations

import os
from typing import Any, Optional, Sequence, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import dtypes as flax_dtypes
from jax import lax

Padding = Union[str, Sequence[tuple[int, int]]]

_VALID_IMPLS = ("xla", "patches", "mxu")

# Process-wide default used by impl="auto".  Read at *trace* time: two jits
# traced under different defaults produce different programs, so callers that
# flip it mid-process must not reuse previously-traced callables (tests
# build fresh functions).
_default_impl = os.environ.get("DTM_CONV_IMPL", "xla")


def set_default_conv_impl(impl: str) -> None:
    global _default_impl
    if impl not in _VALID_IMPLS:
        raise ValueError(f"conv impl must be one of {_VALID_IMPLS}, got {impl!r}")
    _default_impl = impl


def get_default_conv_impl() -> str:
    return _default_impl


def resolve_conv_impl(impl: str) -> str:
    if impl == "auto":
        # Re-validate here rather than at module import: the default may
        # come from the DTM_CONV_IMPL env var, and a typo there must fail
        # loudly instead of silently splitting conv/pool across lowerings.
        if _default_impl not in _VALID_IMPLS:
            raise ValueError(
                f"default conv impl (DTM_CONV_IMPL) must be one of "
                f"{_VALID_IMPLS}, got {_default_impl!r}"
            )
        return _default_impl
    if impl not in _VALID_IMPLS:
        raise ValueError(
            f"conv impl must be 'auto' or one of {_VALID_IMPLS}, got {impl!r}"
        )
    return impl


def _explicit_padding(
    padding: Padding, kh: int, kw: int, sh: int, sw: int, h: int, w: int
) -> tuple[tuple[int, int], tuple[int, int]]:
    """Resolve SAME/VALID/explicit padding to per-dim (low, high) pairs.

    SAME follows the TF/XLA definition: output size ceil(in/stride), total
    pad ``max((out-1)*stride + k - in, 0)`` split low-biased."""
    if isinstance(padding, str):
        p = padding.upper()
        if p == "VALID":
            return (0, 0), (0, 0)
        if p == "SAME":
            def same(in_sz, k, s):
                out = -(-in_sz // s)
                total = max((out - 1) * s + k - in_sz, 0)
                return (total // 2, total - total // 2)

            return same(h, kh, sh), same(w, kw, sw)
        raise ValueError(f"unknown padding {padding!r}")
    (ph0, ph1), (pw0, pw1) = padding
    return (int(ph0), int(ph1)), (int(pw0), int(pw1))


def _shifted_slices(x, kh: int, kw: int, sh: int, sw: int):
    """All kh*kw stride-decimated shifts of a padded NHWC tensor, row-major
    in (dy, dx) — the order a flattened HWIO kernel contracts in."""
    b, h, w, c = x.shape
    oh = (h - kh) // sh + 1
    ow = (w - kw) // sw + 1
    out = []
    for dy in range(kh):
        for dx in range(kw):
            out.append(
                lax.slice(
                    x,
                    (0, dy, dx, 0),
                    (b, dy + (oh - 1) * sh + 1, dx + (ow - 1) * sw + 1, c),
                    (1, sh, sw, 1),
                )
            )
    return out, oh, ow


def conv2d_patches(x, kernel, strides=(1, 1), padding: Padding = "SAME"):
    """``lax.conv_general_dilated`` (NHWC, HWIO) as pad+slices+one matmul."""
    kh, kw, cin, cout = kernel.shape
    sh, sw = strides
    if x.shape[-1] != cin:
        raise ValueError(
            f"input channels {x.shape[-1]} != kernel input channels {cin}"
        )
    (ph0, ph1), (pw0, pw1) = _explicit_padding(
        padding, kh, kw, sh, sw, x.shape[1], x.shape[2]
    )
    if ph0 or ph1 or pw0 or pw1:
        x = jnp.pad(x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)))
    if kh == kw == 1:
        # Degenerate im2col: the "patch" is the pixel itself.
        y = x[:, ::sh, ::sw, :]
        return lax.dot_general(
            y, kernel.reshape(cin, cout), (((3,), (0,)), ((), ()))
        )
    cols, _, _ = _shifted_slices(x, kh, kw, sh, sw)
    xcol = jnp.concatenate(cols, axis=-1)  # [B, OH, OW, kh*kw*cin]
    return lax.dot_general(
        xcol, kernel.reshape(kh * kw * cin, cout), (((3,), (0,)), ((), ()))
    )


def conv2d(x, kernel, strides=(1, 1), padding: Padding = "SAME",
           impl: str = "auto"):
    """NHWC x HWIO -> NHWC conv through the selected lowering."""
    impl = resolve_conv_impl(impl)
    if impl == "patches":
        return conv2d_patches(x, kernel, strides, padding)
    if impl == "mxu":
        # Pallas implicit-GEMM kernel (ops/conv_mxu.py): the same matmul
        # HLO class as patches but without the materialized im2col.
        # Deferred import: conv_mxu reuses this module's padding helpers.
        from .conv_mxu import conv2d_mxu

        return conv2d_mxu(x, kernel, strides, padding)
    if isinstance(padding, str):
        pad = padding.upper()
    else:
        pad = [tuple(p) for p in padding]
    return lax.conv_general_dilated(
        x,
        kernel,
        window_strides=strides,
        padding=pad,
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _pool(x, window, strides, padding: Padding, impl: str, kind: str):
    kh, kw = window
    sh, sw = strides
    impl = resolve_conv_impl(impl)
    # Pooling carries no matmul FLOPs, so "mxu" shares the patches
    # shifted-slice folds — the windowless lowering.
    if impl == "xla":
        if kind == "max":
            return nn.max_pool(x, window, strides=strides, padding=padding)
        return nn.avg_pool(x, window, strides=strides, padding=padding)
    (ph0, ph1), (pw0, pw1) = _explicit_padding(
        padding, kh, kw, sh, sw, x.shape[1], x.shape[2]
    )
    if ph0 or ph1 or pw0 or pw1:
        # -inf identity for max; zeros for avg (flax avg_pool divides by the
        # full window size including padding — count_include_pad semantics —
        # so zero-padding reproduces it exactly).
        fill = jnp.finfo(x.dtype).min if kind == "max" else 0
        x = jnp.pad(
            x, ((0, 0), (ph0, ph1), (pw0, pw1), (0, 0)),
            constant_values=fill,
        )
    cols, _, _ = _shifted_slices(x, kh, kw, sh, sw)
    acc = cols[0]
    for c in cols[1:]:
        acc = jnp.maximum(acc, c) if kind == "max" else acc + c
    if kind == "avg":
        acc = acc / (kh * kw)
    return acc


def max_pool(x, window, strides=None, padding: Padding = "VALID",
             impl: str = "auto"):
    """``flax.linen.max_pool`` semantics (omitted strides = (1, 1), as in
    flax) with a selectable lowering."""
    return _pool(x, window, strides or (1, 1), padding, impl, "max")


def avg_pool(x, window, strides=None, padding: Padding = "VALID",
             impl: str = "auto"):
    """``flax.linen.avg_pool`` semantics (count_include_pad; omitted
    strides = (1, 1), as in flax) with a selectable lowering."""
    return _pool(x, window, strides or (1, 1), padding, impl, "avg")


class Conv2D(nn.Module):
    """Drop-in for ``flax.linen.Conv`` (2-D, NHWC/HWIO) with an ``impl``
    knob selecting the lowering.

    Parameter names, shapes, initializers and dtype-promotion rules match
    ``nn.Conv`` so existing checkpoints load unchanged; ``impl`` is purely a
    compile-time lowering choice with pinned numerics."""

    features: int
    kernel_size: tuple[int, int]
    strides: Union[int, tuple[int, int]] = 1
    padding: Padding = "SAME"
    use_bias: bool = True
    dtype: Optional[Any] = None
    param_dtype: Any = jnp.float32
    kernel_init: Any = nn.initializers.lecun_normal()
    bias_init: Any = nn.initializers.zeros_init()
    impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        kh, kw = self.kernel_size
        strides = (
            (self.strides, self.strides)
            if isinstance(self.strides, int)
            else tuple(self.strides)
        )
        kernel = self.param(
            "kernel",
            self.kernel_init,
            (kh, kw, x.shape[-1], self.features),
            self.param_dtype,
        )
        bias = (
            self.param(
                "bias", self.bias_init, (self.features,), self.param_dtype
            )
            if self.use_bias
            else None
        )
        x, kernel, bias = flax_dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype
        )
        y = conv2d(x, kernel, strides, self.padding, impl=self.impl)
        if bias is not None:
            y = y + bias
        return y
