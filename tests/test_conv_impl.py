"""Pin the matmul-only ("patches") conv lowering to XLA's native conv.

The patches lowering (ops/conv.py) expresses a conv with matmul-class HLO
only.  These tests are the license to trust its numbers: forward,
backward, pooling, and whole-model equivalence against
``lax.conv_general_dilated`` / flax pooling on CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn
from jax import lax

from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.ops.conv import (
    Conv2D,
    avg_pool,
    conv2d,
    conv2d_patches,
    max_pool,
)


def _ref_conv(x, k, strides, padding):
    pad = padding if isinstance(padding, str) else [tuple(p) for p in padding]
    return lax.conv_general_dilated(
        x, k, strides, pad, dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


CASES = [
    # (H, W, Cin, Cout, kh, kw, sh, sw, padding)
    (8, 8, 3, 7, 3, 3, 1, 1, "SAME"),
    (9, 7, 4, 5, 3, 3, 2, 2, "SAME"),      # odd sizes, stride 2 SAME
    (8, 8, 3, 7, 3, 3, 1, 1, "VALID"),
    (11, 11, 2, 6, 5, 5, 2, 2, "VALID"),
    (8, 8, 5, 9, 1, 1, 1, 1, "SAME"),      # pointwise
    (8, 8, 5, 9, 1, 1, 2, 2, "SAME"),      # pointwise strided
    (12, 12, 3, 4, 7, 7, 2, 2, [(3, 3), (3, 3)]),  # resnet stem pattern
    (6, 10, 3, 4, 1, 7, 1, 1, "SAME"),     # inception 1x7 factorized
    (10, 6, 3, 4, 7, 1, 1, 1, "SAME"),     # inception 7x1
]


@pytest.mark.parametrize("case", CASES)
def test_patches_matches_lax_conv_fwd(case):
    h, w, cin, cout, kh, kw, sh, sw, pad = case
    kx, kk = jax.random.split(jax.random.PRNGKey(0))
    x = jax.random.normal(kx, (2, h, w, cin), jnp.float32)
    k = jax.random.normal(kk, (kh, kw, cin, cout), jnp.float32) * 0.1
    got = conv2d_patches(x, k, (sh, sw), pad)
    want = _ref_conv(x, k, (sh, sw), pad)
    assert got.shape == want.shape
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_patches_matches_lax_conv_grad():
    kx, kk = jax.random.split(jax.random.PRNGKey(1))
    x = jax.random.normal(kx, (2, 9, 9, 3), jnp.float32)
    k = jax.random.normal(kk, (3, 3, 3, 8), jnp.float32) * 0.1

    def loss(fn):
        return lambda x, k: jnp.sum(fn(x, k, (2, 2), "SAME") ** 2)

    gx_p, gk_p = jax.grad(loss(conv2d_patches), argnums=(0, 1))(x, k)
    gx_r, gk_r = jax.grad(loss(_ref_conv), argnums=(0, 1))(x, k)
    np.testing.assert_allclose(gx_p, gx_r, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(gk_p, gk_r, rtol=1e-5, atol=1e-5)


def test_patches_backward_contains_no_conv_hlo():
    """The whole point: neither forward nor backward may lower to a
    convolution HLO."""
    x = jnp.ones((2, 8, 8, 3), jnp.float32)
    k = jnp.ones((3, 3, 3, 4), jnp.float32)

    def f(x, k):
        return jnp.sum(conv2d_patches(x, k, (1, 1), "SAME") ** 2)

    text = jax.jit(jax.grad(f, argnums=(0, 1))).lower(x, k).as_text()
    assert "convolution" not in text
    assert "reduce-window" not in text


@pytest.mark.parametrize("kind", ["max", "avg"])
@pytest.mark.parametrize(
    "window,strides,padding",
    [((2, 2), (2, 2), "VALID"), ((3, 3), (2, 2), "VALID"),
     ((3, 3), (1, 1), "SAME"), ((3, 3), (2, 2), "SAME"),
     ((5, 5), (3, 3), "VALID")],
)
def test_pool_patches_matches_flax(kind, window, strides, padding):
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 11, 5), jnp.float32)
    ours = (max_pool if kind == "max" else avg_pool)(
        x, window, strides=strides, padding=padding, impl="patches"
    )
    ref = (nn.max_pool if kind == "max" else nn.avg_pool)(
        x, window, strides=strides, padding=padding
    )
    assert ours.shape == ref.shape
    np.testing.assert_allclose(ours, ref, rtol=1e-6, atol=1e-6)


def test_conv2d_module_param_compat_and_equivalence():
    """Conv2D(impl=...) produces nn.Conv-shaped params and both impls agree
    given the same params."""
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 8, 8, 3), jnp.float32)
    ref = nn.Conv(6, (3, 3), strides=(2, 2), padding="SAME")
    ref_params = ref.init(jax.random.PRNGKey(4), x)

    for impl in ("xla", "patches"):
        mod = Conv2D(6, (3, 3), strides=(2, 2), padding="SAME", impl=impl)
        own = mod.init(jax.random.PRNGKey(4), x)
        assert jax.tree.structure(own) == jax.tree.structure(ref_params)
        got = mod.apply(ref_params, x)
        want = ref.apply(ref_params, x)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "name,kwargs,shape",
    [
        ("lenet", {}, (2, 28, 28, 1)),
        ("resnet32_cifar", {"blocks_per_stage": 1}, (2, 32, 32, 3)),
        ("resnet50", {"dtype": jnp.float32}, (1, 64, 64, 3)),
    ],
)
def test_model_forward_same_under_both_impls(name, kwargs, shape):
    x = jax.random.normal(jax.random.PRNGKey(5), shape, jnp.float32)
    m_xla = get_model(name, conv_impl="xla", **kwargs)
    m_pat = get_model(name, conv_impl="patches", **kwargs)
    # Jitted: one compile a model, where op by op is one a layer's every op.
    variables = jax.jit(m_xla.init)(jax.random.PRNGKey(6), x)
    out_xla = jax.jit(m_xla.apply)(variables, x)
    out_pat = jax.jit(m_pat.apply)(variables, x)
    np.testing.assert_allclose(out_xla, out_pat, rtol=2e-4, atol=2e-4)


def test_model_grads_same_under_both_impls():
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 32, 32, 3), jnp.float32)
    m_xla = get_model("resnet32_cifar", blocks_per_stage=1, conv_impl="xla")
    m_pat = get_model(
        "resnet32_cifar", blocks_per_stage=1, conv_impl="patches"
    )
    variables = jax.jit(m_xla.init)(jax.random.PRNGKey(8), x)
    params, rest = variables["params"], variables["batch_stats"]

    def loss(model):
        def f(p):
            out, _ = model.apply(
                {"params": p, "batch_stats": rest}, x, train=True,
                mutable=["batch_stats"],
            )
            return jnp.sum(out ** 2)

        return f

    g_xla = jax.jit(jax.grad(loss(m_xla)))(params)
    g_pat = jax.jit(jax.grad(loss(m_pat)))(params)
    flat_x, _ = jax.flatten_util.ravel_pytree(g_xla)
    flat_p, _ = jax.flatten_util.ravel_pytree(g_pat)
    np.testing.assert_allclose(flat_p, flat_x, rtol=5e-4, atol=5e-4)


def test_default_impl_env_typo_fails_loudly(monkeypatch):
    from distributed_tensorflow_models_tpu.ops import conv as convlib

    monkeypatch.setattr(convlib, "_default_impl", "patch")  # typo
    with pytest.raises(ValueError, match="DTM_CONV_IMPL"):
        convlib.resolve_conv_impl("auto")


def test_inception_patches_lowers_without_conv_hlo():
    """Every conv and pool in Inception-v3 — all block types, both pool
    kinds, the aux head — must honor conv_impl='patches' (trace only; no
    execution)."""
    model = get_model("inception_v3", conv_impl="patches")
    x = jnp.ones((1, 299, 299, 3), jnp.bfloat16)
    text = (
        jax.jit(
            lambda v, x: model.apply(
                v, x, train=True, mutable=["batch_stats"],
                rngs={"dropout": jax.random.PRNGKey(0)},
            )
        )
        .lower(
            jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0), x)
            ),
            jax.ShapeDtypeStruct(x.shape, x.dtype),
        )
        .as_text()
    )
    assert "convolution" not in text
    assert "reduce-window" not in text


def test_resnet50_patches_train_step_lowers_without_conv_hlo():
    """End-to-end guard for the TPU bench path: the full ResNet-50 patches
    train step (fwd+bwd through every block) contains zero convolution /
    reduce-window HLO."""
    model = get_model("resnet50", conv_impl="patches")
    x = jnp.ones((1, 64, 64, 3), jnp.bfloat16)
    # Traced, not run: the shapes of the variables are enough to lower.
    variables = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    params, stats = variables["params"], variables["batch_stats"]

    def step(p, stats):
        out, _ = model.apply(
            {"params": p, "batch_stats": stats}, x, train=True,
            mutable=["batch_stats"],
        )
        return jnp.sum(out.astype(jnp.float32) ** 2)

    text = jax.jit(jax.grad(step)).lower(params, stats).as_text()
    assert "convolution" not in text
    assert "reduce-window" not in text
