"""The decoder-hybrid-decoder stack in ``models/transformer_lm.py`` (PR 44:
``harness/config.py::phi4_mini_flash``): what a source layer hands on is
read by later layers and takes its gradient as the sum over them, with and
without recomputation; the window is ``"attention"`` layers' and no
``"attention_full"`` layer's; differential attention against its formula;
what the settings refuse; the names of the leaves; the program config."""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.config import PHI4_FLASH_LAYERS, get_config
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.models.transformer_lm import SelfAttention
from distributed_tensorflow_models_tpu.parallel import tensor as tensorlib

# Two periods of the cross-decoder: each source layer has two readers.
LAYERS = ("mamba1", "attention", "mamba1", "attention_full", "gmu", "cross", "gmu", "cross")
SMALL = dict(
    vocab_size=97, num_layers=8, layer_mixers=LAYERS, layer_ids=(0, 1, 16, 17, 18, 19, 20, 21),
    num_heads=8, num_kv_heads=4, d_model=64, d_ff=96, max_len=64, dropout_rate=0.0, pos_encoding="none",
    norm="layernorm", norm_eps=1e-5, use_bias=False, attn_bias=True, mlp="gated_silu", attn_window=8,
    attn_differential=True, mamba1_inner=96, mamba1_state_dim=4, mamba1_dt_rank=4, mamba1_chunk=8,
    tie_embeddings=True, dtype=jnp.float32,
)
T = 24


def _model(**over):
    return get_model("transformer_lm", **{**SMALL, **over})


@pytest.fixture(scope="module")
def setup():
    model = _model()
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, 97)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    moved = jax.tree.unflatten(tree, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    return moved, tokens


def _grads(params, tokens, **over):
    model = _model(**over)

    def loss(p):
        logits, _ = model.apply({"params": p}, tokens)
        return -jnp.mean(jax.nn.log_softmax(logits)[..., 0])

    return jax.jit(jax.value_and_grad(loss))(params)


def test_a_source_s_gradient_is_the_sum_over_its_two_readers(setup):
    """A recomputed half takes what it reads as an input and hands it on as
    a kept output (``nn.remat``); plain autodiff without recomputation
    sums a value's cotangents over its uses by construction.  Both give
    the sources' leaves the same gradient, and with the second period of
    the cross-decoder cut away (one reader each) it is another: both
    readers reach it."""
    params, tokens = setup
    plain_loss, plain = _grads(params, tokens, remat=False)
    remat_loss, remat = _grads(params, tokens, remat=True)
    assert float(plain_loss) == pytest.approx(float(remat_loss), rel=1e-6)
    for got, want in zip(jax.tree.leaves(remat), jax.tree.leaves(plain)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6, rtol=1e-4)
    one_reader = {k: v for k, v in params.items() if k not in ("blocks_6", "blocks_7")}
    _, single = _grads(
        one_reader, tokens, remat=True, num_layers=6, layer_mixers=LAYERS[:6], layer_ids=SMALL["layer_ids"][:6]
    )
    for block, leaf in (("blocks_2", ("ssm", "A_log")), ("blocks_3", ("attn", "value", "kernel"))):
        pick = lambda g: np.asarray(functools.reduce(lambda tree, key: tree[key], leaf, g[block]))
        assert np.abs(pick(remat)).max() > 0
        assert np.linalg.norm(pick(remat) - pick(single)) > 1e-2 * np.linalg.norm(pick(single))
    # The readers' own leaves: every one has a gradient.
    for block in ("blocks_4", "blocks_5", "blocks_6", "blocks_7"):
        assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(remat[block]))


def test_the_leaves_and_what_the_tensor_rules_match(setup):
    params, _ = setup
    assert sorted(params["blocks_0"]["ssm"]) == sorted(
        ["in_proj", "conv", "conv_bias", "x_proj", "dt_proj", "dt_bias", "A_log", "D", "out_proj"])
    assert params["blocks_0"]["ssm"]["A_log"].shape == (96, 4)  # a decay a channel and state
    assert sorted(params["blocks_4"]["ssm"]) == ["in_proj", "out_proj"]  # the memory unit: no scan of its own
    full, cross = params["blocks_3"]["attn"], params["blocks_5"]["attn"]
    lambdas = ["lambda_k1", "lambda_k2", "lambda_q1", "lambda_q2"]
    assert sorted(full) == sorted(["query", "key", "value", "out", "subln", *lambdas])
    assert sorted(cross) == sorted(["query", "out", "subln", *lambdas])  # a query and an output projection only
    assert "bias" in full["query"] and "bias" not in params["blocks_3"]["mlp"]["gate"] and "head" not in params
    assert full["subln"]["scale"].shape == (2 * 64 // 8,) and full["lambda_q1"].shape == (64 // 8,)
    from distributed_tensorflow_models_tpu.core.sharding import _path_str

    paths = [_path_str(p) for p, _ in jax.tree_util.tree_leaves_with_path(params)]
    matched = {p for p in paths for pattern, _ in tensorlib.transformer_tp_rules() if re.search(pattern, p)}
    # The attention projections of every attention layer (a cross layer's
    # query and out among them) and the embedding, as in every other stack;
    # the new leaves match no rule and stay whole.
    assert {p for p in paths if re.search(r"attn/(query|key|value)/(kernel|bias)$|attn/out/kernel$", p)} <= matched
    assert not [p for p in matched if "/ssm/" in p or "lambda" in p or "subln" in p]


def test_differential_attention_is_its_formula():
    """One layer against the formula written out on full score matrices:
    even and odd heads pair up, a pair of key/value heads serves two query
    pairs, the pair's values side by side, the two softmaxes subtracted
    under ``lambda``, the pair norm and ``1 - lambda_init``."""
    H, Hkv, Dh, d, window, layer = 8, 4, 8, 64, 5, 3
    lam0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    attn = SelfAttention(
        H, d, dtype=jnp.float32, num_kv_heads=Hkv, attn_window=window, diff_lambda_init=lam0, norm_eps=1e-5
    )
    x = jax.random.normal(jax.random.key(0), (1, 12, d))
    params = attn.init(jax.random.key(1), x)["params"]
    params = jax.tree.map(lambda a: a + 0.1 * jax.random.normal(jax.random.key(a.size), a.shape), params)
    got = attn.apply({"params": params}, x)
    lin = lambda name: x @ params[name]["kernel"] + params[name]["bias"]
    q, k, v = lin("query").reshape(12, H, Dh), lin("key").reshape(12, Hkv, Dh), lin("value").reshape(12, Hkv, Dh)
    at = np.arange(12)
    seen = (at[:, None] >= at[None, :]) & (at[:, None] - at[None, :] < window)
    soft = lambda qh, kh: jax.nn.softmax(jnp.where(seen, qh @ kh.T / math.sqrt(Dh), -jnp.inf), axis=-1)
    lam = (
        jnp.exp(params["lambda_q1"] @ params["lambda_k1"]) - jnp.exp(params["lambda_q2"] @ params["lambda_k2"]) + lam0
    )
    pairs = []
    for j in range(H // 2):
        m = j // 2  # 4 query pairs over 2 key/value pairs
        values = jnp.concatenate([v[:, 2 * m], v[:, 2 * m + 1]], axis=-1)
        a = soft(q[:, 2 * j], k[:, 2 * m]) @ values - lam * soft(q[:, 2 * j + 1], k[:, 2 * m + 1]) @ values
        a = a * jax.lax.rsqrt(jnp.mean(a * a, axis=-1, keepdims=True) + 1e-5) * params["subln"]["scale"]
        pairs.append((1 - lam0) * a)
    want = jnp.concatenate(pairs, axis=-1) @ params["out"]["kernel"] + params["out"]["bias"]
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want), atol=2e-5)


def test_the_window_is_the_attention_layers_and_not_the_full_layer_s(setup):
    params, tokens = setup
    only = lambda block: {"embedding": params["embedding"], "ln_f": params["ln_f"], "blocks_0": params[block]}
    short, moved = tokens[:1], tokens[:1].at[:, 2].set((tokens[0, 2] + 1) % 97)
    for kind, block, reaches in (("attention", "blocks_1", False), ("attention_full", "blocks_3", True)):
        model = _model(num_layers=1, layer_mixers=(kind,), layer_ids=(1,))
        run = lambda t: model.apply({"params": only(block)}, t)[0]
        far = float(jnp.abs(run(short)[:, 2 + 8 :] - run(moved)[:, 2 + 8 :]).max())
        assert (far > 1e-6) == reaches, kind
        assert float(jnp.abs(run(short)[:, 2:10] - run(moved)[:, 2:10]).max()) > 1e-5


@pytest.mark.parametrize(
    "settings,message",
    [
        (dict(decode=True), "neither decodes nor runs in the pipelined stack"),
        (dict(pipelined=True), "ships the residual stream alone"),
        (dict(attention_fn=lambda q, k, v, causal=True: q), "serving/kv_slots.py"),
        (dict(layer_mixers=("attention",) * 8, attn_differential=True, decode=True), "no pair of softmaxes"),
        (dict(layer_mixers=("gmu",) + LAYERS[1:]), r"layer_mixers\[0\] 'gmu' reads an earlier 'mamba1'"),
        (dict(layer_mixers=LAYERS[:3] + ("attention",) + LAYERS[4:]), r"'cross' reads an earlier 'attention_full'"),
        (dict(layer_ids=(0, 1, 2)), "layer_ids names 3 layers"),
        (dict(pos_encoding="rope"), "rotate and norm no query or key"),
        (dict(attn_differential=False, qk_norm=True), "would be dropped in silence"),
    ],
    ids=["decode", "pipelined", "attention_fn", "differential_decode", "gmu_without_source",
         "cross_without_source", "layer_ids", "rope", "qk_norm_under_a_cross_layer"],
)
def test_settings_that_cannot_run_are_refused_with_the_mechanism(settings, message):
    model = _model(**settings)
    with pytest.raises(ValueError, match=message):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))


def test_the_program_config_is_the_published_stack():
    mk = get_config("phi4_mini_flash").model_kwargs
    layers = mk["layer_mixers"]
    assert layers == PHI4_FLASH_LAYERS and len(layers) == mk["num_layers"] == 32
    kinds = lambda name: [i for i, k in enumerate(layers) if k == name]
    assert kinds("mamba1") == list(range(0, 17, 2)) and kinds("attention") == list(range(1, 16, 2))
    assert kinds("attention_full") == [17]
    assert kinds("gmu") == list(range(18, 32, 2)) and kinds("cross") == list(range(19, 32, 2))
    model = get_model("transformer_lm", **mk)
    # Every memory unit reads layer 16, every cross-attention layer 17.
    assert set(model._sources().values()) == {16, 17} and len(model._sources()) == 14
    assert (mk["attn_window"], mk["attn_differential"], mk["tie_embeddings"], mk["attn_bias"], mk["use_bias"]) == (
        512, True, True, True, False)
    assert (mk["mamba1_inner"], mk["mamba1_state_dim"], mk["mamba1_dt_rank"]) == (5120, 16, math.ceil(2560 / 16))
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 16), jnp.int32)))["params"]
    assert sum(x.size for x in jax.tree.leaves(shapes)) == 3_852_562_944  # "3.8B"
