"""What NVIDIA-Nemotron-3-Nano-30B-A3B brought to the program, at a small
size on the CPU in float32: layers that are one sub-layer alone (a mixer
without a feed-forward, a feed-forward without a mixer) against
hand-written ones, with per-half recomputation; the squared-ReLU
feed-forward; sixteen query heads a key/value head and a projection wider
than the model on the route the chip takes; the parameter tree and the
counts; the refusals.  ``fit`` through the program config is a case of
``tests/test_lm_fit_smoke.py``.

What it shares with Granite 4.0-H and Kimi Linear is tested beside
theirs, as cases of their parametrised tests: the state-space scan with
groups of heads (``tests/test_granite_h.py``, ``tests/test_ssm_kernel.py``),
the Mamba-2 mixer with groups and its grouped norm
(``tests/test_granite_h.py``), experts without a gate and the shares that
add up (``tests/test_moe.py``, ``tests/test_olmoe_block.py``).
The plain reference's side of it (logits, loss, every gradient) is
``tests/benchmark/test_bench_reference_nemotron_h.py``.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.models import transformer_lm as tlm
from distributed_tensorflow_models_tpu.ops import attention as attnlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL = get_config("nemotron3_nano").model_kwargs
PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
KINDS = {"M": "ssm_only", "E": "ffn_only", "*": "attention_only"}
SMALL = {
    **FULL,
    "vocab_size": 97, "num_layers": 5,
    "layer_mixers": ("ssm_only", "ffn_only", "ssm_only", "attention_only", "ffn_only"),
    "num_heads": 4, "num_kv_heads": 2, "head_dim": 8, "d_model": 48, "d_ff": 40, "max_len": 40,
    "ssm_num_heads": 4, "ssm_head_dim": 8, "ssm_state_dim": 16, "ssm_num_groups": 2, "ssm_chunk": 16,
    "num_experts": 8, "moe_top_k": 2, "moe_shared_d_ff": 56, "moe_held": (2, 4),
    "dtype": jnp.float32,
}


def _moved(params, seed=5):
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _block(mixer, feed, remat, **kwargs):
    return tlm.Block(
        num_heads=4, d_model=32, d_ff=48, dropout_rate=0.0, dtype=jnp.float32, attn_impl="blockwise",
        attention_fn=None, num_kv_heads=2, head_dim=16, norm="rmsnorm", norm_eps=1e-5, use_bias=False,
        mlp="relu2", mixer=mixer, feed=feed, remat=remat, **kwargs,
    )


# --- layers of one sub-layer ----------------------------------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputing"])
@pytest.mark.parametrize("kind", ["attention_only", "ffn_only"])
def test_a_layer_of_one_sub_layer_against_a_hand_written_one(kind, remat):
    """``x + F(RMSNorm(x))`` with one ``F``: attention over 4 query and 2
    key/value heads of 16 (64 channels into a width of 32: the projection
    is wider than the model) and no feed-forward, no ``ln2``; or the
    squared-ReLU feed-forward ``W2 relu(W1 u)^2`` and no mixer, no
    ``ln1``."""
    mixer, feed = tlm.TransformerLM._halves(kind)
    block = _block(mixer, feed, remat)
    x = jax.random.normal(jax.random.key(3), (2, 24, 32))
    params = _moved(jax.jit(block.init)(jax.random.key(0), x)["params"])
    assert sorted(params) == (["attn", "ln1"] if kind == "attention_only" else ["ln2", "mlp"])
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x)
        if kind == "attention_only":
            a, h = params["attn"], _rms(x, params["ln1"]["scale"])
            assert a["query"]["kernel"].shape == (32, 64) and a["key"]["kernel"].shape == (32, 32)
            q = (h @ a["query"]["kernel"]).reshape(2, 24, 4, 16)
            k = jnp.repeat((h @ a["key"]["kernel"]).reshape(2, 24, 2, 16), 2, axis=2)
            v = jnp.repeat((h @ a["value"]["kernel"]).reshape(2, 24, 2, 16), 2, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 16**-0.5
            scores = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), scores, -jnp.inf)
            out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v).reshape(2, 24, 64)
            want = x + out @ a["out"]["kernel"]
        else:
            m, h = params["mlp"], _rms(x, params["ln2"]["scale"])
            assert sorted(m) == ["down", "up"]  # two matrices: no gate
            want = x + jnp.square(jnp.maximum(h @ m["up"]["kernel"], 0.0)) @ m["down"]["kernel"]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_a_block_of_both_halves_is_its_two_one_sub_layer_blocks_in_turn():
    """``x + mix(x)`` then ``x + feed(x)``, the block every other model
    runs, is a mixer-only layer followed by a feed-forward-only layer on
    the same leaves: the new layer kinds add no mathematics of their own."""
    x = jax.random.normal(jax.random.key(3), (2, 24, 32))
    both = _block("attention", True, False)
    params = _moved(jax.jit(both.init)(jax.random.key(0), x)["params"])
    assert sorted(params) == ["attn", "ln1", "ln2", "mlp"]
    first = _block("attention", False, False).apply(
        {"params": {k: params[k] for k in ("attn", "ln1")}}, x
    )
    second = _block("none", True, False).apply({"params": {k: params[k] for k in ("ln2", "mlp")}}, first)
    np.testing.assert_array_equal(np.asarray(both.apply({"params": params}, x)), np.asarray(second))


def test_recomputing_a_one_sub_layer_stack_changes_no_value_and_no_leaf():
    """Each layer is one recomputed half.  The family's dense stack (``-``
    for ``E``: no experts), so that the case costs seconds; experts under
    recomputation are the fit (``tests/test_lm_fit_smoke.py``) and the
    reference's test."""
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    dense = {**SMALL, "num_experts": 0, "moe_held": None, "num_layers": 3,
             "layer_mixers": ("ssm_only", "ffn_only", "attention_only")}
    on = get_model("transformer_lm", **dense)
    off = get_model("transformer_lm", **{**dense, "remat": False})
    assert on.remat and not off.remat
    params = jax.jit(on.init)(jax.random.key(0), tokens)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(off.init, jax.random.key(0), tokens)["params"]
    )
    loss = lambda m: lambda p: jnp.sum(jnp.sin(m.apply({"params": p}, tokens)[0]))
    with jax.default_matmul_precision("highest"):
        # Jitted: one compile a side, where op by op is some hundred.
        (a, ga), (b, gb) = (jax.jit(jax.value_and_grad(loss(m)))(params) for m in (on, off))
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert float(jnp.abs(x - y).max()) <= 1e-5 * float(jnp.abs(y).max()) + 1e-7


# --- sixteen query heads a key/value head --------------------------------

def test_sixteen_fold_groups_and_a_wide_projection_on_the_route_the_chip_takes():
    """32 query heads of 128 over 2 key/value heads: ``fused_admissible``
    admits the call, and the fused kernels (interpreted) with ``K`` and
    ``V`` repeated sixteen-fold give the full score matrix's result and
    key/value gradients of two heads."""
    q = jax.random.normal(jax.random.key(0), (1, 256, 32, 128))
    k, v = (jax.random.normal(jax.random.key(i), (1, 256, 2, 128)) for i in (1, 2))
    assert attnlib.fused_admissible(q, k, v)

    def fused(q, k, v):
        rep = lambda y: jnp.repeat(y, 16, axis=2)
        return attnlib.fused_attention(q, rep(k), rep(v), True, None, 128, 128, True)

    with jax.default_matmul_precision("highest"):
        got = fused(q, k, v)
        want = attnlib.reference_attention(q, k, v, causal=True)
        probe = jax.random.normal(jax.random.key(4), want.shape)
        g = jax.grad(lambda *a: jnp.sum(fused(*a) * probe), argnums=(1, 2))(q, k, v)
        w = jax.grad(lambda *a: jnp.sum(attnlib.reference_attention(*a, causal=True) * probe), argnums=(1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    for a, b in zip(g, w):
        assert a.shape == (1, 256, 2, 128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=2e-4)


# --- the tree, the counts, the refusals ------------------------------------

def _tree(kwargs):
    model = get_model("transformer_lm", **kwargs)
    tree = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    return tree, sum(x.size for x in jax.tree.leaves(tree))


def test_the_published_configuration_and_the_cut_count_what_the_issue_reckoned():
    assert tuple(KINDS[k] for k in PATTERN) == tuple(FULL["layer_mixers"]) and len(PATTERN) == 52
    assert [PATTERN.count(k) for k in "ME*"] == [23, 23, 6]
    tree, total = _tree(FULL)
    per = lambda t: sum(x.size for x in jax.tree.leaves(t))
    # ISSUE 40's table.
    assert sorted(tree["blocks_0"]) == ["ln1", "ssm"] and per(tree["blocks_0"]) == 38_744_896
    assert tree["blocks_0"]["ssm"]["in_proj"]["kernel"].shape == (2688, 10304)
    assert tree["blocks_0"]["ssm"]["conv"].shape == (4, 6144)
    assert sorted(tree["blocks_1"]) == ["ln2", "moe"] and per(tree["blocks_1"]) == 1_297_468_032
    assert sorted(tree["blocks_1"]["moe"]) == ["router", "shared", "w_down", "w_up"]  # no w_gate
    assert tree["blocks_1"]["moe"]["w_up"].shape == (128, 2688, 1856)
    assert tree["blocks_1"]["moe"]["shared"]["up"]["kernel"].shape == (2688, 3712)
    assert sorted(tree["blocks_5"]) == ["attn", "ln1"] and per(tree["blocks_5"]) == 23_399_040
    assert tree["head"]["kernel"].shape == (2688, 131072) and "bias" not in tree["head"]
    assert total == 23 * 38_744_896 + 6 * 23_399_040 + 23 * 1_297_468_032 + 2 * 352_321_536 + 2688
    assert total == 31_577_937_344  # the published 31.6 B
    with open(os.path.join(REPO, "benchmark", "configs", "nemotron3_nano.json")) as f:
        cut = json.load(f)
    kw = cut["overrides"]["model_kwargs"]
    kw = {**kw, "layer_mixers": tuple(kw["layer_mixers"]), "moe_held": tuple(kw["moe_held"])}
    tree, total = _tree({**FULL, **kw})
    assert [sorted(tree[f"blocks_{i}"])[-1] for i in range(9)] == [
        {"M": "ssm", "E": "moe", "*": "ln1"}[k] for k in PATTERN[:9]
    ]
    assert per(tree["blocks_1"]) == 100_125_312 and tree["blocks_1"]["moe"]["router"].shape == (2688, 128)
    assert total == cut["parameters"]["count"] == 666_962_944  # x 16 B = 10.67 GB


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"decode": True}, "neither decode nor take"),
        ({"layer_mixers": ("ssm_only", "ffn_only", "mamba_only", "attention_only", "ffn_only")}, "unknown layer_mixers"),
        ({"layer_mixers": ("ssm_only", "ffn_only")}, "names 2 layers"),
        ({"mlp": "relu"}, "unknown mlp"),
        ({"moe_expert": "gated_gelu"}, "unknown moe_expert"),
        ({"ssm_num_groups": 3}, "G dividing H"),
        ({"pipelined": True}, "GPT-2 block only"),
    ],
    ids=["decodes_not", "layer_kind", "layer_count", "mlp", "expert", "groups", "pipelined"],
)
def test_settings_the_stack_does_not_have_are_refused(kwargs, match):
    model = get_model("transformer_lm", **{**SMALL, **kwargs})
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32)))


def test_a_model_without_the_new_fields_traces_what_it_traced_before():
    """One group, gated experts, both halves in every layer: the defaults
    leave the jaxpr of a model that states none of it as it was."""
    kw = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=64, d_ff=96, max_len=40, dropout_rate=0.0,
              dtype=jnp.float32)
    tokens = jnp.zeros((2, 16), jnp.int32)
    model = get_model("transformer_lm", **kw)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))["params"]
    text = lambda m: str(jax.make_jaxpr(lambda p: m.apply({"params": p}, tokens)[0])(params))
    base = text(model)
    assert base == text(get_model("transformer_lm", **kw, layer_mixers=("attention", "attention"),
                                  ssm_num_groups=1, moe_expert="gated_silu", moe_shared_d_ff=0))
    assert base != text(get_model("transformer_lm", **kw, mlp="relu2"))
