"""The plain reference kept beside the ``kimi_linear`` configuration
(``benchmark/references/kimi_linear.py``) against the program's model,
on seeded random weights at a small size in float32 at ``highest``:
logits, the loss, the share of assignments on held experts and the
gradient of every leaf.  Every leaf is moved off its initial value
(norm scales, ``A_log`` and ``dt_bias`` among them), so that a term
dropped on either side shows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KW = dict(
    vocab_size=97, num_layers=5, layer_mixers=("kda", "kda", "kda", "mla", "kda"),
    num_heads=4, d_model=64, d_ff=32, dense_d_ff=96, max_len=150, dropout_rate=0.0,
    pos_encoding="none", norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="gated_silu",
    kda_num_heads=4, kda_head_dim=16, kda_conv_size=4, mla_kv_lora_rank=24,
    mla_nope_dim=16, mla_rope_dim=8, mla_v_dim=16, num_experts=16, moe_router="topk",
    moe_top_k=4, moe_layers="all", moe_first_dense=1, moe_scoring="sigmoid",
    moe_renormalize=True, moe_routed_scale=2.446, moe_shared_experts=1,
    moe_aux_loss_weight=0.0, moe_held=(4, 4), remat=True, dtype=jnp.float32,
)
REF_KW = dict(num_heads=4, top_k=4, routed_scale=2.446, held_first=4, eps=1e-5)
# 150 tokens: two whole chunks of 64 and a rest; one whole block of the
# reference's recomputation (128) and a rest.
T = 150


def _paths(tree):
    return ["/".join(str(k.key) for k in path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.fixture(scope="module")
def setup():
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model("transformer_lm", **KW)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, KW["vocab_size"])
    params = model.init(jax.random.key(0), tokens)["params"]
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves), tokens, jnp.roll(tokens, -1, axis=1)


def _program_loss(model, params, tokens, targets):
    (logits, _), updated = model.apply(
        {"params": params}, tokens, train=True, mutable=["losses", "moe_stats"]
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    assert not jax.tree.leaves(updated.get("losses", {}))  # no auxiliary loss
    stats = updated["moe_stats"]
    held = sum(stats[b]["moe"]["held_share"] for b in stats) / len(stats)
    return nll, {"total": nll, "nll": nll, "held_share": held}


@pytest.fixture(scope="module")
def both(setup):
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "kimi_linear")
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply({"params": params}, tokens, train=False)
        (_, parts), grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, tokens, targets), has_aux=True
        )(params)
    want_logits = ref.forward(params, tokens, **REF_KW)
    (want_total, want_parts), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, targets, **REF_KW), has_aux=True
    )(params)
    return {
        "logits": (logits, want_logits),
        "parts": (parts, {"total": want_total, **want_parts}),
        "grads": (dict(zip(_paths(grads), jax.tree.leaves(grads))),
                  dict(zip(_paths(want_grads), jax.tree.leaves(want_grads)))),
    }


def test_reference_forward_matches_the_model(both):
    got, want = both["logits"]
    assert got.shape == want.shape == (2, T, 97)
    # float32 at "highest" on both sides: reduction order only.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("part", ["total", "nll", "held_share"])
def test_reference_loss_matches_the_model(both, part):
    got, want = both["parts"]
    assert float(got[part]) == pytest.approx(float(want[part]), rel=1e-5, abs=1e-6)


# One leaf of each kind, by the paths of the small model; the test below
# checks that together with the per-layer sweep they are all the leaves.
LEAVES = [
    "embedding/embedding", "head/kernel", "ln_f/scale",
    "blocks_0/mlp/gate/kernel", "blocks_0/mlp/up/kernel", "blocks_0/mlp/down/kernel",
    *(f"blocks_1/linear_attn/{name}" for name in (
        "query/kernel", "key/kernel", "value/kernel", "out/kernel", "conv_query", "conv_key",
        "conv_value", "f_a/kernel", "f_b/kernel", "g_a/kernel", "g_b/kernel", "beta/kernel",
        "A_log", "dt_bias", "o_norm/scale")),
    *(f"blocks_3/attn/{name}" for name in (
        "query/kernel", "kv_a/kernel", "kv_a_norm/scale", "kv_b/kernel", "out/kernel")),
    *(f"blocks_2/moe/{name}" for name in (
        "router", "w_gate", "w_up", "w_down", "shared/gate/kernel", "shared/up/kernel",
        "shared/down/kernel")),
    "blocks_4/ln1/scale", "blocks_4/ln2/scale",
]


def test_the_leaves_compared_cover_every_kind_of_leaf(both):
    got, want = both["grads"]
    assert set(got) == set(want)
    strip = lambda path: path.split("/", 1)[1] if path.startswith("blocks_") else path
    assert {strip(p) for p in got} == {strip(p) for p in LEAVES}


@pytest.mark.parametrize("leaf", LEAVES)
def test_reference_gradient_matches_the_model(both, leaf):
    got, want = both["grads"]
    g, w = np.asarray(got[leaf], np.float64), np.asarray(want[leaf], np.float64)
    assert np.linalg.norm(w) > 0, "a leaf without a gradient tests nothing"
    assert np.linalg.norm(g - w) / np.linalg.norm(w) < 1e-4


def test_every_layer_s_gradient_matches(both):
    got, want = both["grads"]
    for leaf in got:
        g, w = np.asarray(got[leaf], np.float64), np.asarray(want[leaf], np.float64)
        assert np.linalg.norm(g - w) <= 1e-4 * np.linalg.norm(w) + 1e-9, leaf


def test_reference_is_causal_and_routes_every_token_to_k_experts(setup):
    model, params, tokens, _ = setup
    ref = cells.load_module("references", "kimi_linear")
    base = ref.forward(params, tokens, **REF_KW)
    changed = ref.forward(params, tokens.at[:, 100].set((tokens[:, 100] + 1) % 97), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :100]), np.asarray(changed[:, :100]))
    assert float(jnp.abs(base[:, 100:] - changed[:, 100:]).max()) > 1e-4
    chosen = ref.routing(params, tokens, **REF_KW)
    assert len(chosen) == 4  # four expert layers after the dense one
    for c in chosen:
        assert c.shape == (2 * T, 16)
        np.testing.assert_array_equal(np.asarray(c.sum(-1)), 4)


def test_a_lower_precision_would_not_pass(setup):
    """The reference in bfloat16 (what ``compare_reference_kimi_linear.py``
    holds to the bf16 tolerances on the chip) is far outside what float32
    agrees to here."""
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "kimi_linear")
    want = ref.forward(params, tokens, **REF_KW)
    low = ref.forward(params, tokens, **REF_KW, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) > 1e-2


def test_compare_tool_rehearses_on_the_cpu(capsys):
    import json

    from benchmark.tools import compare_reference_kimi_linear as tool

    assert tool.main(["--seed", "3", "--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    by_name = {l["program"]: l for l in lines if "program" in l}
    assert set(by_name) == {"bf16", "f32", "reference_bf16"}
    assert all(by_name["f32"]["within"].values())
    assert "within" not in by_name["bf16"]  # no verdict on bf16 off the chip
    assert set(by_name["f32"]["per_sequence"][0]["grad_rel_by_leaf"]) == {
        "kda_wq", "kda_a_log", "kda_dt_bias", "kda_f_b", "mla_wkva", "mla_wq", "router",
        "shared_gate", "w_gate", "w_up", "w_down",
    }
    assert lines[-1] == {"ok": True}
