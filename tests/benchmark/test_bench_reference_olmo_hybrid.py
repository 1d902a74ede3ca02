"""The plain reference kept beside the ``olmo_hybrid`` configuration
(``benchmark/references/olmo_hybrid.py``) against the program's model,
on seeded random weights at a small size in float32 at ``highest``:
logits, the loss and the gradient of every leaf.  Every leaf is moved
off its initial value (norm scales, ``A_log`` and ``dt_bias`` among
them), so that a term dropped on either side shows.  The model holds 3
heads of 16 in a width of 64: a share of a layer's heads, as the cell's
15 of 128 in 3840."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KW = dict(
    vocab_size=97, num_layers=4, layer_mixers=("gdn", "gdn", "gdn", "attention"),
    num_heads=3, head_dim=16, d_model=64, d_ff=96, max_len=150, dropout_rate=0.0,
    pos_encoding="rope", rope_theta=500000.0, norm="rmsnorm", norm_eps=1e-6, norm_placement="post",
    use_bias=False, qk_norm=True, mlp="gated_silu", gdn_num_heads=3, gdn_key_dim=12,
    gdn_value_dim=24, gdn_conv_size=4, remat=True, dtype=jnp.float32,
)
REF_KW = dict(num_heads=3, eps=1e-6, theta=500000.0)
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
    (logits, _), updated = model.apply({"params": params}, tokens, train=True, mutable=["losses"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    assert not jax.tree.leaves(updated.get("losses", {}))  # no auxiliary loss
    return nll


@pytest.fixture(scope="module")
def both(setup):
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "olmo_hybrid")
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply({"params": params}, tokens, train=False)
        total, grads = jax.value_and_grad(lambda p: _program_loss(model, p, tokens, targets))(params)
    want_logits = ref.forward(params, tokens, **REF_KW)
    (want_total, want_parts), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, targets, **REF_KW), has_aux=True
    )(params)
    return {
        "logits": (logits, want_logits),
        "loss": (total, want_total, want_parts["nll"]),
        "grads": (dict(zip(_paths(grads), jax.tree.leaves(grads))),
                  dict(zip(_paths(want_grads), jax.tree.leaves(want_grads)))),
    }


def test_reference_forward_matches_the_model(both):
    got, want = both["logits"]
    assert got.shape == want.shape == (2, T, 97)
    # float32 at "highest" on both sides: reduction order only.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


def test_reference_loss_matches_the_model(both):
    got, want, nll = both["loss"]
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    assert float(nll) == float(want)  # the cross entropy is the whole loss


# One leaf of each kind, by the paths of the small model; the test below
# checks that together with the per-layer sweep they are all the leaves.
LEAVES = [
    "embedding/embedding", "head/kernel", "ln_f/scale",
    *(f"blocks_1/linear_attn/{name}" for name in (
        "query/kernel", "key/kernel", "value/kernel", "gate/kernel", "out/kernel", "conv_query",
        "conv_key", "conv_value", "a/kernel", "beta/kernel", "A_log", "dt_bias", "o_norm/scale")),
    *(f"blocks_3/attn/{name}" for name in (
        "query/kernel", "key/kernel", "value/kernel", "out/kernel", "q_norm/scale", "k_norm/scale")),
    "blocks_2/mlp/gate/kernel", "blocks_2/mlp/up/kernel", "blocks_2/mlp/down/kernel",
    "blocks_0/ln1/scale", "blocks_3/ln1/scale", "blocks_0/ln2/scale",
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
    # float32 on both sides, the recurrence summed chunk-wise on one and
    # token by token on the other: 1e-4 of the leaf's norm.
    assert np.linalg.norm(g - w) / np.linalg.norm(w) < 1e-4


def test_every_layer_s_gradient_matches(both):
    got, want = both["grads"]
    for leaf in got:
        g, w = np.asarray(got[leaf], np.float64), np.asarray(want[leaf], np.float64)
        assert np.linalg.norm(g - w) <= 1e-4 * np.linalg.norm(w) + 1e-9, leaf


def test_reference_is_causal_and_positions_enter_through_the_attention_layer_only(setup):
    model, params, tokens, _ = setup
    ref = cells.load_module("references", "olmo_hybrid")
    base = ref.forward(params, tokens, **REF_KW)
    changed = ref.forward(params, tokens.at[:, 100].set((tokens[:, 100] + 1) % 97), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :100]), np.asarray(changed[:, :100]))
    assert float(jnp.abs(base[:, 100:] - changed[:, 100:]).max()) > 1e-4
    # Another theta moves the logits (the attention layer rotates) ...
    assert float(jnp.abs(ref.forward(params, tokens, **{**REF_KW, "theta": 10000.0}) - base).max()) > 1e-4
    # ... and with the attention layer taken out nothing depends on it.
    linear_only = {k: v for k, v in params.items() if k != "blocks_3"}
    np.testing.assert_array_equal(
        np.asarray(ref.forward(linear_only, tokens, **REF_KW)),
        np.asarray(ref.forward(linear_only, tokens, **{**REF_KW, "theta": 10000.0})),
    )


def test_the_reference_s_recurrence_is_the_op_s_oracle():
    """``gated_delta_rule`` (token by token, recomputing in blocks) against
    ``ops/linear_attention.py::recurrent_kda`` with the decay spread over
    the key channels: the tests' two oracles are one recurrence."""
    from distributed_tensorflow_models_tpu.ops import linear_attention as linattn

    ref = cells.load_module("references", "olmo_hybrid")
    ks = jax.random.split(jax.random.key(4), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(key, (2, T, 3, 12))) for key in ks[:2])
    v = jax.random.normal(ks[2], (2, T, 3, 24))
    g = -jnp.exp(jax.random.normal(ks[3], (2, T, 3)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(3.0 * jax.random.normal(ks[4], (2, T, 3)))
    got = ref.gated_delta_rule(q, k, v, g, beta) * 12**-0.5
    want = linattn.recurrent_kda(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_a_lower_precision_would_not_pass(setup):
    """The reference in bfloat16 (what ``compare_reference_olmo_hybrid.py``
    holds to the bf16 tolerances on the chip) is far outside what float32
    agrees to here."""
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "olmo_hybrid")
    want = ref.forward(params, tokens, **REF_KW)
    low = ref.forward(params, tokens, **REF_KW, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) > 1e-2


def test_compare_tool_rehearses_on_the_cpu(capsys):
    import json

    from benchmark.tools import compare_reference_olmo_hybrid as tool

    assert tool.main(["--seed", "3", "--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    by_name = {l["program"]: l for l in lines if "program" in l}
    assert set(by_name) == {"bf16", "f32", "reference_bf16"}
    assert all(by_name["f32"]["within"].values())
    assert "within" not in by_name["bf16"]  # no verdict on bf16 off the chip
    assert set(by_name["f32"]["per_sequence"][0]["grad_rel_by_leaf"]) == {name for name, _, _ in tool.LEAVES}
    assert lines[0]["tokens"] == [1, 80] and lines[-1] == {"ok": True}
    # Every bf16 limit is looser than its f32 limit where both are judged,
    # and each column judges the gradients by one reading at least.
    both = [t for t in tool.TOLERANCES.values() if None not in t]
    assert len(both) == 2 and all(bf16 > 10 * f32 for bf16, f32 in both)
    assert tool.TOLERANCES["grad_rel_mean"][0] and tool.TOLERANCES["grad_rel"][1]
    assert set(by_name["f32"]["within"]) == {k for k, t in tool.TOLERANCES.items() if t[1] is not None}


def test_the_tool_s_weights_are_the_seed_s_in_every_process():
    """The key that moves a norm scale, ``A_log`` or ``dt_bias`` comes from
    a hash that no process salts (``hash(str(path))`` differs from run to
    run), and the bf16 side differentiates the loss of ``fit``'s step: the
    fused head, which the f32 side leaves out because it multiplies in
    bfloat16 whatever the model's dtype."""
    import inspect

    from jax.tree_util import DictKey

    from benchmark.tools import compare_reference_olmo_hybrid as tool

    path = (DictKey("blocks_0"), DictKey("linear_attn"), DictKey("A_log"))
    assert tool.path_id(path) == 2223566329  # zlib.crc32(b"blocks_0/linear_attn/A_log")
    source = inspect.getsource(tool)
    assert "hash(" not in source and "compare_reference_kimi_linear" not in source
    assert "trainlib.build_loss(cfg, state)" in inspect.getsource(tool.program_side)
    config = tool.load_config(rehearse=True)
    assert config["overrides"]["fused_unembed"] is True
    cfg, make_model, params, tokens, targets = tool.build(config, 3, 1)
    again = tool.build(config, 3, 1)[2]
    other = tool.build(config, 4, 1)[2]
    a_log = lambda p: np.asarray(p["blocks_0"]["linear_attn"]["A_log"])
    np.testing.assert_array_equal(a_log(params), a_log(again))
    assert not np.array_equal(a_log(params), a_log(other))
    assert cfg.fused_unembed and tokens.shape == targets.shape == (1, 80)
