"""The plain reference kept beside the ``granite_h_micro`` configuration
(``benchmark/references/granite_h.py``) against the program's model, on
seeded random weights at a small size in float32 at ``highest``: logits,
the loss and the gradient of every leaf, the tied embedding's among them
(where a tie done wrong shows).  Every leaf is moved off its initial value
(norm scales, ``A_log``, ``dt_bias``, ``D`` and the convolution's bias
among them), so that a term dropped on either side shows.  The model
holds 4 query heads over 2 key/value heads and a state-space mixer of 4
heads of 8 over a state of 16: grouped heads and an expansion, as the
cell's."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KW = dict(
    vocab_size=97, num_layers=4, layer_mixers=("ssm", "ssm", "attention", "ssm"),
    num_heads=4, num_kv_heads=2, d_model=64, d_ff=96, max_len=150, dropout_rate=0.0,
    pos_encoding="none", norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="gated_silu",
    ssm_num_heads=4, ssm_head_dim=8, ssm_state_dim=16, ssm_conv_size=4, ssm_chunk=64,
    embedding_multiplier=12.0, residual_multiplier=0.22, attention_multiplier=0.015625,
    logits_scaling=8.0, tie_embeddings=True, remat=True, dtype=jnp.float32,
)
REF_KW = dict(num_heads=4, num_kv_heads=2)
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
    ref = cells.load_module("references", "granite_h")
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
# No ``head``: the embedding is the head.
LEAVES = [
    "embedding/embedding", "ln_f/scale",
    *(f"blocks_1/ssm/{name}" for name in (
        "in_proj/kernel", "out_proj/kernel", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm/scale")),
    *(f"blocks_2/attn/{name}" for name in ("query/kernel", "key/kernel", "value/kernel", "out/kernel")),
    "blocks_3/mlp/gate/kernel", "blocks_3/mlp/up/kernel", "blocks_3/mlp/down/kernel",
    "blocks_0/ln1/scale", "blocks_2/ln1/scale", "blocks_0/ln2/scale",
]


def test_the_leaves_compared_cover_every_kind_of_leaf(both):
    got, want = both["grads"]
    assert set(got) == set(want) and "head/kernel" not in got
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


def test_reference_is_causal_and_takes_no_positions(setup):
    model, params, tokens, _ = setup
    ref = cells.load_module("references", "granite_h")
    base = ref.forward(params, tokens, **REF_KW)
    changed = ref.forward(params, tokens.at[:, 100].set((tokens[:, 100] + 1) % 97), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :100]), np.asarray(changed[:, :100]))
    assert float(jnp.abs(base[:, 100:] - changed[:, 100:]).max()) > 1e-4
    # The attention layer alone (no state-space layer carries the order)
    # on one token repeated: every position's logits are the first's.
    attention_only = {k: v for k, v in params.items() if not (k.startswith("blocks_") and "ssm" in v)}
    same = ref.forward(attention_only, jnp.full((1, 20), 7), **REF_KW)
    np.testing.assert_allclose(np.asarray(same), np.asarray(same[:, :1]).repeat(20, 1), atol=1e-5)


@pytest.mark.parametrize(
    "name,value",
    [("embedding_multiplier", 1.0), ("residual_multiplier", 1.0), ("attention_multiplier", 0.125),
     ("logits_scaling", 1.0)],
)
def test_each_of_the_four_scalars_moves_the_reference(setup, name, value):
    model, params, tokens, _ = setup
    ref = cells.load_module("references", "granite_h")
    base = ref.forward(params, tokens[:1, :40], **REF_KW)
    moved = ref.forward(params, tokens[:1, :40], **REF_KW, **{name: value})
    assert float(jnp.abs(base - moved).max()) > 1e-4


def test_the_reference_s_recurrence_is_the_op_s_oracle():
    """``state_space`` (token by token, recomputing in blocks) against
    ``ops/ssm.py::recurrent_ssd``: the tests' two oracles are one
    recurrence."""
    from distributed_tensorflow_models_tpu.ops import ssm

    ref = cells.load_module("references", "granite_h")
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (2, T, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, T, 4)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (4,), minval=1.0, maxval=16.0))
    b, c = (jax.random.normal(key, (2, T, 16)) for key in ks[3:])
    got = ref.state_space(x, dt, jnp.exp(-jnp.exp(a_log) * dt), b, c)
    want = ssm.recurrent_ssd(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_a_lower_precision_would_not_pass(setup):
    """The reference in bfloat16 (what ``compare_reference_granite_h.py``
    holds to the bf16 tolerances on the chip) is far outside what float32
    agrees to here."""
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "granite_h")
    want = ref.forward(params, tokens, **REF_KW)
    low = ref.forward(params, tokens, **REF_KW, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) > 1e-3


def test_compare_tool_rehearses_on_the_cpu(capsys):
    import json

    from benchmark.tools import compare_reference_granite_h as tool

    assert tool.main(["--seed", "3", "--rehearse"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    by_name = {l["program"]: l for l in lines if "program" in l}
    assert set(by_name) == {"bf16", "f32", "reference_bf16"}
    assert all(by_name["f32"]["within"].values())
    assert "within" not in by_name["bf16"]  # no verdict on bf16 off the chip
    assert set(by_name["f32"]["per_sequence"][0]["grad_rel_by_leaf"]) == {name for name, _, _ in tool.LEAVES}
    assert "embedding" in by_name["f32"]["per_sequence"][0]["grad_rel_by_leaf"]  # the tie
    assert lines[0]["tokens"] == [1, 80] and lines[-1] == {"ok": True}
    # Every bf16 limit is looser than its f32 limit where both are judged,
    # and each column judges the gradients by one reading at least.
    both = [t for t in tool.TOLERANCES.values() if None not in t]
    assert len(both) == 2 and all(bf16 > 10 * f32 for bf16, f32 in both)
    assert tool.TOLERANCES["grad_rel_mean"][0] and tool.TOLERANCES["grad_rel"][1]
    assert set(by_name["f32"]["within"]) == {k for k, t in tool.TOLERANCES.items() if t[1] is not None}


def test_the_tool_s_weights_are_the_seed_s_in_every_process():
    """The key that moves a norm scale, ``A_log``, ``dt_bias``, ``D`` or
    the convolution's bias comes from a hash that no process salts, and
    the bf16 side differentiates the loss of ``fit``'s step: the fused
    head fed from the tied embedding, which the f32 side leaves out
    because it multiplies in bfloat16 whatever the model's dtype."""
    import inspect

    from jax.tree_util import DictKey

    from benchmark.tools import compare_reference_granite_h as tool

    path = (DictKey("blocks_0"), DictKey("ssm"), DictKey("A_log"))
    import zlib

    assert tool.path_id(path) == zlib.crc32(b"blocks_0/ssm/A_log")
    source = inspect.getsource(tool)
    assert "hash(" not in source and "olmo" not in source
    assert "trainlib.build_loss(cfg, state)" in inspect.getsource(tool.program_side)
    config = tool.load_config(rehearse=True)
    assert config["overrides"]["fused_unembed"] is True
    cfg, make_model, params, tokens, targets = tool.build(config, 3, 1)
    again = tool.build(config, 3, 1)[2]
    other = tool.build(config, 4, 1)[2]
    assert "head" not in params
    for leaf in ("A_log", "D", "conv_bias"):
        get = lambda p: np.asarray(p["blocks_0"]["ssm"][leaf])
        np.testing.assert_array_equal(get(params), get(again))
        assert not np.array_equal(get(params), get(other))
    assert not np.array_equal(np.asarray(params["blocks_0"]["ssm"]["D"]), 1.0)  # moved off its ones
    assert cfg.fused_unembed and tokens.shape == targets.shape == (1, 80)
