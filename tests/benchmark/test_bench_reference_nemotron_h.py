"""The plain reference kept beside the ``nemotron3_nano`` configuration
(``benchmark/references/nemotron_h.py``) against the program's model, on
seeded random weights at a small size in float32 at ``highest``: logits,
the loss, the share of assignments on held experts and the gradient of
every leaf.  Every leaf is moved off its initial value (norm scales,
``A_log``, ``dt_bias``, ``D`` and the convolution's bias among them), so
that a term dropped on either side shows.  The model is the nine-layer
pattern ``MEMEM*EME`` of one-sub-layer layers: state-space mixers of 4
heads of 8 in 2 groups over a state of 16, 4 query heads over 2 key/value
heads, 8 squared-ReLU experts of which 4 are held (experts 2-5: a range
that is not the whole and does not start at 0), top-2, a shared expert of
another width.

One module fixture computes both sides once; the cases read it (the
file's cases stay on one worker: ``tests/conftest.py``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KINDS = {"M": "ssm_only", "E": "ffn_only", "*": "attention_only"}
KW = dict(
    vocab_size=97, num_layers=9, layer_mixers=tuple(KINDS[k] for k in "MEMEM*EME"),
    num_heads=4, num_kv_heads=2, head_dim=8, d_model=48, d_ff=40, max_len=150, dropout_rate=0.0,
    pos_encoding="none", norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="relu2",
    ssm_num_heads=4, ssm_head_dim=8, ssm_state_dim=16, ssm_num_groups=2, ssm_conv_size=4, ssm_chunk=64,
    num_experts=8, moe_router="topk", moe_top_k=2, moe_layers="all", moe_scoring="sigmoid",
    moe_renormalize=True, moe_routed_scale=2.5, moe_shared_experts=1, moe_shared_d_ff=56,
    moe_expert="relu2", moe_aux_loss_weight=0.0, moe_held=(2, 4), remat=True, dtype=jnp.float32,
)
REF_KW = dict(num_heads=4, num_kv_heads=2, ssm_groups=2, top_k=2, routed_scale=2.5, held_first=2)
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
    held = [v["moe"]["held_share"] for v in updated["moe_stats"].values()]
    return nll, sum(held) / len(held)


@pytest.fixture(scope="module")
def both(setup):
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "nemotron_h")
    with jax.default_matmul_precision("highest"):
        (logits, _), _ = model.apply({"params": params}, tokens, train=False, mutable=["moe_stats"])
        (total, held), grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, tokens, targets), has_aux=True
        )(params)
    want_logits = ref.forward(params, tokens, **REF_KW)
    (want_total, want_parts), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, targets, **REF_KW), has_aux=True
    )(params)
    return {
        "logits": (logits, want_logits),
        "loss": (total, want_total, want_parts["nll"]),
        "held_share": (held, want_parts["held_share"]),
        "grads": (dict(zip(_paths(grads), jax.tree.leaves(grads))),
                  dict(zip(_paths(want_grads), jax.tree.leaves(want_grads)))),
    }


def test_reference_forward_matches_the_model(both):
    got, want = both["logits"]
    assert got.shape == want.shape == (2, T, 97)
    # float32 at "highest" on both sides: reduction order only.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("what", ["loss", "held_share"])
def test_reference_loss_matches_the_model(both, what):
    got, want = both[what][:2]
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-6)
    if what == "loss":
        assert float(both["loss"][2]) == float(want)  # the cross entropy is the whole loss
    else:
        assert 0.2 < float(want) < 0.8  # half the experts held: the range is not the whole


# One leaf of each kind, by the paths of the small model; the test below
# checks that together with the per-layer sweep they are all the leaves.
LEAVES = [
    "embedding/embedding", "head/kernel", "ln_f/scale",
    *(f"blocks_2/ssm/{name}" for name in (
        "in_proj/kernel", "out_proj/kernel", "conv", "conv_bias", "A_log", "dt_bias", "D", "norm/scale")),
    *(f"blocks_5/attn/{name}" for name in ("query/kernel", "key/kernel", "value/kernel", "out/kernel")),
    *(f"blocks_3/moe/{name}" for name in ("router", "w_up", "w_down", "shared/up/kernel", "shared/down/kernel")),
    "blocks_0/ln1/scale", "blocks_5/ln1/scale", "blocks_1/ln2/scale",
]


def test_the_leaves_compared_cover_every_kind_of_leaf(both):
    got, want = both["grads"]
    assert set(got) == set(want) and not any("w_gate" in p or "/mlp/" in p for p in got)
    strip = lambda path: path.split("/", 1)[1] if path.startswith("blocks_") else path
    assert {strip(p) for p in got} == {strip(p) for p in LEAVES}
    # A layer is one sub-layer: one norm and one module.
    by_layer = {}
    for path in got:
        if path.startswith("blocks_"):
            layer, module = path.split("/")[:2]
            by_layer.setdefault(layer, set()).add(module)
    assert [sorted(by_layer[f"blocks_{i}"]) for i in range(9)] == [
        {"M": ["ln1", "ssm"], "E": ["ln2", "moe"], "*": ["attn", "ln1"]}[k] for k in "MEMEM*EME"
    ]


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
    ref = cells.load_module("references", "nemotron_h")
    short = tokens[:1, :60]
    base = ref.forward(params, short, **REF_KW)
    changed = ref.forward(params, short.at[:, 40].set((short[:, 40] + 1) % 97), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :40]), np.asarray(changed[:, :40]))
    assert float(jnp.abs(base[:, 40:] - changed[:, 40:]).max()) > 1e-4
    # Without the state-space layers (nothing else carries the order) one
    # token repeated reads the first position's logits everywhere.
    unordered = {k: v for k, v in params.items() if not (k.startswith("blocks_") and "ssm" in v)}
    same = ref.forward(unordered, jnp.full((1, 20), 7), **REF_KW)
    np.testing.assert_allclose(np.asarray(same), np.asarray(same[:, :1]).repeat(20, 1), atol=1e-5)


def test_the_reference_s_recurrence_is_the_op_s_oracle_with_groups():
    """``state_space`` (token by token, recomputing in blocks, a group's
    heads beside its ``B`` and ``C``) against ``ops/ssm.py::recurrent_ssd``
    with the group axis: the tests' two oracles are one recurrence."""
    from distributed_tensorflow_models_tpu.ops import ssm

    ref = cells.load_module("references", "nemotron_h")
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (2, T, 4, 8))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, T, 4)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (4,), minval=1.0, maxval=16.0))
    b, c = (jax.random.normal(key, (2, T, 2, 16)) for key in ks[3:])
    by_group = lambda y: y.reshape(2, T, 2, 2, *y.shape[3:])
    a = jnp.exp(-jnp.exp(a_log) * dt)
    got = ref.state_space(by_group(x), by_group(dt), by_group(a), b, c).reshape(x.shape)
    want = ssm.recurrent_ssd(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_a_lower_precision_would_not_pass(setup):
    """The reference in bfloat16 (what ``compare_reference_nemotron_h.py``
    holds to the bf16 tolerances on the chip) is far outside what float32
    agrees to here."""
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "nemotron_h")
    short = tokens[:1, :60]
    want = ref.forward(params, short, **REF_KW)
    low = ref.forward(params, short, **REF_KW, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) > 1e-3


def test_the_compare_tool_s_limits_and_leaves():
    """Without running it (the rehearsal is a minute of compiles; the
    builder runs it before chip time is spent): every bf16 limit is looser
    than its float32 limit where both are judged, the float32 program is
    judged before the first other choice, and the leaves it compares are
    leaves of this model."""
    from benchmark.tools import compare_reference_nemotron_h as tool

    both = {k: t for k, t in tool.TOLERANCES.items() if None not in t}
    for name, (bf16, f32) in both.items():
        assert (bf16 < f32) if name.endswith("_min") else (bf16 > f32), name
    assert tool.TOLERANCES["early_logit_max_over_spread"][0] is None
    assert tool.CONFIG == "nemotron3_nano"
    ssm = {"in_proj": {"kernel": 0}, "A_log": 1, "dt_bias": 2, "D": 3, "conv": 4, "norm": {"scale": 5}}
    attn = {"query": {"kernel": 6}, "key": {"kernel": 7}}
    moe = {"router": 8, "shared": {"up": {"kernel": 9}}, "w_up": [10, 11], "w_down": [12, 13]}
    tree = {"blocks_0": {"ssm": ssm}, "blocks_5": {"attn": attn}, "blocks_1": {"moe": moe}}
    names = {"ssm": "blocks_0", "attn": "blocks_5", "moe": "blocks_1"}
    picked = tool.selected(tree, names, 1)
    assert sorted(picked.values()) == [0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 11, 13]
