"""The plain reference kept beside the ``phi4_mini_flash`` configuration
(``benchmark/references/phi4_flash.py``) against the program's model, on
seeded random weights at a small size in float32 at ``highest``: logits,
the loss and the gradient of every leaf, the tied embedding's among them.
Every leaf is moved off its initial value (norm scales and biases, the
projections' biases, ``A_log``, ``dt_bias``, ``D``, the convolution's bias
and the four ``lambda`` vectors among them), so that a term dropped on
either side shows.  The stack holds **two** periods of the cross-decoder,
so each source layer (the Mamba-1 layer whose scan output is the memory,
the full attention whose keys and values are handed on) has two readers
and its gradient is the sum over them; 8 query heads over 4 key/value
heads in pairs, a window of 8."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

LAYERS = ("mamba1", "attention", "mamba1", "attention_full", "gmu", "cross", "gmu", "cross")
IDS = (0, 1, 16, 17, 18, 19, 20, 21)
KW = dict(
    vocab_size=97, num_layers=8, layer_mixers=LAYERS, layer_ids=IDS, num_heads=8, num_kv_heads=4,
    d_model=64, d_ff=96, max_len=136, dropout_rate=0.0, pos_encoding="none", norm="layernorm",
    norm_eps=1e-5, use_bias=False, attn_bias=True, mlp="gated_silu", attn_window=8,
    attn_differential=True, mamba1_inner=96, mamba1_state_dim=4, mamba1_dt_rank=4, mamba1_chunk=32,
    tie_embeddings=True, remat=True, dtype=jnp.float32,
)
REF_KW = dict(
    layers=("mamba", "window", "mamba", "full", "gmu", "cross", "gmu", "cross"), layer_ids=IDS,
    num_heads=8, num_kv_heads=4, window=8,
)
# 136 tokens: four whole chunks of 32 and a rest; one whole block of the
# reference's recomputation (128) and a rest.
T = 136


def _paths(tree):
    return ["/".join(str(k.key) for k in path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


def _moved(params, seed=2):
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


@pytest.fixture(scope="module")
def setup():
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model("transformer_lm", **KW)
    tokens = jax.random.randint(jax.random.key(1), (2, T), 0, KW["vocab_size"])
    params = _moved(jax.jit(model.init)(jax.random.key(0), tokens)["params"])
    return model, params, tokens, jnp.roll(tokens, -1, axis=1)


def _program_loss(model, params, tokens, targets):
    (logits, _), updated = model.apply({"params": params}, tokens, train=True, mutable=["losses"])
    logp = jax.nn.log_softmax(logits, axis=-1)
    assert not jax.tree.leaves(updated.get("losses", {}))  # no auxiliary loss
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))


@pytest.fixture(scope="module")
def both(setup):
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "phi4_flash")

    @jax.jit
    def program(p):
        with jax.default_matmul_precision("highest"):
            logits, _ = model.apply({"params": p}, tokens, train=False)
            return logits, jax.value_and_grad(lambda q: _program_loss(model, q, tokens, targets))(p)

    @jax.jit
    def reference(p):
        return ref.forward(p, tokens, **REF_KW), jax.value_and_grad(
            lambda q: ref.loss(q, tokens, targets, **REF_KW), has_aux=True
        )(p)

    logits, (total, grads) = program(params)
    want_logits, ((want_total, want_parts), want_grads) = reference(params)
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
# No ``head``: the embedding is the head.  No ``key/bias``: a softmax does
# not see a constant added to every key's score, its gradient is zero.
MAMBA = ("in_proj/kernel", "conv", "conv_bias", "x_proj/kernel", "dt_proj/kernel", "dt_bias", "A_log", "D",
         "out_proj/kernel")
ATTENTION = ("query/kernel", "query/bias", "key/kernel", "value/kernel", "value/bias", "out/kernel", "out/bias",
             "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2", "subln/scale")
LEAVES = [
    "embedding/embedding", "ln_f/scale", "ln_f/bias",
    *(f"blocks_2/ssm/{name}" for name in MAMBA),  # the source of the memory: two readers
    *(f"blocks_1/attn/{name}" for name in ATTENTION),  # under the window
    *(f"blocks_3/attn/{name}" for name in ATTENTION),  # the source of keys and values: two readers
    *(f"blocks_6/ssm/{name}" for name in ("in_proj/kernel", "out_proj/kernel")),
    *(f"blocks_7/attn/{name}" for name in ATTENTION if not name.startswith(("key", "value"))),
    "blocks_3/mlp/gate/kernel", "blocks_3/mlp/up/kernel", "blocks_3/mlp/down/kernel",
    "blocks_0/ln1/scale", "blocks_4/ln1/bias", "blocks_5/ln2/scale", "blocks_0/ln2/bias",
]


def test_the_leaves_compared_cover_every_kind_of_leaf(both):
    got, want = both["grads"]
    assert set(got) == set(want) and "head/kernel" not in got
    strip = lambda path: path.split("/", 1)[1] if path.startswith("blocks_") else path
    assert {strip(p) for p in got} == {strip(p) for p in LEAVES} | {"attn/key/bias"}
    assert not any(p.startswith(("blocks_5/attn/key", "blocks_5/attn/value", "blocks_4/ssm/A_log")) for p in got)


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
    scale = max(np.linalg.norm(np.asarray(w)) for w in want.values())
    for leaf in got:
        g, w = np.asarray(got[leaf], np.float64), np.asarray(want[leaf], np.float64)
        # A key bias's gradient is zero but for rounding: held to the
        # largest leaf's scale.
        floor = 1e-6 * scale if leaf.endswith("key/bias") else 1e-9
        assert np.linalg.norm(g - w) <= 1e-4 * np.linalg.norm(w) + floor, leaf


def test_reference_is_causal_and_the_window_is_a_window(setup):
    model, params, tokens, _ = setup
    ref = cells.load_module("references", "phi4_flash")
    short = tokens[:1, :40]
    base = ref.forward(params, short, **REF_KW)
    changed = ref.forward(params, short.at[:, 30].set((short[:, 30] + 1) % 97), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :30]), np.asarray(changed[:, :30]))
    assert float(jnp.abs(base[:, 30:] - changed[:, 30:]).max()) > 1e-4
    # The window layer alone, as a stack of one kind: a token further back
    # than the window moves nothing (no Mamba layer carries it forward).
    only = {"embedding": params["embedding"], "ln_f": params["ln_f"], "blocks_0": params["blocks_1"]}
    kw = dict(REF_KW, layers=("window",), layer_ids=(1,))
    base = ref.forward(only, short, **kw)
    changed = ref.forward(only, short.at[:, 10].set((short[:, 10] + 1) % 97), **kw)
    assert float(jnp.abs(base[:, 10:18] - changed[:, 10:18]).max()) > 1e-5
    np.testing.assert_allclose(np.asarray(base[:, 18:]), np.asarray(changed[:, 18:]), atol=1e-6)
    full = dict(kw, layers=("full",))
    assert float(jnp.abs(ref.forward(only, short, **full)[:, 18:] - base[:, 18:]).max()) > 1e-5


def test_the_reference_s_recurrence_is_the_op_s_oracle():
    """``selective_scan`` of the reference (token by token, recomputing in
    blocks) against ``ops/selective_scan.py::recurrent_selective_scan``:
    the tests' two oracles are one recurrence."""
    from distributed_tensorflow_models_tpu.ops import selective_scan as sscan

    ref = cells.load_module("references", "phi4_flash")
    ks = jax.random.split(jax.random.key(4), 5)
    x = jax.random.normal(ks[0], (2, T, 24))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, T, 24)) - 2.0)
    a_log = jnp.log(jax.random.uniform(ks[2], (24, 4), minval=1.0, maxval=16.0))
    b, c = (jax.random.normal(key, (2, T, 4)) for key in ks[3:])
    got = ref.selective_scan(x, dt, -jnp.exp(a_log), b, c)
    want = sscan.recurrent_selective_scan(x, dt, a_log, b, c)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_a_lower_precision_or_a_dropped_lambda_would_not_pass(setup):
    """The reference in bfloat16 (what ``compare_reference_phi4_flash.py``
    holds to the bf16 tolerances on the chip) is far outside what float32
    agrees to here, and so is a model whose ``lambda`` is its
    ``lambda_init`` alone (the learned vectors zeroed)."""
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "phi4_flash")
    short = tokens[:1, :40]
    want = ref.forward(params, short, **REF_KW)
    low = ref.forward(params, short, **REF_KW, dtype=jnp.bfloat16)
    assert low.dtype == jnp.bfloat16
    assert float(jnp.abs(low.astype(jnp.float32) - want).max()) > 1e-3
    dropped = jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x) if path[-1].key.startswith("lambda_") else x, params
    )
    assert float(jnp.abs(ref.forward(dropped, short, **REF_KW) - want).max()) > 1e-3


@pytest.mark.slow  # five programs compiled: 35 s (CHANGES.md, PR 44)
def test_compare_tool_rehearses_on_the_cpu(capsys):
    import json

    from benchmark.tools import compare_reference_phi4_flash as tool

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
    # The weights are the seed's in every process, moved off their initial
    # values, and the bf16 side differentiates the loss of ``fit``'s step.
    import inspect

    assert "hash(" not in inspect.getsource(tool)
    assert "trainlib.build_loss(cfg, state)" in inspect.getsource(tool.program_side)
    config = tool.load_config(rehearse=True)
    params, again, other = (tool.build(config, seed, 1)[2] for seed in (3, 3, 4))
    assert "head" not in params
    for block, group, leaf in (("blocks_0", "ssm", "D"), ("blocks_1", "attn", "lambda_q1")):
        get = lambda p: np.asarray(p[block][group][leaf])
        np.testing.assert_array_equal(get(params), get(again))
        assert not np.array_equal(get(params), get(other))
    assert not np.array_equal(np.asarray(params["blocks_0"]["ssm"]["D"]), 1.0)  # moved off its ones
    assert float(np.abs(np.asarray(params["blocks_5"]["attn"]["query"]["bias"])).max()) > 0
