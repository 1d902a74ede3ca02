"""The plain reference kept beside the ``olmoe`` configuration
(``benchmark/references/olmoe.py``) against the program's model, on
seeded random weights at a small size: logits, the total loss, both
router losses, the gradient of every leaf, and prefill then decode
through the cache against the reference's full forward."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KW = dict(
    vocab_size=97, num_layers=2, num_heads=4, d_model=64, d_ff=32, max_len=32,
    dropout_rate=0.0, pos_encoding="rope", norm="rmsnorm", norm_eps=1e-5,
    use_bias=False, qk_norm=True, num_experts=8, moe_router="topk", moe_top_k=2,
    moe_layers="all", moe_z_loss_weight=0.001, dtype=jnp.float32,
)
REF_KW = dict(num_heads=4, top_k=2, eps=1e-5, theta=10000.0)
LOSS_KW = dict(aux_weight=0.01, z_weight=0.001)


def _paths(tree):
    return ["/".join(str(k.key) for k in path) for path, _ in jax.tree_util.tree_leaves_with_path(tree)]


@pytest.fixture(scope="module")
def setup():
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model("transformer_lm", **KW)
    tokens = jax.random.randint(jax.random.key(1), (3, 32), 0, KW["vocab_size"])
    params = model.init(jax.random.key(0), tokens)["params"]
    # Norm scales start at 1: move every leaf, so that a reference that
    # dropped a scale (or the QK-norm) would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves), tokens, jnp.roll(tokens, -1, axis=1)


def _program_loss(model, params, tokens, targets):
    """The objective as ``core/train_loop.py::lm_loss_fn`` composes it:
    cross entropy plus everything the model sowed into ``losses``."""
    (logits, _), updated = model.apply(
        {"params": params}, tokens, train=True, mutable=["losses", "moe_stats"]
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    total = nll + sum(jnp.sum(x) for x in jax.tree.leaves(updated["losses"]))
    stats = updated["moe_stats"]
    mean = lambda name: sum(stats[b]["moe"][name] for b in stats) / len(stats)
    return total, {"total": total, "nll": nll, "aux_loss": mean("aux_loss"), "z_loss": mean("z_loss")}


@pytest.fixture(scope="module")
def both(setup):
    model, params, tokens, targets = setup
    ref = cells.load_module("references", "olmoe")
    with jax.default_matmul_precision("highest"):
        logits, _ = model.apply({"params": params}, tokens, train=False)
        (_, parts), grads = jax.value_and_grad(
            lambda p: _program_loss(model, p, tokens, targets), has_aux=True
        )(params)
    want_logits = ref.forward(params, tokens, **REF_KW)
    (want_total, want_parts), want_grads = jax.value_and_grad(
        lambda p: ref.loss(p, tokens, targets, **REF_KW, **LOSS_KW), has_aux=True
    )(params)
    return {
        "logits": (logits, want_logits),
        "parts": (parts, {"total": want_total, **want_parts}),
        "grads": (dict(zip(_paths(grads), jax.tree.leaves(grads))),
                  dict(zip(_paths(want_grads), jax.tree.leaves(want_grads)))),
    }


def test_reference_forward_matches_the_model(both):
    got, want = both["logits"]
    assert got.shape == want.shape == (3, 32, 97)
    # float32 on the CPU: the two differ by reduction order only (the
    # program sorts and groups, the reference applies every expert).
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("part", ["total", "nll", "aux_loss", "z_loss"])
def test_reference_losses_match_the_model(both, part):
    got, want = both["parts"]
    # float32 sums over 96 tokens and 8 experts: reduction order only.
    assert float(got[part]) == pytest.approx(float(want[part]), rel=2e-6, abs=2e-6)
    assert float(want[part]) > 0


# 2 layers x 12 leaves, the embedding, the final norm and the head.
LEAVES = [
    f"blocks_{i}/{leaf}"
    for i in range(2)
    for leaf in (
        "ln1/scale", "ln2/scale", "attn/query/kernel", "attn/key/kernel",
        "attn/value/kernel", "attn/out/kernel", "attn/q_norm/scale",
        "attn/k_norm/scale", "moe/router", "moe/w_gate", "moe/w_up", "moe/w_down",
    )
] + ["embedding/embedding", "ln_f/scale", "head/kernel"]


def test_the_leaves_compared_are_all_the_leaves(both):
    got, want = both["grads"]
    assert sorted(got) == sorted(want) == sorted(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_reference_gradient_matches_the_model(both, leaf):
    got, want = both["grads"]
    g, w = np.asarray(got[leaf]), np.asarray(want[leaf])
    assert np.abs(w).max() > 0
    # float32 on the CPU, reduction order only; the tolerance is relative
    # to the leaf's largest gradient entry (a leaf's small entries are
    # sums of cancelling terms).
    np.testing.assert_allclose(g, w, atol=2e-5 * float(np.abs(w).max()) + 1e-8, rtol=1e-4)


def test_prefill_then_decode_matches_the_reference_forward(setup):
    """Serving's path through the block (QK-norm and RoPE through the
    cache, experts on one token a sequence) against the reference's full
    forward: logits, not tokens."""
    from distributed_tensorflow_models_tpu.models import get_model

    _, params, tokens, _ = setup
    ref = cells.load_module("references", "olmoe")
    want = ref.forward(params, tokens, **REF_KW)
    decoder = get_model("transformer_lm", **KW, decode=True)
    with jax.default_matmul_precision("highest"):
        (got, _), state = decoder.apply({"params": params}, tokens[:, :20], mutable=["cache"])
        pieces = [got]
        for t in range(20, 32):
            (got, _), state = decoder.apply(
                {"params": params, "cache": state["cache"]}, tokens[:, t : t + 1], mutable=["cache"]
            )
            pieces.append(got)
    # float32 on the CPU: reduction order only.
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(pieces, axis=1)), np.asarray(want), atol=2e-5, rtol=1e-5
    )


def test_reference_is_causal_and_routes_every_token_to_k_experts(setup):
    _, params, tokens, _ = setup
    ref = cells.load_module("references", "olmoe")
    base = ref.forward(params, tokens, **REF_KW)
    moved = ref.forward(params, tokens.at[:, 10].set(5), **REF_KW)
    np.testing.assert_array_equal(np.asarray(base[:, :10]), np.asarray(moved[:, :10]))
    assert not np.allclose(np.asarray(base[:, 10:]), np.asarray(moved[:, 10:]))
    for chosen in ref.routing(params, tokens, **REF_KW):
        assert chosen.shape == (96, 8) and np.all(np.asarray(chosen).sum(-1) == 2)
    # Ties go to the lower index, and exactly k are chosen.
    tied = jnp.asarray([[0.2, 0.2, 0.2, 0.1, 0.1, 0.1, 0.05, 0.05]])
    np.testing.assert_array_equal(
        np.asarray(ref.top_k_mask(tied, 2))[0], [True, True] + [False] * 6
    )


def test_a_lower_precision_would_not_pass(setup):
    """The tolerance above is tight enough that the program computed in
    bfloat16 (the nearest precision below the float32 the test states)
    fails it by orders of magnitude."""
    from distributed_tensorflow_models_tpu.models import get_model

    _, params, tokens, _ = setup
    ref = cells.load_module("references", "olmoe")
    want = np.asarray(ref.forward(params, tokens, **REF_KW))
    low, _ = get_model("transformer_lm", **{**KW, "dtype": jnp.bfloat16}).apply(
        {"params": params}, tokens, train=False
    )
    assert float(np.abs(np.asarray(low) - want).max()) > 100 * 2e-5
