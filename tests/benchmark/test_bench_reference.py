"""The plain reference kept beside the ``gpt2m`` configuration against
the program's model, on seeded random weights at a small size."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_testlib  # noqa: F401
from benchmark.lib import cells

KW = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=32, d_ff=64, max_len=16)


@pytest.fixture(scope="module")
def setup():
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model("transformer_lm", **KW, dropout_rate=0.0, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (3, 16), 0, KW["vocab_size"])
    params = model.init(jax.random.key(0), tokens)["params"]
    # Biases and LayerNorm offsets start at 0: move them, so that a
    # reference that dropped one would be caught.
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(2), len(leaves))
    leaves = [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    return model, jax.tree.unflatten(tree, leaves), tokens


def test_reference_forward_matches_the_model(setup):
    model, params, tokens = setup
    ref = cells.load_module("references", "gpt2")
    with jax.default_matmul_precision("highest"):
        want, _ = model.apply({"params": params}, tokens, train=False)
    got = ref.forward(params, tokens, num_heads=KW["num_heads"])
    assert got.shape == want.shape == (3, 16, 97)
    # float32 on the CPU: the two differ by reduction order only.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


def test_reference_is_causal_and_its_loss_is_a_cross_entropy(setup):
    _, params, tokens = setup
    ref = cells.load_module("references", "gpt2")
    base = ref.forward(params, tokens, num_heads=KW["num_heads"])
    moved = ref.forward(params, tokens.at[:, 10].set(5), num_heads=KW["num_heads"])
    np.testing.assert_array_equal(np.asarray(base[:, :10]), np.asarray(moved[:, :10]))
    assert not np.allclose(np.asarray(base[:, 10:]), np.asarray(moved[:, 10:]))
    targets = jnp.roll(tokens, -1, axis=1)
    value = float(ref.loss(params, tokens, targets, num_heads=KW["num_heads"]))
    assert 0.5 * np.log(97) < value < 2.0 * np.log(97)


def test_reference_margins_tell_the_model_s_tokens_from_others(setup):
    """What the serving runner's ``correct`` rests on: a stream the
    reference itself would have chosen is at margin 0, a stream that has
    nothing to do with the model is standard deviations away."""
    _, params, tokens = setup
    ref = cells.load_module("references", "gpt2")
    runner = cells.load_module("runners", "serve_loop")
    prompt = [int(t) for t in tokens[0, :5]]
    forward = jax.jit(lambda t: ref.forward(params, t, num_heads=KW["num_heads"]))
    own = []
    for _ in range(6):
        # Causal: what is padded behind the text changes nothing before it.
        text = prompt + own
        padded = jnp.asarray([text + [0] * (KW["max_len"] - len(text))])
        own.append(int(jnp.argmax(forward(padded)[0, len(text) - 1])))
    args = (ref, params, KW["num_heads"], KW["max_len"])
    assert runner.reference_margins(*args, [(prompt, own)]) == [0.0] * 6
    other = [(t + 41) % KW["vocab_size"] for t in own]
    far = runner.reference_margins(*args, [(prompt, other), (prompt, own)])
    assert len(far) == 12 and min(far[:6]) > 0.25 and max(far[:6]) > 1.0
    assert far[6:] == [0.0] * 6
    assert runner.reference_margins(*args, []) == []
