"""Beam search: exhaustive-search oracle, beam-1 == greedy, shapes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.generate import (
    beam_search,
    generate,
)
from distributed_tensorflow_models_tpu.models import get_model


@pytest.fixture(scope="module")
def tiny_lm():
    # Vocab 3 so K=27 covers every 3-step continuation exhaustively.
    model = get_model(
        "transformer_lm",
        vocab_size=3,
        num_layers=1,
        num_heads=2,
        d_model=16,
        d_ff=32,
        max_len=16,
        dropout_rate=0.0,
        dtype=jnp.float32,
        attn_impl="reference",
    )
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 2), jnp.int32)
    )["params"]
    return model, params


def _brute_force_best(model, params, prompt, steps):
    """Enumerate every vocab^steps continuation; return (seq, logprob)."""
    import itertools

    V = model.vocab_size
    # Jitted: one compile a length, where the bare call compiles every operation of each by itself.
    forward = jax.jit(lambda p, t: model.apply({"params": p}, t, train=False))
    best_lp, best_seq = -np.inf, None
    for cont in itertools.product(range(V), repeat=steps):
        toks = prompt
        lp = 0.0
        for t in cont:
            logits, _ = forward(params, toks)
            logp = jax.nn.log_softmax(
                logits[0, -1].astype(jnp.float32)
            )
            lp += float(logp[t])
            toks = jnp.concatenate(
                [toks, jnp.asarray([[t]], jnp.int32)], axis=1
            )
        if lp > best_lp:
            best_lp, best_seq = lp, cont
    return best_seq, best_lp


def test_beam_matches_exhaustive_search(tiny_lm):
    """K = V^steps makes beam search exhaustive: its best sequence and
    score must equal brute force over all continuations."""
    model, params = tiny_lm
    prompt = jnp.asarray([[1, 2]], jnp.int32)
    steps = 3
    out, score = beam_search(
        model, params, prompt, steps, beam_size=27
    )
    bf_seq, bf_lp = _brute_force_best(model, params, prompt, steps)
    assert tuple(np.asarray(out)[0, 2:]) == bf_seq, (
        np.asarray(out)[0, 2:], bf_seq
    )
    np.testing.assert_allclose(float(score[0]), bf_lp, rtol=1e-4)


def test_beam_one_equals_greedy(tiny_lm):
    model, params = tiny_lm
    prompt = jnp.asarray([[0, 1], [2, 0]], jnp.int32)
    greedy = generate(model, params, prompt, 5)
    beam, _ = beam_search(model, params, prompt, 5, beam_size=1)
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(beam))


def test_exhaustive_beam_dominates_narrower(tiny_lm):
    """K = V^steps IS exhaustive, so its best score bounds any narrower
    beam's from above.  (Generic beam-width monotonicity is a known
    non-theorem — a wider-but-not-exhaustive beam can prune the greedy
    prefix — so only the exhaustive bound is asserted.)"""
    model, params = tiny_lm
    prompt = jnp.asarray([[1, 0]], jnp.int32)
    steps = 3
    _, s1 = beam_search(model, params, prompt, steps, beam_size=1)
    _, s_ex = beam_search(model, params, prompt, steps, beam_size=27)
    assert float(s_ex[0]) >= float(s1[0]) - 1e-5


def test_beam_shapes_and_bounds(tiny_lm):
    model, params = tiny_lm
    prompt = jnp.zeros((3, 2), jnp.int32)
    out, score = beam_search(model, params, prompt, 4, beam_size=2)
    assert out.shape == (3, 6)
    assert score.shape == (3,)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < 3).all()
    with pytest.raises(ValueError):
        beam_search(model, params, prompt, 0)
    with pytest.raises(ValueError):
        beam_search(model, params, prompt, 99)
