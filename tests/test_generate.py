"""KV-cache decode correctness: cached per-token logits must equal the
full-sequence forward, and `generate` must reproduce a naive
recompute-everything greedy loop."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.generate import generate
from distributed_tensorflow_models_tpu.models import get_model


@functools.cache
def _jitted(model, mutable=False):
    """``model.apply`` under ``jit``: one compile a shape, where the bare
    call compiles every operation of every new length by itself."""
    return jax.jit(lambda variables, *args: model.apply(variables, *args, train=False, mutable=mutable))


@pytest.fixture(scope="module")
def small_lm():
    model = get_model(
        "transformer_lm",
        vocab_size=50,
        num_layers=2,
        num_heads=2,
        d_model=32,
        d_ff=64,
        max_len=32,
        dropout_rate=0.0,
        dtype=jnp.float32,
        attn_impl="reference",
    )
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def test_decode_logits_match_full_forward(small_lm):
    """Token-by-token decode through the KV cache reproduces the full
    forward's logits at every position — the exact invariant the cache
    exists to preserve."""
    model, params = small_lm
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, 50, (2, 10)), jnp.int32)

    full_logits, _ = _jitted(model)({"params": params}, tokens)

    decode_model = model.clone(decode=True)
    cache = {}
    step_logits = []
    for t in range(tokens.shape[1]):
        variables = {"params": params}
        if cache:
            variables["cache"] = cache
        (lg, _), mut = _jitted(decode_model, mutable=("cache",))(
            variables, tokens[:, t : t + 1]
        )
        cache = mut["cache"]
        step_logits.append(lg[:, 0])
    np.testing.assert_allclose(
        jnp.stack(step_logits, axis=1), full_logits, rtol=1e-4, atol=1e-4
    )


def test_decode_prompt_chunk_then_steps(small_lm):
    """A multi-token prompt pass followed by single-token steps lands on
    the same logits as all-single-token decoding (positions and cache
    indices advance consistently for T>1 writes)."""
    model, params = small_lm
    rng = np.random.RandomState(1)
    tokens = jnp.asarray(rng.randint(0, 50, (1, 8)), jnp.int32)
    decode_model = model.clone(decode=True)

    (lg_prompt, _), mut = _jitted(decode_model, mutable=("cache",))(
        {"params": params}, tokens[:, :5]
    )
    (lg6, _), _ = _jitted(decode_model, mutable=("cache",))(
        {"params": params, "cache": mut["cache"]},
        tokens[:, 5:6],
    )
    full_logits, _ = _jitted(model)(
        {"params": params}, tokens[:, :6]
    )
    np.testing.assert_allclose(
        lg_prompt, full_logits[:, :5], rtol=1e-4, atol=1e-4
    )
    np.testing.assert_allclose(
        lg6[:, 0], full_logits[:, 5], rtol=1e-4, atol=1e-4
    )


def test_generate_matches_naive_greedy(small_lm):
    """generate() (scan + cache) == recompute-the-whole-prefix greedy."""
    model, params = small_lm
    rng = np.random.RandomState(2)
    prompt = jnp.asarray(rng.randint(0, 50, (2, 4)), jnp.int32)
    max_new = 6

    out = generate(model, params, prompt, max_new)
    assert out.shape == (2, 4 + max_new)

    toks = prompt
    for _ in range(max_new):
        logits, _ = _jitted(model)({"params": params}, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


def test_generate_eos_freeze(small_lm):
    """Rows that hit eos keep emitting eos for the rest of the (static
    length) generation — eos_id is chosen as the model's actual first
    greedy token so the freeze path deterministically triggers."""
    model, params = small_lm
    prompt = jnp.zeros((1, 2), jnp.int32)
    logits, _ = _jitted(model)({"params": params}, prompt)
    eos = int(jnp.argmax(logits[0, -1]))
    out = generate(model, params, prompt, 8, eos_id=eos)
    gen = np.asarray(out)[0, 2:]
    assert gen[0] == eos
    assert (gen == eos).all(), gen


def test_generate_rejects_overflow(small_lm):
    model, params = small_lm
    with pytest.raises(ValueError):
        generate(model, params, jnp.zeros((1, 30), jnp.int32), 8)


def test_generate_zero_and_negative_new_tokens(small_lm):
    model, params = small_lm
    prompt = jnp.zeros((2, 3), jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(generate(model, params, prompt, 0)), np.asarray(prompt)
    )
    with pytest.raises(ValueError):
        generate(model, params, prompt, -1)


def test_generate_temperature_sampling_runs(small_lm):
    model, params = small_lm
    prompt = jnp.zeros((2, 3), jnp.int32)
    out = generate(
        model, params, prompt, 5,
        temperature=1.0, rng=jax.random.key(3),
    )
    assert out.shape == (2, 8)
    assert (np.asarray(out) >= 0).all() and (np.asarray(out) < 50).all()


def test_filter_logits_top_k():
    from distributed_tensorflow_models_tpu.harness.generate import (
        _filter_logits,
    )

    logits = jnp.asarray([[1.0, 3.0, 2.0, 0.5]])
    out = np.asarray(_filter_logits(logits, top_k=2, top_p=1.0))
    assert np.isfinite(out[0, [1, 2]]).all()
    assert np.isinf(out[0, [0, 3]]).all() and (out[0, [0, 3]] < 0).all()


def test_filter_logits_top_p():
    from distributed_tensorflow_models_tpu.harness.generate import (
        _filter_logits,
    )

    # probs ~ [0.643, 0.236, 0.087, 0.032]: top_p=0.6 keeps only the top
    # token (first-prefix >= p rule); top_p=0.7 keeps the top two.
    logits = jnp.log(jnp.asarray([[0.643, 0.236, 0.087, 0.032]]))
    out6 = np.asarray(_filter_logits(logits, 0, 0.6))
    assert np.isfinite(out6[0, 0]) and np.isinf(out6[0, 1:]).all()
    out7 = np.asarray(_filter_logits(logits, 0, 0.7))
    assert np.isfinite(out7[0, :2]).all() and np.isinf(out7[0, 2:]).all()


def test_filter_logits_degenerate_knobs():
    from distributed_tensorflow_models_tpu.harness.generate import (
        _filter_logits,
    )

    logits = jnp.asarray([[1.0, 3.0, 2.0, 0.5]])
    # top_k beyond vocab: no-op.
    np.testing.assert_array_equal(
        np.asarray(_filter_logits(logits, top_k=100, top_p=1.0)),
        np.asarray(logits),
    )
    # top_p=0: keeps exactly the argmax (greedy), not an all--inf row.
    out = np.asarray(_filter_logits(logits, 0, 0.0))
    assert np.isfinite(out[0, 1])
    assert np.isinf(out[0, [0, 2, 3]]).all()


def test_filter_logits_top_k_fast_path_matches_sort():
    """The top-k-only configuration takes a ``lax.top_k`` partial
    selection instead of the full vocab sort; this pins the fast path
    BIT-identical to the reference sort-based filter — including ties
    at the k-th boundary, where both paths threshold on the identical
    k-th VALUE (so equal values are kept by both or masked by both)."""
    from distributed_tensorflow_models_tpu.harness.generate import (
        _filter_logits,
    )

    def sort_reference(logits, top_k):
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        kth = sorted_logits[
            ..., min(top_k, logits.shape[-1]) - 1
        ][..., None]
        return jnp.where(logits < kth, -jnp.inf, logits)

    rng = jax.random.key(0)
    for trial in range(5):
        rng, k = jax.random.split(rng)
        logits = jax.random.normal(k, (3, 101)) * 4
        for top_k in (1, 2, 3, 50, 101, 500):
            np.testing.assert_array_equal(
                np.asarray(_filter_logits(logits, top_k, 1.0)),
                np.asarray(sort_reference(logits, top_k)),
                err_msg=f"trial {trial} top_k {top_k}",
            )
    # Ties straddling the k-th position.
    tied = jnp.asarray([[1.0, 2.0, 2.0, 2.0, 0.5, 3.0]])
    for top_k in (1, 2, 3, 4, 5):
        np.testing.assert_array_equal(
            np.asarray(_filter_logits(tied, top_k, 1.0)),
            np.asarray(sort_reference(tied, top_k)),
            err_msg=f"tied top_k {top_k}",
        )


def test_generate_top_k_sampling_pinned_to_sort_path(small_lm, monkeypatch):
    """End-to-end pin of the fast path: a top-k sampled generation must
    be BYTE-identical to the same generation with ``_filter_logits``
    swapped for the reference full-sort implementation.  If this fails,
    the ``lax.top_k`` optimisation moved sampled token streams — a
    correctness regression, not a perf detail."""
    from distributed_tensorflow_models_tpu.harness import generate as genlib

    model, params = small_lm
    prompt = jnp.zeros((2, 3), jnp.int32)
    fast = generate(
        model, params, prompt, 8,
        temperature=0.8, top_k=5, rng=jax.random.key(17),
    )

    def sort_filter(logits, top_k, top_p):
        if top_k <= 0 and top_p >= 1.0:
            return logits
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        if top_k > 0:
            kth = sorted_logits[
                ..., min(top_k, logits.shape[-1]) - 1
            ][..., None]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
            sorted_logits = jnp.where(
                sorted_logits < kth, -jnp.inf, sorted_logits
            )
        if top_p < 1.0:
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            keep = (cum - probs < top_p).at[..., 0].set(True)
            cutoff = jnp.min(
                jnp.where(keep, sorted_logits, jnp.inf),
                axis=-1, keepdims=True,
            )
            logits = jnp.where(logits < cutoff, -jnp.inf, logits)
        return logits

    monkeypatch.setattr(genlib, "_filter_logits", sort_filter)
    reference = generate(
        model, params, prompt, 8,
        temperature=0.8, top_k=5, rng=jax.random.key(17),
    )
    np.testing.assert_array_equal(np.asarray(fast), np.asarray(reference))


def test_generate_top_k_one_equals_greedy(small_lm):
    """temperature>0 with top_k=1 must reduce to greedy argmax."""
    model, params = small_lm
    prompt = jnp.zeros((2, 3), jnp.int32)
    greedy = generate(model, params, prompt, 5)
    sampled = generate(
        model, params, prompt, 5,
        temperature=1.0, top_k=1, rng=jax.random.key(9),
    )
    np.testing.assert_array_equal(np.asarray(greedy), np.asarray(sampled))


def test_gqa_decode_matches_full_forward():
    """GQA model (2 KV heads under 4 query heads): cached decode logits
    == full forward, and the cache is actually the smaller shape."""
    model = get_model(
        "transformer_lm",
        vocab_size=50,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        d_model=32,
        d_ff=64,
        max_len=16,
        dropout_rate=0.0,
        dtype=jnp.float32,
        attn_impl="reference",
    )
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    rng = np.random.RandomState(7)
    tokens = jnp.asarray(rng.randint(0, 50, (2, 8)), jnp.int32)
    full_logits, _ = _jitted(model)({"params": params}, tokens)

    decode_model = model.clone(decode=True)
    (lg, _), mut = _jitted(decode_model, mutable=("cache",))(
        {"params": params}, tokens
    )
    np.testing.assert_allclose(lg, full_logits, rtol=1e-4, atol=1e-4)
    ck = mut["cache"]["blocks_0"]["attn"]["cached_key"]
    assert ck.shape == (2, 16, 2, 8), ck.shape  # Hkv=2, Dh=32/4


def test_generate_rnn_matches_naive_greedy():
    """Carry-threaded LSTM decode == recompute-the-whole-prefix greedy."""
    from distributed_tensorflow_models_tpu.harness.generate import (
        generate_rnn,
    )

    model = get_model(
        "ptb_lstm", config="small", vocab_size=40, dropout_rate=0.0
    )
    rng = np.random.RandomState(11)
    prompt = jnp.asarray(rng.randint(0, 40, (2, 5)), jnp.int32)
    params = jax.jit(model.init)(
        jax.random.key(0), prompt, model.initial_carry(2)
    )["params"]

    out = generate_rnn(model, params, prompt, 6)
    assert out.shape == (2, 11)

    toks = prompt
    for _ in range(6):
        logits, _ = _jitted(model)(
            {"params": params}, toks, model.initial_carry(2)
        )
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))


@pytest.mark.slow
def test_cli_train_then_generate(tmp_path):
    """The user surface: train a transformer_lm checkpoint via the CLI,
    then sample from it with the generate subcommand."""
    import json

    from distributed_tensorflow_models_tpu.harness import cli

    wd = str(tmp_path / "wd")
    rc = cli.main(
        ["train", "--config", "transformer_lm", "--workdir", wd,
         "--train-steps", "2", "--batch-size", "8"]
    )
    assert rc == 0
    rc = cli.main(
        ["generate", "--config", "transformer_lm", "--workdir", wd,
         "--prompt", "5,6,7", "--max-new-tokens", "4"],
    )
    assert rc == 0


def test_cli_generate_rejects_non_lm(tmp_path):
    from distributed_tensorflow_models_tpu.harness import cli

    with pytest.raises(SystemExit):
        cli.main(
            ["generate", "--config", "lenet_mnist",
             "--workdir", str(tmp_path)]
        )
