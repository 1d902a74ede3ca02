"""RoPE properties: relative-position invariance, decode parity, and the
train->generate round trip with pos_encoding='rope'."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import rotary
from distributed_tensorflow_models_tpu.models import get_model


@functools.cache
def _jitted(model, mutable=False):
    """``model.apply`` under ``jit``: one compile a shape, where the bare
    call compiles every operation of every new length by itself."""
    return jax.jit(lambda variables, *args: model.apply(variables, *args, train=False, mutable=mutable))


def test_rope_is_relative():
    """Attention over RoPE'd q/k must be invariant to a global position
    shift — the defining property of rotary embeddings."""
    rng = np.random.RandomState(0)
    B, T, H, D = 2, 32, 2, 16
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    k = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))
    v = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32))

    def attn_at(offset):
        pos = offset + jnp.arange(T)
        qr = rotary.apply_rope(q, pos)
        kr = rotary.apply_rope(k, pos)
        return attnlib.reference_attention(qr, kr, v, causal=True)

    np.testing.assert_allclose(
        attn_at(0), attn_at(117), rtol=1e-4, atol=1e-4
    )


def test_rope_changes_with_relative_distance():
    """Sanity: rotating only k (not q) by a shift must change outputs —
    guards against apply_rope silently being a no-op."""
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(1, 16, 2, 16).astype(np.float32))
    a = rotary.apply_rope(x, jnp.arange(16))
    b = rotary.apply_rope(x, 5 + jnp.arange(16))
    assert float(jnp.max(jnp.abs(a - b))) > 1e-3
    # Position 0 is the identity rotation.
    np.testing.assert_allclose(
        rotary.apply_rope(x, jnp.zeros((16,), jnp.int32)), x, atol=1e-6
    )


def test_rope_rejects_odd_dim():
    with pytest.raises(ValueError):
        rotary.rope_angles(jnp.arange(4), 15)


@pytest.fixture(scope="module")
def rope_lm():
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
        pos_encoding="rope",
    )
    params = jax.jit(model.init)(
        jax.random.key(0), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    return model, params


def test_rope_has_no_pos_table(rope_lm):
    model, params = rope_lm
    assert "pos_embedding" not in params


def test_rope_decode_matches_full_forward(rope_lm):
    """Cached decode (keys cached post-rotation, queries rotated by the
    cache index) == full forward."""
    model, params = rope_lm
    rng = np.random.RandomState(2)
    tokens = jnp.asarray(rng.randint(0, 50, (2, 10)), jnp.int32)
    full_logits, _ = _jitted(model)({"params": params}, tokens)

    decode_model = model.clone(decode=True)
    cache = {}
    outs = []
    for t in range(tokens.shape[1]):
        variables = {"params": params}
        if cache:
            variables["cache"] = cache
        (lg, _), mut = _jitted(decode_model, mutable=("cache",))(
            variables, tokens[:, t : t + 1]
        )
        cache = mut["cache"]
        outs.append(lg[:, 0])
    np.testing.assert_allclose(
        jnp.stack(outs, axis=1), full_logits, rtol=1e-4, atol=1e-4
    )


def test_rope_generate_matches_naive(rope_lm):
    from distributed_tensorflow_models_tpu.harness.generate import generate

    model, params = rope_lm
    rng = np.random.RandomState(3)
    prompt = jnp.asarray(rng.randint(0, 50, (2, 4)), jnp.int32)
    out = generate(model, params, prompt, 5)
    toks = prompt
    for _ in range(5):
        logits, _ = _jitted(model)({"params": params}, toks)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        toks = jnp.concatenate([toks, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(toks))
