"""A sliding window in the fused attention kernels (``ops/attention.py``):
the kernels in interpret mode against ``reference_attention`` with
gradients, with a window and without; the block pairs a window leaves; the
route and the scope of a windowed call; and that a call **without** a
window traces the program it traced before the window came (PR 44)."""

import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.ops import attention as attnlib


def _qkv(T, H, Dq, Dv, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    shape = lambda d: (1, T, H, d)
    return tuple(jax.random.normal(k, shape(d)) for k, d in zip(ks, (Dq, Dq, Dv, Dv)))


@pytest.mark.parametrize(
    "T,H,Dq,Dv,window,block_q,block_kv",
    [
        (512, 2, 64, 64, 100, 128, 128),  # a window inside one tile, two heads a lane block
        (512, 2, 64, 64, None, 128, 128),  # full causal, as before
        (768, 1, 128, 128, 256, 256, 128),  # tiles that differ, a window of whole tiles
        (640, 1, 128, 128, 200, None, None),  # the tile the length chooses (128)
    ],
    ids=["window_100", "full", "window_256_tiles_differ", "window_200_auto_tile"],
)
def test_fused_kernels_are_the_reference_with_gradients(T, H, Dq, Dv, window, block_q, block_kv):
    q, k, v, weight = _qkv(T, H, Dq, Dv)
    reference = lambda q, k, v: attnlib.reference_attention(q, k, v, causal=True, window=window)
    fused = lambda q, k, v: attnlib.fused_attention(q, k, v, True, None, block_q, block_kv, True, window)
    run = lambda f: jax.jit(
        lambda *a: (f(*a), jax.grad(lambda *b: jnp.sum(f(*b) * weight), argnums=(0, 1, 2))(*a))
    )
    (got, grads), (want, want_grads) = run(fused)(q, k, v), run(reference)(q, k, v)
    # float32 on both sides: the streaming softmax's order of sums.
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-6)
    for g, w in zip(grads, want_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5)


def test_a_window_drops_the_pairs_outside_it():
    """8,192 positions under a window of 512: 2 of 8.5 kv tiles a query
    tile on average at the tile of 512 a call of that length takes (3 of
    16.5 at 256, 5 of 32.5 at 128)."""
    count = lambda tile, window: len(
        attnlib._fused_pairs(8192 // tile, 8192 // tile, tile, tile, True, False, window)[0]
    )
    causal = {tile: (8192 // tile) * (8192 // tile + 1) // 2 for tile in (128, 256, 512)}
    assert {t: count(t, None) for t in causal} == causal
    assert count(256, 512) == 3 * 32 - 3 and count(512, 512) == 2 * 16 - 1 and count(128, 512) == 5 * 64 - 10
    assert 0.17 < count(256, 512) / causal[256] < 0.18 < count(512, 512) / causal[512] < 0.25
    assert attnlib._fused_tile(8192) == 512 and attnlib._fused_tile(640) == 128
    # kv-major for the backward: the same pairs.
    i, j = attnlib._fused_pairs(32, 32, 256, 256, True, True, 512)
    assert sorted(zip(i.tolist(), j.tolist())) == sorted(
        zip(*(x.tolist() for x in attnlib._fused_pairs(32, 32, 256, 256, True, False, 512)))
    )
    # A pair is kept exactly where some query of the tile sees some key of it.
    seen = lambda a, b: any(0 <= t - s < 300 for t in range(a * 128, a * 128 + 128) for s in (b * 128, b * 128 + 127))
    i, j = attnlib._fused_pairs(8, 8, 128, 128, True, False, 300)
    assert set(zip(i.tolist(), j.tolist())) == {(a, b) for a in range(8) for b in range(8) if seen(a, b)}


def test_a_windowed_call_takes_the_fused_route_under_its_scope(monkeypatch):
    q, k, v, _ = _qkv(256, 2, 64, 64)
    assert attnlib.auto_route(q, k, v, window=64) == "blockwise"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attnlib, "mosaic_can_lower", lambda: True)
    assert attnlib.auto_route(q, k, v, window=64) == "fused"
    assert attnlib.auto_route(q, k, v, window=64, causal=False) == "blockwise"  # the kernels' window is causal
    assert attnlib.fused_admissible(q, k, jnp.zeros((1, 256, 2, 128)), window=64)  # 64 under 128 value channels
    text = str(jax.make_jaxpr(lambda *a: attnlib.attention(*a, causal=True, window=64))(q, k, v))
    assert "pallas_call" in text
    monkeypatch.undo()
    lowered = jax.jit(lambda *a: attnlib.attention(*a, causal=True, window=64)).lower(q, k, v)
    names = lowered.as_text(debug_info=True)
    assert re.search(r"attention_core/swa_core", names)
    assert "swa_core" not in jax.jit(lambda *a: attnlib.attention(*a, causal=True)).lower(q, k, v).as_text(
        debug_info=True
    )


# sha256 of the differentiated jaxpr (forward and backward kernels, their
# bodies included; addresses masked) of ``attention(q, k, v, causal=True)``
# on the fused route, taken on the parent of PR 44 (commit 90ee99d) and the
# same on this tree: a call without a window lowers what it lowered before.
# A change to the kernels that means to change it takes the digest anew.
_PINNED = {
    "gpt2m": ((8, 1024, 16, 64), (8, 1024, 16, 64),
              "7859a7ab7cd3d6e29ecc9981cee7d8507a89fdc423040e5a1fe183babb9ef0e9"),
    "granite_h_micro": ((1, 8192, 32, 64), (1, 8192, 8, 64),
                        "a92619c874c7de3519b7abaa3131259f0497d7339ae0c014f7029b9fd2794286"),
}


@pytest.mark.parametrize("config", sorted(_PINNED))
def test_a_call_without_a_window_traces_the_program_of_before(config, monkeypatch):
    q_shape, kv_shape, digest = _PINNED[config]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(attnlib, "mosaic_can_lower", lambda: True)
    q, kv = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in (q_shape, kv_shape))
    call = lambda q, k, v: attnlib.attention(q, k, v, causal=True)
    both = lambda q, k, v: jax.vjp(call, q, k, v)[1](jnp.ones(q_shape, jnp.bfloat16))
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(both)(q, kv, kv)))
    assert text.count("pallas_call") >= 2 and "window" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == digest
