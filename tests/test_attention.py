"""Attention numerics: blockwise, the fused kernels and the ring's flash
pair vs reference, and the sequence-parallel forms (ring, Ulysses) vs
single-device reference."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.parallel import ring
from distributed_tensorflow_models_tpu.telemetry import registry as reglib


def _qkv(B=2, T=128, H=4, D=32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rng.randn(B, T, H, D).astype(np.float32) * 0.5
    )
    return mk(), mk(), mk()


def _pair(q, k, v, causal, block_q=64, block_kv=64, window=None):
    """The flash pair as the ring runs it, at offsets 0: the forward kernel
    and the FlashAttention-2 backward through ``flash_attention_chunk``,
    interpreted on the CPU.  Positional: custom_vjp + nondiff_argnums."""
    return attnlib.flash_attention_chunk(
        q, k, v, 0, 0, causal, None, block_q, block_kv, True, window
    )[0]


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("block_kv", [32, 128])
def test_blockwise_matches_reference(causal, block_kv):
    q, k, v = _qkv()
    ref = attnlib.reference_attention(q, k, v, causal=causal)
    out = attnlib.blockwise_attention(
        q, k, v, causal=causal, block_kv=block_kv
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_kernel_matches_reference(causal):
    q, k, v = _qkv(T=256)
    ref = attnlib.reference_attention(q, k, v, causal=causal)
    out = _pair(q, k, v, causal)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grads_match_reference(causal):
    """The Pallas FlashAttention-2 backward kernels (dQ, dK/dV) vs autodiff
    through the O(T^2) reference — multi-block so the causal block-skip and
    the scratch accumulation across sweeps are both exercised."""
    q, k, v = _qkv(T=256)

    def loss_ref(q, k, v):
        return jnp.sum(
            attnlib.reference_attention(q, k, v, causal=causal) ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(_pair(q, k, v, causal) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_grads_cross_attention_shapes():
    """Tq != Tkv (non-causal cross-attention): the two backward kernels
    sweep grids of different lengths — catches transposed index maps."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, 128, 2, 32).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(2, 256, 2, 32).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(2, 256, 2, 32).astype(np.float32) * 0.5)

    def loss_ref(q, k, v):
        return jnp.sum(attnlib.reference_attention(q, k, v) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(_pair(q, k, v, False) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_flash_bf16_grads_close_to_reference():
    """bf16 in/out (the models' activation dtype): grads within bf16
    round-off of the f32 reference."""
    q, k, v = _qkv(T=128, D=32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    def loss_ref(q, k, v):
        return jnp.sum(
            attnlib.reference_attention(q, k, v, causal=True) ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(_pair(q, k, v, True).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(qb, kb, vb)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(
            a, np.asarray(b, np.float32), rtol=0.1, atol=0.15
        )


@pytest.mark.parametrize("window", [None, 48])
@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_pads_odd_lengths(causal, window):
    """KV lengths that don't divide the block are padded+masked, under a
    sliding window too (on the chip blockwise is the route for both)."""
    q, k, v = _qkv(T=100)
    ref = attnlib.reference_attention(q, k, v, causal=causal, window=window)
    out = attnlib.blockwise_attention(
        q, k, v, causal=causal, block_kv=64, window=window
    )
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "Tq,Tkv,hkv,bkv,causal,window,qoff",
    [
        (512, 512, 2, 128, True, 96, 0),
        (256, 384, 2, 100, True, None, 128),  # padding and an offset
        (256, 256, 2, 128, False, 64, 0),  # window without causal
        (256, 256, 1, 64, True, 80, 0),  # grouped KV heads under a window
    ],
    ids=["causal_window", "pad_offset", "window_only", "gqa_window"],
)
def test_blockwise_masked_geometries_match_reference(
    Tq, Tkv, hkv, bkv, causal, window, qoff
):
    """What ``auto`` sends to blockwise on the chip (windows, grouped KV
    heads, lengths no tile divides) and the ring's fold (offsets)."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(2, Tq, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(2, Tkv, hkv, 16), jnp.float32)
    v = jnp.asarray(rng.randn(2, Tkv, hkv, 16), jnp.float32)
    kw = dict(causal=causal, q_offset=qoff, kv_offset=0, window=window)
    ref = attnlib.reference_attention(q, k, v, **kw)
    out = attnlib.blockwise_attention(q, k, v, block_kv=bkv, **kw)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "window,hkv", [(None, 2), (80, 2), (None, 1)],
    ids=["causal", "causal_window", "gqa"],
)
def test_blockwise_grads_match_reference(window, hkv):
    """dQ, dK, dV through the remat-ed scan against autodiff through the
    reference; with grouped KV heads the expansion's transpose sums the
    group's gradients."""
    rng = np.random.RandomState(4)
    q = jnp.asarray(rng.randn(1, 256, 2, 16), jnp.float32)
    k = jnp.asarray(rng.randn(1, 256, hkv, 16), jnp.float32)
    v = jnp.asarray(rng.randn(1, 256, hkv, 16), jnp.float32)

    def loss(fn):
        return lambda q, k, v: jnp.sum(
            fn(q, k, v, causal=True, window=window) ** 2
        )

    g_ref = jax.grad(loss(attnlib.reference_attention), (0, 1, 2))(q, k, v)
    g_bw = jax.grad(
        loss(functools.partial(attnlib.blockwise_attention, block_kv=64)),
        (0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_bw, g_ref):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_blockwise_traced_offset_equals_static():
    """The ring's fold passes traced offsets: under ``jit`` they mask as
    the static ones do."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)

    @jax.jit
    def f(q, k, v, off):
        return attnlib.blockwise_attention(
            q, k, v, causal=True, block_kv=64, q_offset=off, kv_offset=0
        )

    base = attnlib.blockwise_attention(
        q, q, q, causal=True, block_kv=64, q_offset=128, kv_offset=0
    )
    np.testing.assert_allclose(f(q, q, q, jnp.int32(128)), base, rtol=1e-6)


def test_blockwise_live_rows_with_dead_rows_present():
    """kv_offset > q_offset leaves the first rows with no visible key
    (their output is documented garbage, _check_window); every row that
    sees a key equals the reference's."""
    rng = np.random.RandomState(8)
    q = jnp.asarray(rng.randn(1, 128, 2, 16), jnp.float32)
    kw = dict(causal=True, q_offset=0, kv_offset=64)
    ref = attnlib.reference_attention(q, q, q, **kw)
    out = attnlib.blockwise_attention(q, q, q, block_kv=64, **kw)
    np.testing.assert_allclose(out[:, 64:], ref[:, 64:], rtol=2e-5, atol=2e-5)


def test_blockwise_backward_is_remat():
    """Backward must not stack score-sized residuals: residual bytes stay
    well under T_q x T_kv elements."""
    q, k, v = _qkv(B=1, T=1024, H=1, D=16)
    _, vjp = jax.vjp(
        lambda q, k, v: attnlib.blockwise_attention(
            q, k, v, causal=True, block_kv=128
        ),
        q, k, v,
    )
    n_res = sum(
        np.prod(x.shape)
        for x in jax.tree.leaves(vjp)
        if hasattr(x, "shape")
    )
    assert n_res < 1024 * 1024 / 2, n_res


@pytest.mark.parametrize("causal", [False, True])
def test_flash_chunk_merge_matches_full(causal):
    """Chunked (out, lse) results merged by the streaming LSE recurrence
    == full-sequence attention: the invariant the ring flash path rests
    on.  KV split into 2 chunks with global offsets."""
    q, k, v = _qkv(T=256, D=32)
    ref = attnlib.reference_attention(q, k, v, causal=causal)

    halves = []
    for c in range(2):
        kc = k[:, c * 128 : (c + 1) * 128]
        vc = v[:, c * 128 : (c + 1) * 128]
        halves.append(
            attnlib.flash_attention_chunk(
                q, kc, vc, 0, c * 128,
                causal=causal, block_q=64, block_kv=64, interpret=True,
            )
        )
    (o0, lse0), (o1, lse1) = halves
    m = jnp.maximum(lse0, lse1)
    w0, w1 = jnp.exp(lse0 - m), jnp.exp(lse1 - m)
    out = (o0 * w0[..., None] + o1 * w1[..., None]) / (w0 + w1)[..., None]
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize(
    "window,hkv", [(None, 2), (48, 2), (None, 1)],
    ids=["causal", "window", "gqa"],
)
def test_flash_chunk_lse_grads(window, hkv):
    """Gradients through BOTH chunk outputs (out and lse) — the lse
    cotangent folds into the backward delta; checked against autodiff of
    an equivalent XLA computation.  Under a window the block skip, and
    under grouped KV heads the group sum, meet a non-zero LSE cotangent:
    the backward the ring runs."""
    q, k, v = _qkv(B=1, T=128, H=2, D=32)
    k, v = k[:, :, :hkv], v[:, :, :hkv]

    def loss_chunk(q, k, v):
        o, lse = attnlib.flash_attention_chunk(
            q, k, v, 0, 0, causal=True, block_q=64, block_kv=64,
            interpret=True, window=window,
        )
        return jnp.sum(o**2) + jnp.sum(jnp.sin(lse))

    def loss_ref(q, k, v):
        k, v = (jnp.repeat(x, 2 // hkv, axis=2) for x in (k, v))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * (32**-0.5)
        qi = jnp.arange(128)[:, None]
        kj = jnp.arange(128)[None, :]
        valid = qi >= kj
        if window is not None:
            valid = valid & (qi - kj < window)
        s = jnp.where(valid, s, attnlib.NEG_INF)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B,H,Tq]
        o = jnp.einsum(
            "bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v
        )
        return jnp.sum(o**2) + jnp.sum(jnp.sin(jnp.swapaxes(lse, 1, 2)))

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ch = jax.grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_ch):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-4)


# ------------------------------------------------------------- sliding window


@pytest.mark.parametrize("window", [1, 32, 100])
def test_window_reference_oracle(window):
    """Sliding-window masking against a hand-built mask."""
    q, k, v = _qkv(T=64, D=16)
    out = attnlib.reference_attention(q, k, v, causal=True, window=window)
    qi = np.arange(64)[:, None]
    kj = np.arange(64)[None, :]
    mask = (qi >= kj) & (qi - kj < window)
    logits = np.einsum("bqhd,bkhd->bhqk", q, k) * (16**-0.5)
    logits = np.where(mask, logits, -1e30)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, v)
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [16, 96])
def test_window_blockwise_and_flash_match_reference(window):
    """Window through the streaming impls (incl. the flash block-skip:
    window=16 < block 64 skips whole blocks; 96 crosses blocks)."""
    q, k, v = _qkv(T=256, D=32)
    ref = attnlib.reference_attention(q, k, v, causal=True, window=window)
    bw = attnlib.blockwise_attention(
        q, k, v, causal=True, block_kv=64, window=window
    )
    fl = _pair(q, k, v, True, window=window)
    np.testing.assert_allclose(bw, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(fl, ref, rtol=2e-5, atol=2e-5)


def test_window_rejects_nonpositive():
    q, k, v = _qkv(T=64, D=16)
    for w in (0, -3):
        with pytest.raises(ValueError):
            attnlib.reference_attention(q, k, v, causal=True, window=w)
        with pytest.raises(ValueError):
            attnlib.blockwise_attention(q, k, v, causal=True, window=w)


# f32 to autodiff tolerance; bf16 (the models' activation dtype, where the
# dS and P casts round) to bf16 round-off of the f32 reference.
_PAIR_GRAD_TOL = {"float32": (1e-4, 1e-4), "bfloat16": (0.1, 0.15)}


def _assert_pair_grads_close(g_ref, g_pair, dtype):
    rtol, atol = _PAIR_GRAD_TOL[dtype]
    for a, b in zip(g_ref, g_pair):
        np.testing.assert_allclose(
            a, np.asarray(b, np.float32), rtol=rtol, atol=atol
        )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_window_flash_grads_match_reference(dtype):
    q, k, v = _qkv(B=1, T=256, H=2, D=32)

    def loss_ref(q, k, v):
        return jnp.sum(
            attnlib.reference_attention(
                q, k, v, causal=True, window=80
            )
            ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(
            _pair(q, k, v, True, window=80).astype(jnp.float32) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(
        *(x.astype(dtype) for x in (q, k, v))
    )
    _assert_pair_grads_close(g_ref, g_fl, dtype)


# ----------------------------------------------------------------- GQA


@pytest.mark.parametrize("hkv", [1, 2])
def test_gqa_reference_equals_expanded_mha(hkv):
    """GQA == MHA run on explicitly repeated KV heads, for every impl."""
    rng = np.random.RandomState(5)
    B, T, H, D = 2, 128, 4, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(B, T, hkv, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(B, T, hkv, D).astype(np.float32) * 0.5)
    kx = jnp.repeat(k, H // hkv, axis=2)
    vx = jnp.repeat(v, H // hkv, axis=2)
    ref = attnlib.reference_attention(q, kx, vx, causal=True)
    for out in (
        attnlib.reference_attention(q, k, v, causal=True),
        attnlib.blockwise_attention(q, k, v, causal=True, block_kv=64),
        _pair(q, k, v, True),
    ):
        np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gqa_flash_grads_match_expanded_reference(dtype):
    """Flash GQA backward (group index maps + outside group-sum) vs
    autodiff through the expanded-KV reference."""
    rng = np.random.RandomState(6)
    B, T, H, hkv, D = 1, 128, 4, 2, 32
    q = jnp.asarray(rng.randn(B, T, H, D).astype(np.float32) * 0.5)
    k = jnp.asarray(rng.randn(B, T, hkv, D).astype(np.float32) * 0.5)
    v = jnp.asarray(rng.randn(B, T, hkv, D).astype(np.float32) * 0.5)
    g = H // hkv

    def loss_ref(q, k, v):
        kx = jnp.repeat(k, g, axis=2)
        vx = jnp.repeat(v, g, axis=2)
        return jnp.sum(
            attnlib.reference_attention(q, kx, vx, causal=True) ** 2
        )

    def loss_flash(q, k, v):
        return jnp.sum(_pair(q, k, v, True).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(
        *(x.astype(dtype) for x in (q, k, v))
    )
    _assert_pair_grads_close(g_ref, g_fl, dtype)


def test_gqa_rejects_indivisible_heads():
    q, k, v = _qkv(H=4)
    with pytest.raises(ValueError):
        attnlib.reference_attention(q, k[:, :, :3], v[:, :, :3])


# ------------------------------------------------------------ seq parallel


@pytest.fixture(scope="module")
def seq_mesh():
    return meshlib.create_mesh(meshlib.MeshSpec(data=-1, seq=4))


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_reference(seq_mesh, causal):
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    ref = attnlib.reference_attention(q, k, v, causal=causal)
    out = jax.jit(
        functools.partial(
            ring.ring_attention, mesh=seq_mesh, causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_matches_reference(seq_mesh, causal):
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    ref = attnlib.reference_attention(q, k, v, causal=causal)
    out = jax.jit(
        functools.partial(
            ring.ulysses_attention, mesh=seq_mesh, causal=causal
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ring_attention_grads(seq_mesh):
    q, k, v = _qkv(B=2, T=64, H=2, D=16)

    def loss_ref(q, k, v):
        return jnp.mean(
            attnlib.reference_attention(q, k, v, causal=True) ** 2
        )

    def loss_ring(q, k, v):
        return jnp.mean(
            ring.ring_attention(q, k, v, seq_mesh, causal=True) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_reference(seq_mesh, causal):
    """Ring with the Pallas inner kernel (interpret mode): per-chunk
    flash + LSE merge under shard_map == single-device reference."""
    q, k, v = _qkv(B=2, T=256, H=2, D=32)
    ref = attnlib.reference_attention(q, k, v, causal=causal)
    out = jax.jit(
        functools.partial(
            ring.ring_attention,
            mesh=seq_mesh, causal=causal, impl="flash", interpret=True,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ring_flash_grads(seq_mesh):
    q, k, v = _qkv(B=2, T=256, H=2, D=32)

    def loss_ref(q, k, v):
        return jnp.mean(
            attnlib.reference_attention(q, k, v, causal=True) ** 2
        )

    def loss_ring(q, k, v):
        return jnp.mean(
            ring.ring_attention(
                q, k, v, seq_mesh, causal=True, impl="flash",
                interpret=True,
            )
            ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


@pytest.mark.parametrize("impl", ["fold", "flash"])
def test_ring_window_matches_reference(seq_mesh, impl):
    """Sliding window through both ring paths: global-coordinate window
    masking across chunk boundaries == single-device reference."""
    q, k, v = _qkv(B=2, T=256, H=2, D=32)
    ref = attnlib.reference_attention(
        q, k, v, causal=True, window=80
    )
    out = jax.jit(
        functools.partial(
            ring.ring_attention,
            mesh=seq_mesh, causal=True, impl=impl,
            interpret=True, window=80,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ring_window_grads_match_reference(seq_mesh):
    """Windowed gradients through the ring flash path: a window mismatch
    between the chunk custom_vjp's fwd and bwd would pass the
    forward-only tests while gradients silently diverge."""
    q, k, v = _qkv(B=2, T=256, H=2, D=32)

    def loss_ref(q, k, v):
        return jnp.mean(
            attnlib.reference_attention(
                q, k, v, causal=True, window=80
            )
            ** 2
        )

    def loss_ring(q, k, v):
        return jnp.mean(
            ring.ring_attention(
                q, k, v, seq_mesh, causal=True, impl="flash",
                interpret=True, window=80,
            )
            ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_ring_window_rejects_nonpositive(seq_mesh):
    q, k, v = _qkv(B=2, T=64, H=2, D=16)
    with pytest.raises(ValueError):
        ring.ring_attention(
            q, k, v, seq_mesh, causal=True, impl="fold", window=0
        )


def test_ulysses_window_matches_reference(seq_mesh):
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    ref = attnlib.reference_attention(q, k, v, causal=True, window=20)
    out = jax.jit(
        functools.partial(
            ring.ulysses_attention, mesh=seq_mesh, causal=True, window=20
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ring_rejects_indivisible_seq(seq_mesh):
    q, k, v = _qkv(T=66)
    with pytest.raises(ValueError):
        ring.ring_attention(q, k, v, seq_mesh)


# --------------------------------------------------- seq parallel + GQA


@pytest.mark.parametrize("impl", ["fold", "flash"])
@pytest.mark.parametrize("hkv", [1, 2])
def test_ring_gqa_matches_reference(seq_mesh, impl, hkv):
    """GQA through the ring natively: KV rotates at H_kv heads (no
    expansion before sharding) and must equal the single-device GQA
    reference.  Covers MQA (hkv=1) and 2-way grouping."""
    q, k, v = _qkv(B=2, T=256, H=4, D=32)
    k, v = k[:, :, :hkv], v[:, :, :hkv]
    ref = attnlib.reference_attention(q, k, v, causal=True)
    out = jax.jit(
        functools.partial(
            ring.ring_attention,
            mesh=seq_mesh, causal=True, impl=impl, interpret=True,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("impl", ["fold", "flash"])
def test_ring_gqa_grads_match_reference(seq_mesh, impl):
    q, k, v = _qkv(B=2, T=256, H=4, D=32)
    k, v = k[:, :, :2], v[:, :, :2]

    def loss_ref(q, k, v):
        return jnp.mean(
            attnlib.reference_attention(q, k, v, causal=True) ** 2
        )

    def loss_ring(q, k, v):
        return jnp.mean(
            ring.ring_attention(
                q, k, v, seq_mesh, causal=True, impl=impl,
                interpret=True,
            )
            ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_ring):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_ring_gqa_window_matches_reference(seq_mesh):
    """GQA x sliding window x ring fold: the folded-row position mapping
    (row r at global q_off + r % T_local) must mask identically to the
    unfolded reference."""
    q, k, v = _qkv(B=2, T=256, H=4, D=32)
    k, v = k[:, :, :2], v[:, :, :2]
    ref = attnlib.reference_attention(q, k, v, causal=True, window=80)
    out = jax.jit(
        functools.partial(
            ring.ring_attention,
            mesh=seq_mesh, causal=True, impl="fold", window=80,
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_matches_reference():
    """GQA through Ulysses: q scatters at H heads, kv at their native
    H_kv (2 here, over a seq-2 axis) — no expansion, and the contiguous
    head split preserves the group mapping."""
    mesh2 = meshlib.create_mesh(meshlib.MeshSpec(data=-1, seq=2))
    q, k, v = _qkv(B=4, T=64, H=4, D=16)
    k, v = k[:, :, :2], v[:, :, :2]
    ref = attnlib.reference_attention(q, k, v, causal=True)
    out = jax.jit(
        functools.partial(
            ring.ulysses_attention, mesh=mesh2, causal=True
        )
    )(q, k, v)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_window_grads_match_reference():
    """GQA x window x Ulysses gradients on a seq-2 axis."""
    mesh2 = meshlib.create_mesh(meshlib.MeshSpec(data=-1, seq=2))
    q, k, v = _qkv(B=4, T=64, H=4, D=16)
    k, v = k[:, :, :2], v[:, :, :2]

    def loss_ref(q, k, v):
        return jnp.mean(
            attnlib.reference_attention(
                q, k, v, causal=True, window=20
            ) ** 2
        )

    def loss_uly(q, k, v):
        return jnp.mean(
            ring.ulysses_attention(
                q, k, v, mesh2, causal=True, window=20
            ) ** 2
        )

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_uly = jax.jit(jax.grad(loss_uly, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_ref, g_uly):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-4
        )


def test_ulysses_gqa_rejects_kv_heads_not_dividing_axis(seq_mesh):
    # H_kv=2 on a seq-4 axis: the KV all_to_all cannot split 2 heads 4
    # ways — must fail loudly, not wedge or silently replicate.
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    with pytest.raises(ValueError):
        ring.ulysses_attention(
            q, k[:, :, :2], v[:, :, :2], seq_mesh, causal=True
        )


def test_ring_gqa_rejects_indivisible_heads(seq_mesh):
    q, k, v = _qkv(B=2, T=64, H=4, D=16)
    with pytest.raises(ValueError):
        ring.ring_attention(q, k[:, :, :3], v[:, :, :3], seq_mesh)


# --------------------------------------------------- round-3 tuning layer


def test_auto_block_resolution():
    """None tiles resolve per-length: 256 where divisible (the winner of
    a round-3 v5e forward sweep; old access layer, not re-measured), 128
    fallback, clamped to the sequence length."""
    assert attnlib._check_blocks(512, 512, None, None) == (256, 256)
    assert attnlib._check_blocks(2048, 2048, None, None) == (256, 256)
    assert attnlib._check_blocks(384, 384, None, None) == (128, 128)
    assert attnlib._check_blocks(64, 64, None, None) == (64, 64)
    assert attnlib._check_blocks(512, 384, None, None) == (256, 128)
    # Explicit tiles still validated against divisibility.
    with pytest.raises(ValueError):
        attnlib._check_blocks(384, 384, 256, 256)


def test_auto_block_bwd_resolution():
    """Backward default tiles resolve INDEPENDENTLY of the forward's:
    128 everywhere the kernels accept (the round-3 sweep behind the
    forward's 256 timed no backward — ADVICE r3), clamped for short
    sequences like the forward path."""
    assert attnlib._auto_block_bwd(512) == 128
    assert attnlib._auto_block_bwd(2048) == 128
    assert attnlib._auto_block_bwd(256) == 128
    assert attnlib._auto_block_bwd(64) == 64  # clamp below one tile
    # The split is observable end-to-end: at T=512 the forward resolves
    # 256 tiles while the backward None-path must resolve 128.
    assert attnlib._check_blocks(512, 512, None, None) == (256, 256)
    bq = attnlib._auto_block_bwd(512)
    assert attnlib._check_blocks(512, 512, bq, bq) == (128, 128)


def test_flash_bwd_none_tiles_resolve_independently():
    """The custom_vjp backward with None tiles must run (and match the
    reference grads) at a length where fwd auto=256 but bwd auto=128 —
    the exact split added after ADVICE r3 flagged the backward 256 as
    unmeasured."""
    q, k, v = _qkv(T=512)
    f = lambda q, k, v: jnp.sum(
        _pair(q, k, v, True, None, None).astype(jnp.float32) ** 2
    )
    r = lambda q, k, v: jnp.sum(
        attnlib.reference_attention(
            q.astype(jnp.float32),
            k.astype(jnp.float32),
            v.astype(jnp.float32),
            causal=True,
        )
        ** 2
    )
    gf = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(r, argnums=(0, 1, 2))(
        q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    )
    for a, b in zip(gf, gr):
        assert jnp.max(jnp.abs(a.astype(jnp.float32) - b)) < 0.15


def _route_counts():
    reg = reglib.get_registry()
    return (
        reg.counter(reglib.ATTN_ROUTE_FUSED).value,
        reg.counter(reglib.ATTN_ROUTE_BLOCKWISE).value,
    )


@pytest.mark.parametrize(
    "backend, devices, shape, kv_heads, window, traced_offset, want",
    [
        # Off the chip every call keeps the scan.
        ("cpu", 1, (2, 256, 4, 64), 4, None, False, "blockwise"),
        ("cpu", 8, (1, 1024, 16, 64), 16, None, False, "blockwise"),
        # Described as one TPU: the two cells' shapes and a small one.
        ("tpu", 1, (1, 1024, 16, 64), 16, None, False, "fused"),
        ("tpu", 1, (1, 4096, 16, 128), 16, None, False, "fused"),
        ("tpu", 1, (2, 256, 4, 64), 4, None, False, "fused"),
        # ... and what the kernels do not take.
        ("tpu", 1, (2, 200, 4, 64), 4, None, False, "blockwise"),  # 128 does not divide
        ("tpu", 1, (2, 256, 4, 64), 4, None, True, "blockwise"),  # traced offsets
        ("tpu", 1, (2, 256, 4, 64), 4, 64, False, "fused"),  # sliding window (since PR 44)
        # Grouped KV heads: repeated over their groups into the kernels (PR 38).
        ("tpu", 1, (2, 256, 4, 64), 2, None, False, "fused"),
        ("tpu", 1, (2, 256, 3, 64), 3, None, False, "blockwise"),  # half a lane block
        ("tpu", 1, (2, 256, 4, 32), 4, None, False, "blockwise"),  # head size
        # A jit over several devices cannot partition a Mosaic kernel.
        ("tpu", 4, (1, 1024, 16, 64), 16, None, False, "blockwise"),
    ],
)
def test_auto_route(
    monkeypatch, backend, devices, shape, kv_heads, window, traced_offset,
    want,
):
    """``impl="auto"`` chooses from the backend and what the call shows at
    trace time, counts the choice once per traced call, and off the chip
    is blockwise bit for bit."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    B, T, H, D = shape
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, T, kv_heads, D), jnp.bfloat16)
    if traced_offset:
        # attention() is self-attention (offsets are the static zeros);
        # the rule itself refuses an offset it cannot read.
        route = []
        jax.eval_shape(
            lambda off: route.append(
                attnlib.auto_route(q, kv, kv, q_offset=off)
            ),
            jax.ShapeDtypeStruct((), jnp.int32),
        )
        assert route == [want]
        assert attnlib.auto_route(q, kv, kv, q_offset=128) == want
        return
    assert attnlib.auto_route(q, kv, kv, window=window) == want
    fused0, blockwise0 = _route_counts()
    # Traced, not run: a Mosaic kernel cannot run here.
    out = jax.eval_shape(
        lambda q, k, v: attnlib.attention(
            q, k, v, causal=True, window=window
        ),
        q, kv, kv,
    )
    assert out.shape == shape and out.dtype == jnp.bfloat16
    fused1, blockwise1 = _route_counts()
    assert (fused1 - fused0, blockwise1 - blockwise0) == (
        (1, 0) if want == "fused" else (0, 1)
    )
    if backend == "cpu" and T <= 256:
        qa, ka, va = _qkv(B=B, T=T, H=H, D=D)
        a = attnlib.attention(qa, ka, va, causal=True, impl="auto")
        b = attnlib.attention(qa, ka, va, causal=True, impl="blockwise")
        assert jnp.array_equal(a, b)


@pytest.mark.parametrize(
    "manual, want", [(("data", "seq"), "fused"), (("seq",), "blockwise")]
)
def test_auto_route_inside_shard_map(monkeypatch, manual, want):
    """Several devices: a Mosaic kernel lowers only where every mesh axis
    is manual (Ulysses, the pipeline stages), and there ``auto`` takes it."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "seq"))
    q = jnp.zeros((2, 256, 4, 64), jnp.bfloat16)
    routes = []

    def local(q):
        routes.append(attnlib.auto_route(q, q, q))
        return q

    spec = P(*(a if a in manual else None for a in ("data", "seq")))
    jax.eval_shape(
        jax.shard_map(
            local, mesh=mesh, in_specs=spec, out_specs=spec,
            axis_names=set(manual),
        ),
        q,
    )
    assert routes == [want]


def test_named_impl_is_not_counted():
    """Only ``auto`` chooses, so only ``auto`` counts."""
    q, k, v = _qkv(T=128)
    before = _route_counts()
    attnlib.attention(q, k, v, causal=True, impl="blockwise")
    attnlib.attention(q, k, v, causal=True, impl="reference")
    assert _route_counts() == before


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads, head_dim", [(4, 64), (2, 128)])
def test_fused_matches_reference(heads, head_dim, dtype, causal):
    """The kernels ``auto`` runs on the chip, in interpret mode: values
    and all three gradients against the materialized reference, at both
    lane packings (two heads of 64 a block, one of 128), over several
    tiles so that interior, diagonal and skipped block pairs all occur.
    f32 tight; bf16 at the tolerances the blockwise route is held to."""
    q, k, v = _qkv(B=2, T=384, H=heads, D=head_dim, seed=3)
    w = _qkv(B=2, T=384, H=heads, D=head_dim, seed=4)[0]
    cast = lambda x: x.astype(dtype)

    def grads(fn, *args):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * w),
            argnums=(0, 1, 2),
        )(*args)

    ref = attnlib.reference_attention(q, k, v, causal=causal)
    _, g_ref = grads(
        lambda q, k, v: attnlib.reference_attention(q, k, v, causal=causal),
        q, k, v,
    )
    fused = lambda q, k, v: attnlib.fused_attention(
        q, k, v, causal, None, 128, 128, True  # tiles, interpret
    )
    out = fused(cast(q), cast(k), cast(v))
    assert out.dtype == jnp.dtype(dtype)
    _, g = grads(fused, cast(q), cast(k), cast(v))
    tol = dict(rtol=2e-5, atol=2e-5) if dtype == "float32" else dict(
        rtol=3e-2, atol=3e-2
    )
    np.testing.assert_allclose(np.asarray(out, np.float32), ref, **tol)
    gtol = dict(rtol=2e-4, atol=2e-5) if dtype == "float32" else dict(
        rtol=5e-2, atol=5e-2
    )
    for got, want in zip(g, g_ref):
        assert got.dtype == jnp.dtype(dtype)
        np.testing.assert_allclose(np.asarray(got, np.float32), want, **gtol)


@pytest.mark.parametrize("block_q, block_kv", [(128, 256), (256, 128)])
def test_fused_uneven_tiles(block_q, block_kv):
    """Query and key tiles of different size: the pair list, the mask's
    boundary test and the first/last-block tests are all in global
    positions."""
    q, k, v = _qkv(B=1, T=512, H=2, D=64, seed=5)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    ref = attnlib.reference_attention(q, k, v, causal=True)
    fused = lambda q, k, v: attnlib.fused_attention(
        q, k, v, True, None, block_q, block_kv, True
    )
    np.testing.assert_allclose(fused(q, k, v), ref, rtol=2e-5, atol=2e-5)
    g = jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v: attnlib.reference_attention(q, k, v, causal=True)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for got, want in zip(g, g_ref):
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_fused_refuses_what_it_cannot_tile():
    q, k, v = _qkv(T=200, H=4, D=64)
    assert not attnlib.fused_admissible(q, k, v)
    with pytest.raises(ValueError, match="fused attention"):
        attnlib.fused_attention(q, k, v, True, None, None, None, True)


def test_removed_flash_impl_fails_loudly():
    """``impl="flash"`` was a route until PR 27: the name must raise, not
    fall through to another implementation."""
    q, k, v = _qkv(T=128)
    with pytest.raises(ValueError, match="unknown attention impl"):
        attnlib.attention(q, k, v, impl="flash")


def test_cli_refuses_removed_flash_impl(capsys):
    from distributed_tensorflow_models_tpu.harness import cli

    with pytest.raises(SystemExit) as e:
        cli.main(
            ["train", "--config", "transformer_lm", "--attn-impl", "flash"]
        )
    assert e.value.code == 2
    assert "invalid choice: 'flash'" in capsys.readouterr().err


def test_blockwise_bf16_matches_f32_reference():
    """bf16 inputs take the input-dtype matmul path (f32 accumulation):
    results must stay within bf16 round-off of the full-f32 reference,
    forward and grad."""
    q, k, v = _qkv(T=192)
    ref = attnlib.reference_attention(q, k, v, causal=True)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = attnlib.blockwise_attention(qb, kb, vb, causal=True, block_kv=64)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref), rtol=3e-2, atol=3e-2
    )

    g_ref = jax.grad(
        lambda q: jnp.sum(
            attnlib.reference_attention(q, k, v, causal=True) ** 2
        )
    )(q)
    g_bf = jax.grad(
        lambda q: jnp.sum(
            attnlib.blockwise_attention(
                q, kb, vb, causal=True, block_kv=64
            ).astype(jnp.float32)
            ** 2
        )
    )(qb)
    np.testing.assert_allclose(
        np.asarray(g_bf, np.float32), np.asarray(g_ref),
        rtol=5e-2, atol=5e-2,
    )


def test_blockwise_f32_unchanged_by_dtype_scheme():
    """f32 inputs keep full f32 math — the input-dtype scheme must not
    perturb the CPU oracle path beyond reordering-level noise."""
    q, k, v = _qkv(T=192)
    ref = attnlib.reference_attention(q, k, v, causal=True)
    out = attnlib.blockwise_attention(q, k, v, causal=True, block_kv=64)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
    )
