"""What Kimi Linear brought to the program, at a small size on the CPU in
float32: the chunk-wise delta rule against its recurrence, the latent
attention's shapes through every attention route, the router's sigmoid
scores, an expert layer that holds a share of the experts (and the
shares adding up), and the stack whose layers differ.

The plain reference's side of it (logits, loss, every gradient) is
``tests/benchmark/test_bench_reference_kimi_linear.py``.
"""

import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.models import get_model, mixers
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import linear_attention as linattn
from distributed_tensorflow_models_tpu.parallel import moe as moelib
from distributed_tensorflow_models_tpu.telemetry import registry as reglib

@pytest.fixture(autouse=True)
def _release_compiled_programs():
    """This file compiles some hundreds of programs for the CPU, the
    interpreted kernels among them, each of many small mapped objects, and
    jitted functions keep theirs for the life of the process: one worker
    running the whole file came to the kernel's limit of memory mappings
    (``vm.max_map_count``, 65,530) and the next compile died of a
    segmentation fault.  Past half of that, drop what JAX has cached."""
    yield
    try:
        with open("/proc/self/maps") as maps:
            mapped = sum(1 for _ in maps)
    except OSError:  # no procfs: nothing to count
        return
    if mapped > 30_000:
        jax.clear_caches()


SMALL = {
    **get_config("kimi_linear").model_kwargs,
    "vocab_size": 97, "num_layers": 5,
    "layer_mixers": ("kda", "kda", "kda", "mla", "kda"),
    "num_heads": 4, "d_model": 64, "d_ff": 32, "dense_d_ff": 96,
    "kda_num_heads": 4, "kda_head_dim": 16, "mla_kv_lora_rank": 24,
    "mla_nope_dim": 16, "mla_rope_dim": 8, "mla_v_dim": 16,
    "num_experts": 16, "moe_top_k": 4, "moe_held": (4, 4),
}


# --- the chunk-wise delta rule ------------------------------------------

def _kda_inputs(seed, T, decay, B=2, H=3, dk=16, dv=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk)))
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    noise = jax.random.normal(ks[3], (B, T, H, dk))
    g = {
        # a_t about 0.9 .. 0.999
        "mild": -jnp.exp(noise - 4.0),
        # a_t within 1e-6 of 1: the state hardly forgets
        "near_one": -jnp.exp(noise - 14.0),
        # many a_t below e^-20, some below e^-100: e^{-G} leaves float32
        # inside one chunk, the quotients do not
        "near_zero": -jnp.exp(1.5 * noise + 2.0),
        # both in one sequence, channel by channel
        "mixed": jnp.where(noise > 0, -jnp.exp(noise + 3.0), -jnp.exp(noise - 12.0)),
    }[decay]
    beta = jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


KDA_CASES = [
    # chunk, sub, length, decay
    (64, 16, 128, "mild"),
    (64, 16, 70, "near_zero"),   # a length the chunk does not divide
    (64, 16, 150, "mixed"),
    (32, 8, 100, "near_one"),
    (32, 16, 33, "near_zero"),
    (16, 16, 64, "mixed"),       # one block a chunk: no product between blocks
    (16, 4, 50, "mild"),
    (128, 16, 130, "near_zero"),
    (8, 8, 5, "near_one"),       # shorter than a chunk
]


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("chunk,sub,T,decay", KDA_CASES)
def test_chunked_delta_rule_is_the_recurrence(chunk, sub, T, decay, what):
    x = _kda_inputs(chunk + T, T, decay)
    chunked = functools.partial(linattn.chunked_kda, chunk=chunk, sub=sub)
    with jax.default_matmul_precision("highest"):
        # Jitted here and below: one compile a side, where op by op is some hundred.
        if what == "forward":
            got, want = jax.jit(chunked)(*x), jax.jit(linattn.recurrent_kda)(*x)
            scale = float(jnp.abs(want).max())
            assert scale > 1e-3
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-4 * scale, rtol=1e-4)
            return
        probe = jax.random.normal(jax.random.key(9), x[2].shape)
        grad = lambda f: jax.jit(jax.grad(lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2, 3, 4)))(*x)
        for name, g, w in zip("q k v g beta".split(), grad(chunked), grad(linattn.recurrent_kda)):
            assert bool(jnp.isfinite(g).all()), name
            scale = float(jnp.abs(w).max())
            np.testing.assert_allclose(
                np.asarray(g), np.asarray(w), atol=1e-3 * scale + 1e-6, rtol=1e-3, err_msg=name
            )


def test_a_split_exponential_would_overflow_where_the_chunked_form_does_not():
    """The case the ``near_zero`` decays are there for: ``e^{-G_s}``
    inside one chunk is beyond float32, the chunk-wise form is finite."""
    q, k, v, g, beta = _kda_inputs(3, 64, "near_zero")
    G = jnp.cumsum(g, axis=1)
    assert not bool(jnp.isfinite(jnp.exp(-G)).all())
    out = linattn.chunked_kda(q, k, v, g, beta)
    assert bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("size,sub", [(16, 16), (32, 8), (64, 16), (64, 4)])
def test_unit_lower_inverse_and_its_cotangent(size, sub):
    # Entries as the layer has them: b_t k_t . k_s of unit keys, below 1.
    a = jnp.tril(0.3 * jax.random.normal(jax.random.key(size + sub), (3, 2, size, size)), -1)
    eye = jnp.eye(size)
    with jax.default_matmul_precision("highest"):
        got = linattn.unit_lower_inverse(a, sub)
        want = jax.scipy.linalg.solve_triangular(
            eye + a, jnp.broadcast_to(eye, a.shape), lower=True, unit_diagonal=True
        )
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3 * float(jnp.abs(want).max()))
        probe = jax.random.normal(jax.random.key(1), a.shape)
        g = jax.grad(lambda a: jnp.sum(linattn.unit_lower_inverse(a, sub) * probe))(a)
        w = jax.grad(lambda a: jnp.sum(jnp.linalg.inv(eye + jnp.tril(a, -1)) * probe))(a)
    np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-3 * float(jnp.abs(w).max()))
    assert float(jnp.abs(jnp.triu(g)).max()) == 0.0


def test_a_chunk_that_is_no_power_of_two_of_blocks_is_refused():
    x = _kda_inputs(0, 48, "mild")
    with pytest.raises(ValueError, match="power-of-two"):
        linattn.chunked_kda(*x, chunk=48, sub=16)


# --- the same as Pallas kernels (interpret mode on the CPU) ----------------

KERNEL_CASES = [
    # length, decay; whole tiles (128 key and value channels, chunks of 64)
    (128, "mild"),        # two chunks in one grid step
    (70, "near_zero"),    # a length the chunk does not divide
    (150, "mixed"),       # three grid steps of one chunk: the carried state
    (192, "near_one"),
    (384, "mixed"),       # three grid steps of two chunks
]


def _kernel_route(*x):
    return linattn.kernel_kda(*x, None, 64, True)


_KDA_ROUTES = {
    "kernel": _kernel_route,
    "plain": linattn.plain_kda,
    "recurrence": linattn.recurrent_kda,
}


@functools.lru_cache(maxsize=None)
def _kda_result(route, T, decay, what, dtype="float32"):
    """The output, or the five gradients of a probed sum, of one route."""
    x = _kda_inputs(T, T, decay, B=1, H=2, dk=128, dv=128)
    x = tuple(a.astype(dtype) for a in x[:3]) + x[3:]
    f = _KDA_ROUTES[route]
    # Jitted: one compile a result, where op by op is some hundred.
    with jax.default_matmul_precision("highest"):
        if what == "forward":
            return (jax.jit(f)(*x),)
        probe = jax.random.normal(jax.random.key(9), x[2].shape)
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(*x)


@pytest.mark.parametrize("oracle", ["recurrence", "plain"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("T,decay", KERNEL_CASES)
def test_the_kernels_are_the_recurrence_and_the_plain_route(T, decay, what, oracle):
    got, want = _kda_result("kernel", T, decay, what), _kda_result(oracle, T, decay, what)
    tol = 2e-4 if what == "forward" else 1e-3
    for name, g, w in zip("q k v g beta".split(), got, want):
        assert bool(jnp.isfinite(g).all()), name
        scale = float(jnp.abs(w).max())
        assert scale > 1e-3
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=tol * scale + 1e-6, rtol=1e-3,
            err_msg=name if what == "gradient" else "output",
        )


def test_the_kernels_take_keys_of_two_lane_blocks_and_an_odd_number_of_heads():
    """256 key channels over 128 value channels, three heads (one head a
    grid step): the output and the five gradients of the recurrence."""
    x = _kda_inputs(7, 100, "mixed", B=1, H=3, dk=256, dv=128)
    probe = jax.random.normal(jax.random.key(9), x[2].shape)
    both = lambda f: jax.value_and_grad(
        lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2, 3, 4)
    )(*x)
    with jax.default_matmul_precision("highest"):
        (got_sum, got), (want_sum, want) = both(_kernel_route), both(linattn.recurrent_kda)
    assert float(got_sum) == pytest.approx(float(want_sum), rel=1e-4, abs=1e-4)
    for name, g, w in zip("q k v g beta".split(), got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-3 * scale + 1e-6, rtol=1e-3, err_msg=name
        )


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("T,decay", [(192, "mild"), (150, "mixed")])
def test_the_kernels_in_bf16_are_the_plain_route_in_bf16(T, decay, what):
    """Both routes round the same operands to bfloat16 and accumulate in
    float32; they differ in the order of float32 sums and in which
    cotangents the backward rounds, so they agree to a few bfloat16
    roundings of the largest entry (0.03), and each is as near the float32
    result as the other (within a factor of two)."""
    got = _kda_result("kernel", T, decay, what, "bfloat16")
    plain = _kda_result("plain", T, decay, what, "bfloat16")
    exact = _kda_result("plain", T, decay, what)
    for name, g, p, e in zip("q k v g beta".split(), got, plain, exact):
        assert g.dtype == p.dtype, name
        g, p = g.astype(jnp.float32), p.astype(jnp.float32)
        scale = float(jnp.abs(e).max())
        assert float(jnp.abs(g - p).max()) <= 0.03 * scale, name
        assert float(jnp.abs(g - e).max()) <= 2 * float(jnp.abs(p - e).max()) + 4e-3 * scale, name


def _kda_route_counts():
    reg = reglib.get_registry()
    return (
        reg.counter(reglib.KDA_ROUTE_KERNEL).value,
        reg.counter(reglib.KDA_ROUTE_PLAIN).value,
    )


@pytest.mark.parametrize(
    "backend, devices, dk, dv, chunk, sub, want",
    [
        # Off the chip every call is the plain form.
        ("cpu", 1, 128, 128, 64, 16, "plain"),
        # Described as one TPU: the cell's widths.
        ("tpu", 1, 128, 128, 64, 16, "kernel"),
        ("tpu", 1, 256, 128, 64, 16, "kernel"),
        # ... and what the kernels do not take.
        ("tpu", 1, 16, 8, 64, 16, "plain"),      # no whole lane block
        ("tpu", 1, 128, 64, 64, 16, "plain"),    # half a lane block of values
        ("tpu", 1, 128, 128, 32, 16, "plain"),   # another chunk
        ("tpu", 1, 128, 128, 64, 8, "plain"),    # other blocks
        # A jit over several devices cannot partition a Mosaic kernel.
        ("tpu", 4, 128, 128, 64, 16, "plain"),
    ],
)
def test_which_route_the_delta_rule_takes(monkeypatch, backend, devices, dk, dv, chunk, sub, want):
    """``chunked_kda`` chooses from the backend and what the call shows at
    trace time, and counts the choice once per traced call."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    x = (spec(2, 200, 4, dk), spec(2, 200, 4, dk), spec(2, 200, 4, dv),
         jax.ShapeDtypeStruct((2, 200, 4, dk), jnp.float32),
         jax.ShapeDtypeStruct((2, 200, 4), jnp.float32))
    assert linattn.kda_route(*x, chunk=chunk, sub=sub) == want
    kernel0, plain0 = _kda_route_counts()
    # Traced, not run: a Mosaic kernel cannot run here.
    out = jax.eval_shape(functools.partial(linattn.chunked_kda, chunk=chunk, sub=sub), *x)
    assert out.shape == (2, 200, 4, dv) and out.dtype == jnp.bfloat16
    kernel1, plain1 = _kda_route_counts()
    assert (kernel1 - kernel0, plain1 - plain0) == ((1, 0) if want == "kernel" else (0, 1))


def test_the_entry_runs_the_kernels_where_it_would_on_the_chip(monkeypatch):
    """``chunked_kda`` itself, taken down the kernel route (the backend
    described as one TPU, the kernels interpreted): the recurrence, and
    one count of ``kda/route_kernel``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    kernels = linattn.kernel_kda
    monkeypatch.setattr(
        linattn, "kernel_kda", lambda *x: kernels(*x[:5], None, 64, True)
    )
    x = _kda_inputs(5, 100, "mild", B=1, H=2, dk=128, dv=128)
    kernel0, plain0 = _kda_route_counts()
    got = linattn.chunked_kda(*x)
    assert _kda_route_counts() == (kernel0 + 1, plain0)
    with jax.default_matmul_precision("highest"):
        want = linattn.recurrent_kda(*x)
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4 * float(jnp.abs(want).max())
    )


# --- the mixer's element-wise work as fused passes (interpreted) -----------

PASS_H, PASS_D, PASS_BLOCK = 2, 128, 64
PASS_LENGTHS = [
    64,    # one token block
    192,   # three: rows of history cross a block's edge, forward and back
    150,   # a length the block does not divide: the last block overhangs
    40,    # shorter than a block, and no whole halo
]


def _pass_inputs(T, dtype, B=2):
    ks = jax.random.split(jax.random.key(T), 13)
    W = PASS_H * PASS_D
    wide = lambda k: jax.random.normal(k, (B, T, W), jnp.float32).astype(dtype)
    taps = lambda k: jax.random.uniform(k, (4, W), jnp.float32, -0.5, 0.5)
    return {
        "prologue": (
            wide(ks[0]), wide(ks[1]), wide(ks[2]), wide(ks[3]), taps(ks[4]), taps(ks[5]),
            taps(ks[6]), jax.random.normal(ks[7], (W,)),
            jnp.log(jax.random.uniform(ks[8], (PASS_H,), jnp.float32, 1.0, 16.0)),
        ),
        "epilogue": (
            wide(ks[9]), wide(ks[10]), 1.0 + 0.1 * jax.random.normal(ks[11], (PASS_D,)),
        ),
    }


def _plain_prologue(xq, xk, xv, f, wq, wk, wv, dt_bias, a_log):
    """What ``KDAMixer`` runs between its projections and the core on the
    plain route, with the results folded back to the flat views."""
    B, T, W = xq.shape
    heads = lambda x: x.reshape(B, T, PASS_H, PASS_D)
    mixed = lambda y, w: heads(jax.nn.silu(mixers.causal_depthwise_conv(y, w)))
    q = mixers.l2norm(mixed(xq, wq)).astype(xq.dtype)
    k = mixers.l2norm(mixed(xk, wk)).astype(xk.dtype)
    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(heads(f.astype(jnp.float32) + dt_bias))
    return tuple(x.reshape(B, T, W) for x in (q, k, mixed(xv, wv), g))


def _plain_epilogue(o, gate, scale):
    B, T, W = o.shape
    heads = lambda x: x.reshape(B, T, PASS_H, PASS_D)
    n = nn.RMSNorm(epsilon=1e-5, dtype=jnp.float32).apply({"params": {"scale": scale}}, heads(o))
    return (n * jax.nn.sigmoid(heads(gate.astype(jnp.float32)))).astype(o.dtype).reshape(B, T, W)


_PASSES = {
    ("prologue", "fused"): functools.partial(linattn.kda_prologue, block=PASS_BLOCK, interpret=True),
    ("prologue", "plain"): _plain_prologue,
    ("epilogue", "fused"): functools.partial(
        linattn.kda_epilogue, eps=1e-5, block=PASS_BLOCK, interpret=True
    ),
    ("epilogue", "plain"): _plain_epilogue,
}


@functools.lru_cache(maxsize=None)
def _pass_result(which, route, T, dtype):
    """The outputs of a pass and the gradients of a probed sum of them by
    every argument, as float32."""
    args = _pass_inputs(T, jnp.float32)[which]
    # The same numbers in both precisions: draws rounded to bfloat16.
    rounded = lambda x: x.astype(jnp.bfloat16).astype(dtype) if x.ndim == 3 else x
    args = tuple(rounded(x) for x in args)
    fn = _PASSES[which, route]

    def loss(*a):
        outs = fn(*a)
        outs = outs if isinstance(outs, tuple) else (outs,)
        total = sum(
            jnp.sum(o.astype(jnp.float32) * jax.random.normal(jax.random.key(i), o.shape))
            for i, o in enumerate(outs)
        )
        return total, outs

    (_, outs), grads = jax.jit(jax.value_and_grad(loss, tuple(range(len(args))), has_aux=True))(*args)
    return tuple(x.astype(jnp.float32) for x in outs + grads)


_PASS_NAMES = {
    "prologue": "q k v g d_xq d_xk d_xv d_f d_conv_query d_conv_key d_conv_value d_dt_bias d_A_log".split(),
    "epilogue": "out d_o d_gate d_o_norm_scale".split(),
}


@pytest.mark.parametrize("which", ["prologue", "epilogue"])
@pytest.mark.parametrize("T", PASS_LENGTHS)
def test_the_fused_passes_in_float32_are_the_plain_functions_to_rounding(T, which):
    got = _pass_result(which, "fused", T, jnp.float32)
    want = _pass_result(which, "plain", T, jnp.float32)
    for name, g, w in zip(_PASS_NAMES[which], got, want, strict=True):
        assert g.shape == w.shape and bool(jnp.isfinite(g).all()), name
        scale = float(jnp.abs(w).max())
        assert scale > 1e-3, name
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-5 * scale, rtol=1e-4, err_msg=name
        )


@pytest.mark.parametrize("which", ["prologue", "epilogue"])
@pytest.mark.parametrize("T", [192, 150])
def test_the_fused_passes_in_bf16_are_within_the_plain_path_s_own_rounding(T, which):
    """The passes hold float32 between the bfloat16 they read and the
    bfloat16 they write, where the plain path rounds the convolution and
    the SiLU on the way: each result is as near the float32 one as the
    plain path's, to a rounding of the largest entry, and in the dtype
    the plain path gives it."""
    got = _pass_result(which, "fused", T, jnp.bfloat16)
    plain = _pass_result(which, "plain", T, jnp.bfloat16)
    exact = _pass_result(which, "plain", T, jnp.float32)
    for name, g, p, e in zip(_PASS_NAMES[which], got, plain, exact, strict=True):
        scale = float(jnp.abs(e).max())
        err, plain_err = float(jnp.abs(g - e).max()), float(jnp.abs(p - e).max())
        assert err <= plain_err + 2.0**-8 * scale, (name, err, plain_err, scale)
    fused = _PASSES[which, "fused"](*_pass_inputs(T, jnp.bfloat16)[which])
    want = _PASSES[which, "plain"](*_pass_inputs(T, jnp.bfloat16)[which])
    assert jax.tree.map(lambda x: x.dtype, fused) == jax.tree.map(lambda x: x.dtype, want)


def test_the_first_positions_of_every_sequence_see_zeros_before_them():
    """The convolution's history before position 0 is zeros, in every
    batch row (the rows before a block are the block before it, never the
    sequence before it) and at a token block's first rows alike; the
    cotangent after the last position is zero too."""
    T, W = 150, PASS_H * PASS_D
    xq, w = _pass_inputs(T, jnp.float32)["prologue"][::4][:2]
    conv = lambda x: linattn.short_conv_silu(x, w, PASS_D, True, 1e-6, PASS_BLOCK, True)
    both = conv(xq)
    for b in range(xq.shape[0]):
        np.testing.assert_array_equal(np.asarray(both[b]), np.asarray(conv(xq[b:b + 1])[0]))
    # Position 0 by hand: the last tap alone.
    s = jax.nn.silu(w[3] * xq[:, 0]).reshape(-1, PASS_H, PASS_D)
    want = (s * jax.lax.rsqrt(jnp.sum(s * s, -1, keepdims=True) + 1e-6)).reshape(-1, W)
    np.testing.assert_allclose(np.asarray(both[:, 0]), np.asarray(want), atol=1e-6)
    # A sequence is the start of a longer one, value and gradient: nothing
    # after a position reaches it, nothing beyond the end comes back.
    probe = jax.random.normal(jax.random.key(3), (xq.shape[0], 100, W))
    grad = lambda x: jax.grad(lambda x: jnp.sum(conv(x)[:, :100] * probe))(x)
    np.testing.assert_allclose(np.asarray(conv(xq[:, :100])), np.asarray(both[:, :100]), atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(grad(xq[:, :100])), np.asarray(grad(xq)[:, :100]), atol=1e-6
    )
    assert not np.asarray(grad(xq)[:, 100:]).any()


def _on_the_fused_route(monkeypatch, block=PASS_BLOCK):
    """``KDAMixer`` as it runs on the chip (the backend described as one
    TPU), with every kernel interpreted and small token blocks."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    for name, kwargs in (
        ("kda_prologue", {"block": block, "interpret": True}),
        ("kda_epilogue", {"block": block, "interpret": True}),
        ("chunked_kda_flat", {"interpret": True}),
    ):
        monkeypatch.setattr(linattn, name, functools.partial(getattr(linattn, name), **kwargs))


def _mixer_counts():
    reg = reglib.get_registry()
    return tuple(
        reg.counter(name).value
        for name in (reglib.KDA_MIXER_FUSED, reglib.KDA_MIXER_PLAIN, reglib.KDA_ROUTE_KERNEL)
    )


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_a_whole_mixer_on_the_fused_route_is_the_mixer_on_the_plain_route(monkeypatch, dtype):
    """One ``KDAMixer`` (two heads of 128, a length no block divides),
    output and the gradient of every parameter and of the input: the
    fused route with its kernels interpreted against the plain route, on
    the same parameter tree."""
    mixer = mixers.KDAMixer(num_heads=2, head_dim=128, d_model=64, dtype=dtype)
    x = jax.random.normal(jax.random.key(1), (2, 150, 64), dtype)
    params = mixer.init(jax.random.key(0), x)
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.key(p.size), p.shape), params
    )
    probe = jax.random.normal(jax.random.key(2), (2, 150, 64))

    def loss(p, x):
        out = mixer.apply(p, x)
        return jnp.sum(out.astype(jnp.float32) * probe), out

    # A fresh jit each time: the second is traced after the routes are patched.
    both = lambda: jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(params, x)
    with jax.default_matmul_precision("highest"):
        counts = _mixer_counts()
        (_, want_out), want = both()
        assert _mixer_counts() == (counts[0], counts[1] + 1, counts[2])
        _on_the_fused_route(monkeypatch)
        (_, got_out), got = both()
        assert _mixer_counts() == (counts[0] + 1, counts[1] + 1, counts[2] + 1)
        assert jax.tree.structure(mixer.init(jax.random.key(0), x)) == jax.tree.structure(params)
    # bf16: the routes round at different places (a rounding of the
    # largest entry, 2^-8, a few times over); f32: the order of sums.
    tol = 1e-4 if dtype == jnp.float32 else 0.04
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, g), (_, w) in zip(flat((got_out, got)), flat((want_out, want)), strict=True):
        g, w = g.astype(jnp.float32), w.astype(jnp.float32)
        assert g.shape == w.shape and bool(jnp.isfinite(g).all())
        assert float(jnp.abs(g - w).max()) <= tol * float(jnp.abs(w).max()), jax.tree_util.keystr(path)


@pytest.mark.parametrize(
    "backend, devices, head_dim, taps, want",
    [
        ("cpu", 1, 128, 4, "plain"),
        ("tpu", 1, 128, 4, "fused"),
        ("tpu", 1, 256, 2, "fused"),
        ("tpu", 1, 16, 4, "plain"),    # no whole lane block a head
        ("tpu", 1, 128, 12, "plain"),  # more history than a chunk keeps
        ("tpu", 4, 128, 4, "plain"),   # the core would not take its kernels
    ],
)
def test_which_placement_the_mixer_takes_and_that_it_counts_it_once(
    monkeypatch, backend, devices, head_dim, taps, want
):
    """``KDAMixer`` chooses from the backend and what the call shows at
    trace time, and counts ``kda/mixer_fused`` or ``kda/mixer_plain`` once
    per traced call (traced, not run: a Mosaic kernel cannot run here);
    the core's own route is counted beside it as before."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    mixer = mixers.KDAMixer(num_heads=2, head_dim=head_dim, d_model=64, conv_size=taps)
    x = jax.ShapeDtypeStruct((2, 200, 64), jnp.bfloat16)
    wide = jax.ShapeDtypeStruct((2, 200, 2 * head_dim), jnp.bfloat16)
    assert linattn.kda_mixer_route(wide, wide, wide, heads=2, taps=taps) == want
    narrow = jax.ShapeDtypeStruct(wide.shape, jnp.float32)
    assert linattn.kda_mixer_route(wide, wide, narrow, heads=2, taps=taps) == "plain"
    fused0, plain0, kernel0 = _mixer_counts()
    params = jax.eval_shape(mixer.init, jax.random.key(0), x)
    out = jax.eval_shape(mixer.apply, params, x)
    assert out.shape == (2, 200, 64) and out.dtype == jnp.bfloat16
    fused1, plain1, kernel1 = _mixer_counts()
    calls = (2, 0) if want == "fused" else (0, 2)
    assert (fused1 - fused0, plain1 - plain0) == calls
    # Too many taps leave the core on its kernels and the mixer plain.
    core_on_kernels = (backend, devices, head_dim % 128) == ("tpu", 1, 0)
    assert kernel1 - kernel0 == (2 if core_on_kernels else 0)


# --- latent attention's shapes through the attention routes --------------

def _mla_qkv(T=256, H=2, dqk=192, dv=128, dtype=jnp.float32):
    ks = jax.random.split(jax.random.key(0), 4)
    shape = lambda d: (1, T, H, d)
    q, k = jax.random.normal(ks[0], shape(dqk), dtype), jax.random.normal(ks[1], shape(dqk), dtype)
    return q, k, jax.random.normal(ks[2], shape(dv), dtype), jax.random.normal(ks[3], shape(dv))


def _fused_interpreted(q, k, v):
    """What ``attention(impl="auto")`` runs on the chip for these shapes,
    with the kernels interpreted."""
    widen = ((0, 0),) * 3 + ((0, -q.shape[-1] % 128),)
    return attnlib.fused_attention(
        jnp.pad(q, widen), jnp.pad(k, widen), v, True, q.shape[-1] ** -0.5, 128, 128, True
    )


@pytest.mark.parametrize("route", ["blockwise", "auto_on_the_cpu", "fused_interpreted"])
def test_192_key_and_128_value_channels_against_a_full_score_matrix(route):
    q, k, v, probe = _mla_qkv()
    fn = {
        "blockwise": functools.partial(attnlib.blockwise_attention, causal=True, block_kv=64),
        "auto_on_the_cpu": functools.partial(attnlib.attention, causal=True, scale=192**-0.5),
        "fused_interpreted": _fused_interpreted,
    }[route]
    want_fn = functools.partial(attnlib.reference_attention, causal=True)
    with jax.default_matmul_precision("highest"):
        got, want = fn(q, k, v), want_fn(q, k, v)
        assert got.shape == v.shape
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
        grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * probe), argnums=(0, 1, 2))(q, k, v)
        for g, w in zip(grad(fn), grad(want_fn)):
            assert g.shape == w.shape
            np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5)


@pytest.mark.parametrize(
    "shape_qk,shape_v,want",
    [
        ((1, 1024, 16, 64), (1, 1024, 16, 64), True),     # gpt2m_train
        ((1, 4096, 16, 128), (1, 4096, 16, 128), True),   # olmoe_train
        ((2, 8192, 32, 192), (2, 8192, 32, 128), True),   # kimi_linear_train's MLA
        ((1, 1024, 32, 192), (1, 1024, 32, 64), False),   # narrower values: no kernel
        ((1, 1024, 4, 96), (1, 1024, 4, 96), False),
        ((1, 1000, 32, 192), (1, 1000, 32, 128), False),  # no tile divides the length
        ((1, 1024, 32, 192), (1, 1024, 8, 128), False),   # grouped values
    ],
)
def test_which_shapes_the_fused_kernels_admit(shape_qk, shape_v, want):
    q = jax.ShapeDtypeStruct(shape_qk, jnp.bfloat16)
    v = jax.ShapeDtypeStruct(shape_v, jnp.bfloat16)
    assert attnlib.fused_admissible(q, q, v) is want
    assert attnlib.fused_admissible(q, q, v, window=128) is False


def test_fused_attention_itself_refuses_channels_that_are_not_whole_lane_blocks():
    q, k, v, _ = _mla_qkv()
    with pytest.raises(ValueError, match="whole blocks"):
        attnlib.fused_attention(q, k, v, True, None, 128, 128, True)


# --- the router -----------------------------------------------------------

def test_sigmoid_scores_renormalised_and_scaled_with_ties_to_the_lower_index():
    # Four tokens whose router logits are given outright (x = identity).
    logits = jnp.array([
        [2.0, -1.0, 0.5, 0.5, 0.5, -3.0],   # a three-way tie for two places
        [0.0, 0.0, 0.0, 0.0, 0.0, 0.0],     # all tied
        [-1.0, 3.0, -2.0, 1.0, 0.0, 2.0],
        [5.0, 4.0, 3.0, 2.0, 1.0, 0.0],
    ])
    x, router = jnp.eye(4), logits
    routing = moelib.Routing("sigmoid", True, 2.446)
    got_logits, scores, weight, expert = moelib.route_topk(router, x, 3, routing)
    np.testing.assert_array_equal(np.asarray(expert), [[0, 2, 3], [0, 1, 2], [1, 5, 3], [0, 1, 2]])
    s = np.asarray(jax.nn.sigmoid(logits))
    np.testing.assert_allclose(np.asarray(scores), s, rtol=1e-6)
    chosen = np.take_along_axis(s, np.asarray(expert), axis=-1)
    np.testing.assert_allclose(
        np.asarray(weight), 2.446 * chosen / chosen.sum(-1, keepdims=True), rtol=1e-6
    )
    np.testing.assert_allclose(np.asarray(weight).sum(-1), 2.446, rtol=1e-6)
    # The softmax router, untouched: its probabilities as they are.
    _, probs, plain, same = moelib.route_topk(router, x, 3)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(expert))
    np.testing.assert_allclose(
        np.asarray(plain), np.take_along_axis(np.asarray(jax.nn.softmax(logits)), np.asarray(expert), -1),
        rtol=1e-6,
    )
    with pytest.raises(ValueError, match="scoring"):
        moelib.route_topk(router, x, 3, moelib.Routing("tanh"))


# --- an expert layer that holds a share of the experts -------------------

E, K, D, F, N = 16, 4, 64, 32, 96
ROUTING = moelib.Routing("sigmoid", True, 2.446)


def _layer_params(seed=0, kind="gated_silu"):
    """The expert stacks of either kind: ``"gated_silu"``, three matrices
    an expert (Kimi Linear's, OLMoE's), or ``"relu2"``, two and no gate
    (Nemotron 3 Nano's)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    params = {
        "router": jax.random.normal(keys[0], (D, E)) * D**-0.5,
        "w_gate": jax.random.normal(keys[1], (E, D, F)) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (E, D, F)) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (E, F, D)) * F**-0.5,
    }
    if kind == "relu2":
        del params["w_gate"]
    return params


def _stacks(params):
    return [k for k in ("w_gate", "w_up", "w_down") if k in params]


def _share(params, first, count):
    take = lambda w: w[first : first + count]
    return {"router": params["router"], **{k: take(params[k]) for k in _stacks(params)}}


def _dense_masked(params, x, held=(0, E)):
    """Every expert of the range on every token, masked by the top-k over
    all experts: what a share has to equal.  The plain form of either
    kind of expert, by whether there is a gate matrix."""
    with jax.default_matmul_precision("highest"):
        h = x.reshape(-1, D)
        s = jax.nn.sigmoid(h @ params["router"])
        kth = jnp.sort(s, axis=-1)[:, -K][:, None]
        chosen = s >= kth  # no ties on random inputs
        w = jnp.where(chosen, s, 0.0)
        w = 2.446 * w / w.sum(-1, keepdims=True)
        up = jnp.einsum("nd,edf->enf", h, params["w_up"])
        if "w_gate" in params:
            hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", h, params["w_gate"])) * up
        else:
            hidden = jnp.square(jnp.maximum(up, 0.0))
        ys = jnp.einsum("enf,efd->end", hidden, params["w_down"])
        mine = (jnp.arange(E) >= held[0]) & (jnp.arange(E) < held[0] + held[1])
        return jnp.einsum("ne,end->nd", w * mine, ys).reshape(x.shape), chosen


def _held_layer(params, x, held):
    with jax.default_matmul_precision("highest"):
        return moelib.topk_moe_ffn(
            _share(params, *held), x, top_k=K, dtype=jnp.float32, routing=ROUTING, held=held
        )


_SKEWS = ["random", "everything_on_one_share", "nothing_on_this_share"]
SHARE_CASES = [
    *((held, skew, "gated_silu") for skew in _SKEWS for held in [(0, 4), (4, 4), (12, 4), (2, 8)]),
    # Experts of two matrices around a squared ReLU: the same dispatch, the
    # slab's backward (a vjp of the slab) with the other activation.
    ((2, 8), "random", "relu2"), ((4, 4), "everything_on_one_share", "relu2"),
]


@pytest.mark.parametrize(
    "held,skew,kind", SHARE_CASES, ids=[f"held{h[0]}_{h[1]}-{s}-{k}" for h, s, k in SHARE_CASES]
)
def test_a_share_computes_its_own_experts_part_and_drops_nothing(held, skew, kind):
    """Forward, and the hand-written backward of the held range (a
    ``custom_vjp`` that walks the slabs again) against autodiff of the
    plain dense masked form, for both kinds of expert."""
    params = _layer_params(kind=kind)
    x = jnp.abs(jax.random.normal(jax.random.key(7), (2, N // 2, D))) + 0.1
    if skew != "random":
        # Positive inputs: a router column of one sign decides an expert.
        sign = 1.0 if skew == "everything_on_one_share" else -1.0
        cols = jnp.arange(held[0], held[0] + held[1])
        params["router"] = params["router"].at[:, cols].set(
            sign * (0.1 + 0.01 * jnp.arange(held[1]))  # apart, and short of saturation: no ties
        )
    want, chosen = _dense_masked(params, x, held)
    got = _held_layer(params, x, held)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want), atol=3e-5, rtol=1e-5)
    on_share = float(chosen[:, held[0] : held[0] + held[1]].sum()) / (K * N)
    assert float(got.held_share) == pytest.approx(on_share, abs=1e-6)
    if skew == "everything_on_one_share":
        assert on_share == 1.0  # every assignment of every token, all computed
    if skew == "nothing_on_this_share":
        assert on_share == 0.0 and float(jnp.abs(got.out).max()) == 0.0
    probe = jax.random.normal(jax.random.key(3), x.shape)
    share = _share(params, *held)

    def dense(p, y):
        full = {**params, **{k: params[k].at[held[0] : held[0] + held[1]].set(p[k]) for k in _stacks(params)}}
        return jnp.sum(_dense_masked({**full, "router": p["router"]}, y, held)[0] * probe)

    def grouped(p, y):
        with jax.default_matmul_precision("highest"):
            out = moelib.topk_moe_ffn(p, y, top_k=K, dtype=jnp.float32, routing=ROUTING, held=held)
        return jnp.sum(out.out * probe)

    for g, w in zip(
        jax.tree.leaves(jax.grad(grouped, argnums=(0, 1))(share, x)),
        jax.tree.leaves(jax.grad(dense, argnums=(0, 1))(share, x)),
    ):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("kind", ["gated_silu", "relu2"])
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(kind):
    """The model-configs guide's test of the cut: 16 experts as 4 shares
    of 4.  The routed parts the four shares give, plus the shared expert
    (which every chip computes alike) counted once, are the uncut layer;
    with squared-ReLU experts and a shared expert of another width
    (Nemotron 3 Nano's layer) the uncut layer is also what the plain
    reference gives with every expert held."""
    from distributed_tensorflow_models_tpu.models import transformer_lm as tlm

    x = jax.random.normal(jax.random.key(5), (2, N // 2, D))
    sizes = dict(dtype=jnp.float32, routing=ROUTING, shared_experts=1, aux_loss_weight=0.0)
    if kind == "relu2":
        sizes.update(expert="relu2", shared_d_ff=F + 8)
        alone = tlm.MLP(D, F + 8, dtype=jnp.float32, use_bias=False, activation="relu2")
    else:
        alone = tlm.GatedMLP(D, F, jnp.float32)
    whole = tlm.TopKExpertsFFN(E, K, D, F, **sizes)
    variables = whole.init(jax.random.key(0), x)
    params = variables["params"]
    assert ("w_gate" in params) == (kind == "gated_silu")
    with jax.default_matmul_precision("highest"):
        uncut, _ = whole.apply(variables, x, mutable=["moe_stats"])
        shared = alone.apply({"params": params["shared"]}, x)
        parts, shares = [], []
        for first in range(0, E, 4):
            layer = tlm.TopKExpertsFFN(E, K, D, F, held=(first, 4), **sizes)
            mine = {**params, **{k: params[k][first : first + 4] for k in _stacks(params)}}
            out, stats = layer.apply({"params": mine}, x, mutable=["moe_stats"])
            parts.append(out - shared)  # this chip's routed part
            shares.append(float(stats["moe_stats"]["held_share"]))
        # The uncut layer against the dense masked formulation too.
        routed, _ = _dense_masked({k: params[k] for k in ("router", *_stacks(params))}, x)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(uncut), atol=3e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(uncut - shared), np.asarray(routed), atol=3e-5, rtol=1e-5)
    assert sum(shares) == pytest.approx(1.0, abs=1e-6)
    if kind == "relu2":
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from benchmark.lib import cells

        ref = cells.load_module("references", "nemotron_h")
        want, _, share = ref.experts(x.reshape(-1, D), params, K, 2.446, 0)
        np.testing.assert_allclose(np.asarray(uncut), np.asarray(want).reshape(x.shape), atol=3e-5, rtol=1e-5)
        assert float(share) == 1.0


@pytest.mark.parametrize("skew", [0.0, 0.08, 0.2], ids=["one_slab", "several_slabs", "every_assignment"])
def test_held_rows_in_one_slab_or_in_many(skew):
    """64 experts of which 4 are held, 2,048 assignments: the layer works
    through the held experts' sorted rows in slabs of 512 (four times an even
    routing's 128): one slab, several, or all four.  Each against the
    dense masked formulation, forward and gradient."""
    from distributed_tensorflow_models_tpu.parallel.moe import _slab_rows

    E64, held, n = 64, (8, 4), 512
    keys = jax.random.split(jax.random.key(11), 5)
    params = {
        "router": jax.random.normal(keys[0], (D, E64)) * D**-0.5,
        "w_gate": jax.random.normal(keys[1], (4, D, F)) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (4, D, F)) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (4, F, D)) * F**-0.5,
    }
    x = jnp.abs(jax.random.normal(keys[4], (1, n, D))) + 0.1
    params["router"] = params["router"].at[:, 8:12].add(skew * (1.0 + 0.1 * jnp.arange(4)))
    probe = jax.random.normal(jax.random.key(3), x.shape)

    def dense(p, y):
        with jax.default_matmul_precision("highest"):
            h = y.reshape(-1, D)
            s = jax.nn.sigmoid(h @ p["router"])
            chosen = s >= jnp.sort(s, axis=-1)[:, -K][:, None]
            w = jnp.where(chosen, s, 0.0)
            w = 2.446 * w / w.sum(-1, keepdims=True)
            ys = jnp.einsum(
                "enf,efd->end",
                jax.nn.silu(jnp.einsum("nd,edf->enf", h, p["w_gate"])) * jnp.einsum("nd,edf->enf", h, p["w_up"]),
                p["w_down"],
            )
            return jnp.einsum("ne,end->nd", w[:, 8:12], ys).reshape(y.shape), chosen[:, 8:12].sum()

    def layer(p, y):
        with jax.default_matmul_precision("highest"):
            return moelib.topk_moe_ffn(p, y, top_k=K, dtype=jnp.float32, routing=ROUTING, held=held)

    want, on_share = dense(params, x)
    prefix = _slab_rows(n * K, held[1], E64, 256)
    assert prefix == 512
    assert (int(on_share) > prefix) == (skew > 0), int(on_share)
    if skew == 0.2:
        assert int(on_share) == n * K
    got = layer(params, x)
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want), atol=3e-5, rtol=1e-5)
    assert float(got.held_share) == pytest.approx(int(on_share) / (n * K))
    g = jax.grad(lambda p, y: jnp.sum(layer(p, y).out * probe), argnums=(0, 1))(params, x)
    w = jax.grad(lambda p, y: jnp.sum(dense(p, y)[0] * probe), argnums=(0, 1))(params, x)
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)


def test_held_has_to_match_the_expert_stacks():
    params, x = _layer_params(), jnp.ones((1, 8, D))
    for held in ((0, 4), (14, 16), (-1, 16)):
        with pytest.raises(ValueError, match="held"):
            moelib.topk_moe_ffn(params, x, top_k=K, dtype=jnp.float32, held=held)


def _parent_topk_local(params, x, top_k, dtype):
    """``parallel/moe.py::_topk_local`` of the parent commit (e15f5a8),
    verbatim but for the scopes: what ``olmoe``'s path has to stay."""
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    x = x.astype(dtype)
    logits = jnp.dot(
        x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weight, expert = jax.lax.top_k(probs, top_k)
    flat = expert.reshape(n * top_k)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    counts = jnp.sum(jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0)
    rows = moelib._permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
    rows, sizes = moelib._pad_rows(rows, counts)
    grouped = functools.partial(moelib.grouped_matmul, group_sizes=sizes)
    gate = grouped(rows, params["w_gate"].astype(dtype))
    up = grouped(rows, params["w_up"].astype(dtype))
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(dtype)
    down = grouped(hidden, params["w_down"].astype(dtype))[: n * top_k]
    back = moelib._permute_rows(down, inverse, order).reshape(n, top_k, d)
    out = jnp.sum(back.astype(jnp.float32) * weight[..., None], axis=1).astype(dtype)
    fraction = counts.astype(jnp.float32) / (n * top_k)
    aux = num_experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out, aux, z, jnp.max(fraction) * num_experts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_with_everything_held_the_layer_is_bit_for_bit_the_parent_s(dtype):
    params = _layer_params(1)
    x = jax.random.normal(jax.random.key(2), (2, 40, D))
    probe = jax.random.normal(jax.random.key(3), x.shape)

    def ours(p, y):
        res = moelib.topk_moe_ffn(p, y, top_k=K, dtype=dtype)
        return jnp.sum(res.out.astype(jnp.float32) * probe) + res.aux_loss + res.z_loss, res

    def parents(p, y):
        out, aux, z, load = _parent_topk_local(p, y.reshape(-1, D), K, dtype)
        return jnp.sum(out.reshape(y.shape).astype(jnp.float32) * probe) + aux + z, (out, aux, z, load)

    (_, res), got = jax.value_and_grad(ours, argnums=(0, 1), has_aux=True)(params, x)
    (_, (out, aux, z, load)), want = jax.value_and_grad(parents, argnums=(0, 1), has_aux=True)(params, x)
    np.testing.assert_array_equal(np.asarray(res.out.reshape(-1, D), np.float32), np.asarray(out, np.float32))
    for a, b in ((res.aux_loss, aux), (res.z_loss, z), (res.load_max_over_mean, load)):
        assert float(a) == float(b)
    assert float(res.held_share) == 1.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


# --- the stack whose layers differ ----------------------------------------

def _tree(params):
    return {
        "/".join(k.key for k in path): tuple(leaf.shape)
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }


def test_kimi_linear_parameter_tree():
    model = get_model("transformer_lm", **SMALL)
    tokens = jnp.zeros((2, 16), jnp.int32)
    got = _tree(jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))["params"])
    d, w, h = 64, 64, 16  # hidden, heads x head, head
    kda = {
        "query/kernel": (d, w), "key/kernel": (d, w), "value/kernel": (d, w), "out/kernel": (w, d),
        "conv_query": (4, w), "conv_key": (4, w), "conv_value": (4, w),
        "f_a/kernel": (d, h), "f_b/kernel": (h, w), "g_a/kernel": (d, h), "g_b/kernel": (h, w),
        "beta/kernel": (d, 4), "A_log": (4,), "dt_bias": (w,), "o_norm/scale": (h,),
    }
    mla = {
        "query/kernel": (d, 4 * 24), "kv_a/kernel": (d, 24 + 8), "kv_a_norm/scale": (24,),
        "kv_b/kernel": (24, 4 * 32), "out/kernel": (4 * 16, d),
    }
    moe = {
        "router": (d, 16), "w_gate": (4, d, 32), "w_up": (4, d, 32), "w_down": (4, 32, d),
        "shared/gate/kernel": (d, 32), "shared/up/kernel": (d, 32), "shared/down/kernel": (32, d),
    }
    dense = {"gate/kernel": (d, 96), "up/kernel": (d, 96), "down/kernel": (96, d)}
    want = {"embedding/embedding": (97, d), "ln_f/scale": (d,), "head/kernel": (d, 97)}
    for i, mixer in enumerate(SMALL["layer_mixers"]):
        name, sizes = ("attn", mla) if mixer == "mla" else ("linear_attn", kda)
        ffn_name, ffn = ("mlp", dense) if i == 0 else ("moe", moe)
        for group, leaves in ((name, sizes), (ffn_name, ffn)):
            want.update({f"blocks_{i}/{group}/{k}": v for k, v in leaves.items()})
        want.update({f"blocks_{i}/ln1/scale": (d,), f"blocks_{i}/ln2/scale": (d,)})
    # No bias, no position table, an untied head, a dense first layer.
    assert got == want


def test_jitted_init_draws_the_parameters_without_the_forward_pass():
    """``TrainState.create``'s jitted init returns ``params`` and
    ``batch_stats`` alone: the ``moe_stats`` an expert layer sows hang on
    the whole forward pass, and the cell's init program compiled it for
    nothing.  Same parameters, to the bit, as the program that returned
    every collection; the program that draws them holds nothing of
    the chunk-wise delta rule or of the expert layers."""
    from distributed_tensorflow_models_tpu.core.train_state import TrainState
    from distributed_tensorflow_models_tpu.ops import optim

    model = get_model("transformer_lm", **SMALL)
    key, tokens = jax.random.key(5), jnp.zeros((2, 16), jnp.int32)
    whole = jax.jit(lambda r, s: model.init(r, s))
    assert "moe_stats" in whole(key, tokens)
    state = TrainState.create(model, optim.sgd(0.1), key, tokens, jit_init=True)
    for a, b in zip(jax.tree.leaves(whole(key, tokens)["params"]), jax.tree.leaves(state.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    drawn = jax.jit(lambda r, s: model.init(r, s)["params"])
    text = whole.lower(key, tokens).compile().as_text()
    assert "kda_core" in text and "moe_dispatch" in text
    text = drawn.lower(key, tokens).compile().as_text()
    assert "kda_core" not in text and "moe_dispatch" not in text


def test_the_published_configuration_counts_what_the_issue_reckoned():
    """At the published widths, cut as the benchmark cuts it: 602.4 M."""
    cut = {
        **get_config("kimi_linear").model_kwargs, "vocab_size": 20480, "num_layers": 5,
        "layer_mixers": ("kda", "kda", "kda", "mla", "kda"), "moe_held": (0, 8),
    }
    model = get_model("transformer_lm", **cut)
    shapes = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 64), jnp.int32)))["params"]
    tree = _tree(shapes)
    count = lambda prefix: sum(int(np.prod(s)) for k, s in tree.items() if k.startswith(prefix))
    assert count("blocks_1/linear_attn/") == 39_514_272
    assert count("blocks_3/attn/") == 29_114_880
    assert count("blocks_0/mlp/") == 63_700_992
    assert count("blocks_1/moe/") == 8 * 7_077_888 + 7_077_888 + 589_824
    assert count("") == 602_433_408
    full = get_config("kimi_linear").model_kwargs
    assert full["layer_mixers"].count("mla") == 7 and full["layer_mixers"].count("kda") == 20
    assert [i + 1 for i, m in enumerate(full["layer_mixers"]) if m == "mla"] == [4, 8, 12, 16, 20, 24, 27]


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"decode": True}, "neither decode"),
        ({"layer_mixers": ("kda", "mla")}, "names 2 layers"),
        ({"layer_mixers": ("kda", "kda", "gla", "mla", "kda")}, "unknown layer_mixers"),
        ({"moe_scoring": "tanh"}, "unknown moe_scoring"),
        ({"mlp": "relu"}, "unknown mlp"),
        ({"pipelined": True}, "GPT-2 block only"),
    ],
)
def test_settings_the_stack_does_not_have_are_refused(kwargs, match):
    model = get_model("transformer_lm", **{**SMALL, **kwargs})
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32)))


def test_fit_trains_the_kimi_linear_program_config_and_reports_the_held_share(tmp_path):
    from distributed_tensorflow_models_tpu.core import mesh as meshlib
    from distributed_tensorflow_models_tpu.harness import train as trainlib

    cfg = get_config(
        "kimi_linear", model_kwargs={**SMALL, "max_len": 40}, vocab_size=97, num_steps=40,
        global_batch_size=2, train_steps=8, log_every_steps=2, trace_export=True,
    )
    mesh = meshlib.data_parallel_mesh(jax.devices()[:1])
    result = trainlib.fit(cfg, str(tmp_path), mesh=mesh)
    assert int(result.state.step) == 8
    import json
    import subprocess
    import sys

    rows = [json.loads(l) for l in open(tmp_path / "metrics.jsonl")]
    final = [r for r in rows if "loss" in r][-1]
    assert np.isfinite(final["loss"])
    # 4 of 16 experts held: about a quarter of the assignments, and the
    # three routing statistics beside it; no auxiliary loss in the objective.
    assert 0.05 < final["moe_held_share"] < 0.6
    assert final["moe_load_max_over_mean"] >= 1.0 and "moe_aux_loss" in final
    assert "aux_loss" not in final and final["loss"] == pytest.approx(final["nll"])
    check = subprocess.run(
        [sys.executable, "scripts/check_metrics_schema.py", str(tmp_path / "metrics.jsonl")],
        capture_output=True, text=True,
    )
    assert check.returncode == 0, check.stdout + check.stderr
    # The scopes the per-layer readers find the new layers by.
    scopes = json.load(open(tmp_path / "step_scopes_p0.json"))["modules"]
    names = " ".join(n for module in scopes.values() for n in module.values())
    for scope in ("linear_attn", "kda_core", "attention_core", "moe_shared", "moe_dispatch", "moe_experts"):
        assert f"/{scope}/" in names or f"({scope})" in names, scope
    telemetry = json.load(open(tmp_path / "telemetry.json"))["metrics"]
    # One MLA layer's call, counted once per traced program (blockwise on the CPU).
    assert telemetry["attention/route_blockwise"] >= 1 and telemetry.get("attention/route_fused", 0) == 0
    # Four KDA layers' calls likewise (the plain route on the CPU), each
    # mixer's placement beside its core's route.
    assert telemetry["kda/route_plain"] >= 4 and telemetry["kda/route_kernel"] == 0
    assert telemetry["kda/mixer_plain"] == telemetry["kda/route_plain"]
    assert telemetry["kda/mixer_fused"] == 0


def test_kimi_linear_warms_up_and_the_other_language_models_do_not():
    from distributed_tensorflow_models_tpu.harness.config import OptimizerConfig

    schedule = get_config("kimi_linear").optimizer.schedule()
    got = [float(schedule(t)) for t in (0, 1, 39, 1998, 1999, 2000, 10_000)]
    want = [3e-4 * f for f in (1 / 2000, 2 / 2000, 40 / 2000, 1999 / 2000, 1.0, 1.0, 1.0)]
    assert got == pytest.approx(want, rel=1e-6)
    for name in ("transformer_lm", "olmoe"):
        assert get_config(name).optimizer.schedule() == 3e-4
    # On top of a schedule: the reference's staircase decay behind four steps of warm-up.
    both = OptimizerConfig(
        name="sgd", learning_rate=1.0, decay_steps=10, decay_rate=0.5, warmup_steps=4
    ).schedule()
    assert [float(both(t)) for t in (0, 3, 4, 9, 10, 25)] == pytest.approx(
        [0.25, 1.0, 1.0, 1.0, 0.5, 0.25]
    )
