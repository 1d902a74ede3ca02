"""What Kimi Linear brought to the program, at a small size on the CPU in
float32: the chunk-wise delta rule against its recurrence, the latent
attention's shapes through every attention route, the router's sigmoid
scores, and the stack whose layers differ.  Every call of the library goes
through ``jax.jit``, a value and its gradients as one program.

The expert layer that holds a share of the experts (and the shares adding
up) is ``tests/test_moe.py``'s; ``fit`` through the program config is a
case of ``tests/test_lm_fit_smoke.py``; the plain reference's side of it
(logits, loss, every gradient) is
``tests/benchmark/test_bench_reference_kimi_linear.py``.
"""

import contextlib
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


def _probed(f, x, dtype="float32"):
    """``(f(*x), its five gradients)`` of a sum of the output weighed entry
    by entry, as one jitted program: one compile where op by op is some
    hundred, and the forward pass once for both."""
    x = tuple(a.astype(dtype) for a in x[:3]) + tuple(x[3:])
    probe = jax.random.normal(jax.random.key(9), x[2].shape)

    def loss(*a):
        out = f(*a)
        return jnp.sum(out.astype(jnp.float32) * probe), out

    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*x)
    return (out, *grads)


_NAMES = "q k v g beta".split()

KDA_CASES = [
    # chunk, sub, length, decay; a decay's regime takes one chunk and a remainder
    (64, 16, 128, "mild"),       # two whole chunks: the carried state
    (64, 16, 70, "near_zero"),   # a length the chunk does not divide
    (64, 16, 150, "mixed"),      # two chunks and a remainder, both regimes in each
    (32, 8, 40, "near_one"),     # a state that hardly forgets, carried into a remainder
    (32, 16, 33, "near_zero"),   # a remainder of one token
    (16, 16, 64, "mixed"),       # one block a chunk: no product between blocks
    (16, 4, 20, "mild"),         # four blocks a chunk: two doublings of the inverse
    (128, 16, 130, "near_zero"), # eight blocks a chunk: three doublings, the longest sums of decays
    (8, 8, 5, "near_one"),       # shorter than a chunk
]


@functools.cache
def _chunked_and_recurrence(chunk, sub, T, decay):
    # One batch row of two heads: an odd number of heads is the kernels' test below.
    x = _kda_inputs(chunk + T, T, decay, B=1, H=2)
    return (
        _probed(functools.partial(linattn.chunked_kda, chunk=chunk, sub=sub), x),
        _probed(linattn.recurrent_kda, x),
    )


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("chunk,sub,T,decay", KDA_CASES)
def test_chunked_delta_rule_is_the_recurrence(chunk, sub, T, decay, what):
    got, want = _chunked_and_recurrence(chunk, sub, T, decay)
    if what == "forward":
        scale = float(jnp.abs(want[0]).max())
        assert scale > 1e-3
        np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-4 * scale, rtol=1e-4)
        return
    for name, g, w in zip(_NAMES, got[1:], want[1:]):
        assert bool(jnp.isfinite(g).all()), name
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-3 * scale + 1e-6, rtol=1e-3, err_msg=name
        )


def test_a_split_exponential_would_overflow_where_the_chunked_form_does_not():
    """The case the ``near_zero`` decays are there for: ``e^{-G_s}``
    inside one chunk is beyond float32, the chunk-wise form is finite."""
    x = _kda_inputs(3, 64, "near_zero")
    split = jax.jit(lambda g: jnp.isfinite(jnp.exp(-jnp.cumsum(g, axis=1))).all())
    assert not bool(split(x[3]))
    out = jax.jit(linattn.chunked_kda)(*x)
    assert bool(jnp.isfinite(out).all())


@pytest.mark.parametrize("size,sub", [(16, 16), (32, 8), (64, 16), (64, 4)])
def test_unit_lower_inverse_and_its_cotangent(size, sub):
    # Entries as the layer has them: b_t k_t . k_s of unit keys, below 1.
    a = jnp.tril(0.3 * jax.random.normal(jax.random.key(size + sub), (3, 2, size, size)), -1)
    eye = jnp.eye(size)
    probe = jax.random.normal(jax.random.key(1), a.shape)
    probed = lambda f: jax.jit(jax.value_and_grad(lambda a: (lambda y: (jnp.sum(y * probe), y))(f(a)), has_aux=True))
    with jax.default_matmul_precision("highest"):
        (_, got), g = probed(lambda a: linattn.unit_lower_inverse(a, sub))(a)
        (_, _), w = probed(lambda a: jnp.linalg.inv(eye + jnp.tril(a, -1)))(a)
        want = jax.jit(
            lambda a: jax.scipy.linalg.solve_triangular(
                eye + a, jnp.broadcast_to(eye, a.shape), lower=True, unit_diagonal=True
            )
        )(a)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3 * float(jnp.abs(want).max()))
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
    (72, "near_one"),     # a state that hardly forgets, carried into a remainder
    (384, "mixed"),       # three grid steps of two chunks
    # One state a grid step's token block is kept (ISSUE 47): the backward
    # kernel walks a block's chunks forward from it.
    (50, "mild"),         # one chunk, padded: a block the walk makes nothing for
    (500, "mixed"),       # one grid step of eight chunks, the last one padded
    (1500, "near_one"),   # three grid steps of eight chunks: a kept state a block, the last chunk padded
]


def _kernel_route(*x):
    return linattn.kernel_kda(*x, None, 64, True)


_KDA_ROUTES = {
    "kernel": _kernel_route,
    "plain": linattn.plain_kda,
    "recurrence": linattn.recurrent_kda,
}


@functools.cache
def _kda_result(route, T, decay, dtype="float32"):
    """``{"forward": (output,), "gradient": the five gradients of a probed
    sum}`` of one route: one program, compiled and run once in a worker
    for every case that asks."""
    out, *grads = _probed(_KDA_ROUTES[route], _kda_inputs(T, T, decay, B=1, H=2, dk=128, dv=128), dtype)
    return {"forward": (out,), "gradient": tuple(grads)}


@pytest.mark.parametrize("oracle", ["recurrence", "plain"])
@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("T,decay", KERNEL_CASES)
def test_the_kernels_are_the_recurrence_and_the_plain_route(T, decay, what, oracle):
    got, want = _kda_result("kernel", T, decay)[what], _kda_result(oracle, T, decay)[what]
    tol = 2e-4 if what == "forward" else 1e-3
    for name, g, w in zip(_NAMES, got, want):
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
    (got_out, *got), (want_out, *want) = _probed(_kernel_route, x), _probed(linattn.recurrent_kda, x)
    got_sum, want_sum = jnp.sum(got_out * probe), jnp.sum(want_out * probe)
    assert float(got_sum) == pytest.approx(float(want_sum), rel=1e-4, abs=1e-4)
    for name, g, w in zip(_NAMES, got, want):
        scale = float(jnp.abs(w).max())
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=1e-3 * scale + 1e-6, rtol=1e-3, err_msg=name
        )


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("T,decay", [(128, "mild"), (150, "mixed"), (500, "mixed")])  # of KERNEL_CASES: their float32 results
def test_the_kernels_in_bf16_are_the_plain_route_in_bf16(T, decay, what):
    """Both routes round the same operands to bfloat16 and accumulate in
    float32; they differ in the order of float32 sums and in which
    cotangents the backward rounds, so they agree to a few bfloat16
    roundings of the largest entry (0.03), and each is as near the float32
    result as the other (within a factor of two)."""
    got = _kda_result("kernel", T, decay, "bfloat16")[what]
    plain = _kda_result("plain", T, decay, "bfloat16")[what]
    exact = _kda_result("plain", T, decay)[what]
    for name, g, p, e in zip(_NAMES, got, plain, exact):
        assert g.dtype == p.dtype, name
        g, p = g.astype(jnp.float32), p.astype(jnp.float32)
        scale = float(jnp.abs(e).max())
        assert float(jnp.abs(g - p).max()) <= 0.03 * scale, name
        assert float(jnp.abs(g - e).max()) <= 2 * float(jnp.abs(p - e).max()) + 4e-3 * scale, name


def _forward_rule_results(x):
    """The shapes of the output and of what the kernels' forward rule
    hands the backward one beside the padded inputs (the states, ``T``)."""
    B, T = x[4].shape[:2]
    flat = lambda a: a.reshape(B, T, -1)
    out, res = jax.eval_shape(
        lambda *a: linattn._kernel_fwd(*a, None, 64, True, None), *map(flat, x[:4]), x[4]
    )
    return (out, *res[5:])


@pytest.mark.parametrize(
    "T, blocks, chunks",
    [(50, 1, 1), (128, 1, 2), (150, 3, 1), (500, 1, 8), (1500, 3, 8)],
)
def test_the_kernels_keep_one_state_a_token_block_and_every_chunks_inverse(T, blocks, chunks):
    """What the forward rule hands the backward one beside the padded
    inputs, as traced: the state at the start of each grid step's token
    block ``[B, n / chunks, H, dv, dk]`` and ``T`` for each of the ``n``
    chunks; :func:`kernel_kda_results` says the same shapes."""
    q, k, v, g, beta = x = _kda_inputs(T, T, "mild", B=1, H=2, dk=128, dv=256)
    flat = lambda a: a.reshape(1, T, -1)
    out, states, ts = _forward_rule_results(x)
    assert out.shape == (1, T, 2 * 256)
    assert states.shape == (1, blocks, 2, 256, 128) and states.dtype == jnp.float32
    assert ts.shape == (1, blocks * chunks, 2, 64, 64) and ts.dtype == jnp.float32
    said = linattn.kernel_kda_results(flat(q), flat(v), beta)
    assert [(x.shape, x.dtype) for x in said] == [
        ((1, blocks * chunks * 64, 2 * 256), out.dtype), (states.shape, states.dtype), (ts.shape, ts.dtype)
    ]


@contextlib.contextmanager
def _chunks_a_block(monkeypatch, chunks):
    """The kernels with ``chunks`` chunks a grid step.  The rules are
    jitted, so their traces at eight chunks a block go, here and when the
    patch does."""
    clear = lambda: [f.clear_cache() for f in (linattn._kernel_fwd, linattn._kernel_bwd)]
    with monkeypatch.context() as patch:
        clear()
        patch.setattr(linattn, "_KERNEL_BLOCK_CHUNKS", (chunks,))
        try:
            yield
        finally:
            clear()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("chunks", [1, 2])
@pytest.mark.parametrize("T,decay", [(500, "mixed")])  # of KERNEL_CASES: eight chunks a block
def test_the_walked_states_are_the_forward_kernels_bit_for_bit(T, decay, chunks, dtype, monkeypatch):
    """The backward kernel makes a block's chunks' states again from the
    one state it is handed and ``T``, with the forward kernel's own
    expressions: the output and the five cotangents at eight chunks a block
    (seven states made again) equal, to the last bit, those at one chunk a
    block, where every chunk's state is the forward kernel's own and
    nothing is made again, and those at two.  But for one array, which is
    XLA:CPU's and not the walk's: at one chunk a block the kernels' loop
    over a block's chunks has one trip and dissolves, the interpreted
    backward kernel is another program around ``dG``'s sums, and ``dg`` in
    float32 moves in its last bit (1e-7 of its largest entry; the kernels
    that kept a state a chunk, ISSUE 47's parent, did the same between
    eight chunks a block and one, and equal these at eight bit for bit)."""
    walked = _kda_result("kernel", T, decay, dtype)
    x = _kda_inputs(T, T, decay, B=1, H=2, dk=128, dv=128)
    with _chunks_a_block(monkeypatch, chunks):
        assert _forward_rule_results(x)[1].shape[1] == 8 // chunks
        out, *grads = _probed(_kernel_route, x, dtype)  # not through the cache of results: the patched kernels'
    assert jnp.array_equal(out, walked["forward"][0])
    for name, g, w in zip(_NAMES, grads, walked["gradient"]):
        assert g.dtype == w.dtype, name
        if (name, chunks, dtype) == ("g", 1, "float32"):
            assert float(jnp.abs(g - w).max()) <= 1e-6 * float(jnp.abs(w).max())
        else:
            assert jnp.array_equal(g, w), name


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
    got = jax.jit(linattn.chunked_kda)(*x)
    assert _kda_route_counts() == (kernel0 + 1, plain0)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(linattn.recurrent_kda)(*x)
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
    dtypes = lambda route: jax.tree.map(
        lambda x: x.dtype, jax.eval_shape(_PASSES[which, route], *_pass_inputs(T, jnp.bfloat16)[which])
    )
    assert dtypes("fused") == dtypes("plain")


def test_the_first_positions_of_every_sequence_see_zeros_before_them():
    """The convolution's history before position 0 is zeros, in every
    batch row (the rows before a block are the block before it, never the
    sequence before it) and at a token block's first rows alike; the
    cotangent after the last position is zero too."""
    T, W = 150, PASS_H * PASS_D
    xq, w = _pass_inputs(T, jnp.float32)["prologue"][::4][:2]
    conv = jax.jit(lambda x: linattn.short_conv_silu(x, w, PASS_D, True, 1e-6, PASS_BLOCK, True))
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
    grad = jax.jit(jax.grad(lambda x: jnp.sum(conv(x)[:, :100] * probe)))
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
    x = jax.random.normal(jax.random.key(1), (1, 150, 64), dtype)
    params = jax.jit(mixer.init)(jax.random.key(0), x)
    params = jax.tree.map(
        lambda p: p + 0.05 * jax.random.normal(jax.random.key(p.size), p.shape), params
    )
    probe = jax.random.normal(jax.random.key(2), x.shape)

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
        assert jax.tree.structure(jax.eval_shape(mixer.init, jax.random.key(0), x)) == jax.tree.structure(params)
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
    both = lambda f: jax.jit(
        jax.value_and_grad(lambda *a: (lambda y: (jnp.sum(y * probe), y))(f(*a)), argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    with jax.default_matmul_precision("highest"):
        ((_, got), got_grads), ((_, want), want_grads) = both(fn), both(want_fn)
    assert got.shape == v.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for g, w in zip(got_grads, want_grads):
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
    # A window is admitted with the causal mask (PR 44), never without it.
    assert attnlib.fused_admissible(q, q, v, window=128) is want
    assert attnlib.fused_admissible(q, q, v, window=128, causal=False) is False


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

    # One layer of each kind: the delta rule over the dense feed-forward, MLA over the experts.
    model = get_model("transformer_lm", **{**SMALL, "num_layers": 2, "layer_mixers": ("kda", "mla")})
    key, tokens = jax.random.key(5), jnp.zeros((2, 16), jnp.int32)
    whole = jax.jit(lambda r, s: model.init(r, s)).lower(key, tokens).compile()
    everything = whole(key, tokens)
    assert "moe_stats" in everything
    state = TrainState.create(model, optim.sgd(0.1), key, tokens, jit_init=True)
    for a, b in zip(jax.tree.leaves(everything["params"]), jax.tree.leaves(state.params), strict=True):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    drawn = jax.jit(lambda r, s: model.init(r, s)["params"])
    text = whole.as_text()
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
