"""The state-space scan's Pallas kernels (``ops/ssm.py::kernel_ssd``) on
the CPU in interpret mode, and the route ``chunked_ssd`` takes.

What the kernels do on the chip (their speed, the compiler's verdict on
their tiles) is ``tests/test_chip_compile.py``'s and the chip's; here:
the mathematics, against the recurrence token by token in float32 and
against the plain route in bfloat16, what the backward pass keeps, and
the choice of route from the backend, the shapes and the dtype of a call.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.ops import ssm
from distributed_tensorflow_models_tpu.telemetry import registry as reglib

NAMES = ("x", "dt", "a_log", "b", "c", "d_skip")


def _inputs(seed, T, decay, B=2, H=2, P=64, N=128, G=0):
    """``G`` 0: ``B`` and ``C`` ``[B, T, N]`` (one group, the call without
    the axis); else ``[B, T, G, N]``."""
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    raw = jax.random.normal(ks[1], (B, T, H))
    dt = {
        # softplus(dt + dt_bias) for dt_bias drawn as the model draws it.
        "model": jax.nn.softplus(raw - 3.0),
        # dt large: exp(-G) alone overflows float32 inside one chunk.
        "near_0": 20.0 + 10.0 * jax.nn.sigmoid(raw),
        # dt near 0: no decay, (almost) nothing written.
        "near_1": 1e-6 * jax.nn.sigmoid(raw),
    }[decay]
    a_log = jnp.log(jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0))
    shape = (B, T, G, N) if G else (B, T, N)
    b = jax.random.normal(ks[3], shape) * N**-0.5
    c = jax.random.normal(ks[4], shape)
    d_skip = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a_log, b, c, d_skip


# length, decay, head width, heads, chunk, groups of heads (0: one, the
# call without the axis); batch 2, a state of 128.
KERNEL_CASES = [
    (512, "model", 64, 2, 256, 0),   # two chunks: the carried state
    (600, "model", 64, 4, 256, 0),   # three chunks, a length the chunk does not divide
    (300, "near_0", 64, 2, 256, 0),
    (300, "near_1", 64, 2, 256, 0),
    (300, "model", 128, 2, 256, 0),  # a head a lane tile
    (600, "model", 64, 2, 512, 0),   # the other chunk the kernels take
    # Two groups of six heads: a grid step takes two heads (a lane tile) and
    # never of two groups, so a group is three steps that share its C B^T
    # and add up its dB and dC, and the next group starts both afresh.
    (300, "model", 64, 12, 256, 2),
]
_IDS = [
    f"{T}-{decay}-p{P}-h{H}-c{chunk}" + (f"-g{G}" if G else "")
    for T, decay, P, H, chunk, G in KERNEL_CASES
]

_ROUTES = {
    "kernel": lambda chunk: lambda *a: ssm.kernel_ssd(*a, chunk, True),
    "plain": lambda chunk: lambda *a: ssm.plain_ssd(*a, chunk=chunk),
    "recurrence": lambda chunk: ssm.recurrent_ssd,
}


@functools.lru_cache(maxsize=None)
def _result(route, case, what, dtype="float32"):
    """The output, or the six gradients of a probed sum, of one route."""
    T, decay, P, H, chunk, G = case
    args = _inputs(1, T, decay, H=H, P=P, G=G)
    args = tuple(a.astype(dtype) if i in (0, 3, 4) else a for i, a in enumerate(args))
    f = _ROUTES[route](chunk)
    # Jitted: one compile a result, where op by op is some hundred.
    with jax.default_matmul_precision("highest"):
        if what == "forward":
            return (jax.jit(f)(*args),)
        probe = jax.random.normal(jax.random.key(9), args[0].shape)
        loss = lambda *a: jnp.sum(f(*a).astype(jnp.float32) * probe)
        return jax.jit(jax.grad(loss, argnums=tuple(range(6))))(*args)


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("case", KERNEL_CASES, ids=_IDS)
def test_the_kernels_are_the_recurrence(case, what):
    """Float32, interpreted: the output and the gradients to 1e-5 of the
    largest entry, over two chunks and more, a length the chunk does not
    divide, decays near 0 (``exp(-G)`` alone is beyond float32 inside a
    chunk) and near 1, batch 2.  ``A_log``'s gradient to 1e-4: chunk-wise
    it is a difference of a mask's row and column sums added up over every
    token, and the plain route reads 1e-5 to 6e-5 on these cases itself."""
    T, decay, P, H, chunk, _ = case
    if decay == "near_0":
        _, dt, a_log = _inputs(1, T, decay, H=H, P=P)[:3]
        G = np.cumsum(np.asarray(-jnp.exp(a_log) * dt, np.float64), axis=1)
        assert np.exp(-G[:, min(chunk, T) - 1]).max() > 1e38
    got, want = _result("kernel", case, what), _result("recurrence", case, what)
    for name, g, w in zip(NAMES if what == "gradient" else ("output",), got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert bool(jnp.isfinite(g).all()), name
        # Room where the gradient is itself tiny (dt large: the decay's is
        # a difference of vanishing terms).
        scale = float(jnp.abs(w).max())
        tol = 1e-4 if name == "a_log" else 1e-5
        assert float(jnp.abs(g - w).max()) <= tol * scale + 1e-6, name


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("case", KERNEL_CASES[:2] + KERNEL_CASES[4:5], ids=_IDS[:2] + _IDS[4:5])
def test_the_kernels_in_bf16_are_the_plain_route_in_bf16(case, what):
    """Both routes round the same operands to bfloat16 and accumulate in
    float32; they differ in the order of float32 sums and in which
    cotangents the backward rounds, so they agree to a few bfloat16
    roundings of the largest entry, and each is as near the float32
    result as the other."""
    got = _result("kernel", case, what, "bfloat16")
    plain = _result("plain", case, what, "bfloat16")
    exact = _result("recurrence", case, what)
    for name, g, p, e in zip(NAMES if what == "gradient" else ("output",), got, plain, exact):
        assert g.dtype == p.dtype, name
        g, p = g.astype(jnp.float32), p.astype(jnp.float32)
        scale = float(jnp.abs(e).max())
        assert float(jnp.abs(g - p).max()) <= 0.03 * scale, name
        assert float(jnp.abs(g - e).max()) <= 2 * float(jnp.abs(p - e).max()) + 4e-3 * scale, name


def test_the_backward_pass_keeps_a_state_a_chunk_and_never_one_a_token():
    B, T, H, P, N, chunk = 2, 600, 2, 64, 128, 256
    args = _inputs(2, T, "model", B=B, H=H, P=P, N=N)
    loss = lambda *a: jnp.sum(ssm.kernel_ssd(*a, chunk, True))
    kept = jax.tree.leaves(jax.vjp(loss, *args)[1])  # what the pullback holds
    chunks = -(-T // chunk)
    states = [a for a in kept if a.size == B * chunks * N * H * P]
    assert len(states) == 1 and states[0].dtype == jnp.float32
    # Everything else is an input's size: nothing T x N x P, nothing T x L.
    assert max(a.size for a in kept if a is not states[0]) <= B * chunks * chunk * H * P
    assert sum(a.size for a in kept) < B * T * N * P


def test_without_a_skip_the_kernels_add_none():
    x, dt, a_log, b, c, _ = _inputs(3, 300, "model")
    got = ssm.kernel_ssd(x, dt, a_log, b, c, None, 256, True)
    with jax.default_matmul_precision("highest"):
        want = ssm.recurrent_ssd(x, dt, a_log, b, c)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max())


def _route_counts():
    reg = reglib.get_registry()
    return (
        reg.counter(reglib.SSD_ROUTE_KERNEL).value,
        reg.counter(reglib.SSD_ROUTE_PLAIN).value,
    )


@pytest.mark.parametrize(
    "backend, devices, H, P, N, chunk, dtype, want, G",
    [
        # Off the chip every call is the plain form.
        ("cpu", 1, 64, 64, 128, 256, "bfloat16", "plain", 0),
        # Described as one TPU: the cells' calls (Granite's one group,
        # Nemotron's eight), and what else the kernels take.
        ("tpu", 1, 64, 64, 128, 256, "bfloat16", "kernel", 0),
        ("tpu", 1, 64, 64, 128, 256, "bfloat16", "kernel", 8),
        ("tpu", 1, 64, 64, 128, 256, "float32", "kernel", 0),
        ("tpu", 1, 6, 128, 256, 512, "bfloat16", "kernel", 0),
        ("tpu", 1, 6, 128, 256, 512, "bfloat16", "kernel", 3),
        # ... and what they do not.
        ("tpu", 1, 4, 8, 16, 16, "bfloat16", "plain", 0),       # the rehearsal's sizes
        ("tpu", 1, 64, 96, 128, 256, "bfloat16", "plain", 0),   # heads that fill no lane tile
        ("tpu", 1, 3, 64, 128, 256, "bfloat16", "plain", 0),    # half a tile left over
        ("tpu", 1, 64, 64, 128, 256, "bfloat16", "plain", 64),  # a group of one head: half a tile
        ("tpu", 1, 64, 64, 64, 256, "bfloat16", "plain", 0),    # half a tile of state
        ("tpu", 1, 64, 64, 128, 128, "bfloat16", "plain", 0),   # a smaller chunk
        ("tpu", 1, 64, 64, 128, 384, "bfloat16", "plain", 0),   # one they were not built for
        # A jit over several devices cannot partition a Mosaic kernel.
        ("tpu", 4, 64, 64, 128, 256, "bfloat16", "plain", 0),
    ],
)
def test_which_route_the_scan_takes(monkeypatch, backend, devices, H, P, N, chunk, dtype, want, G):
    """``chunked_ssd`` chooses from the backend and what the call shows at
    trace time, and counts exactly one of the two routes a traced call."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    bc = spec((1, 700, G, N) if G else (1, 700, N), dtype)
    args = (spec((1, 700, H, P), dtype), spec((1, 700, H)), spec((H,)), bc, bc, spec((H,)))
    assert ssm.ssd_route(*args[:5], chunk=chunk) == want
    before = _route_counts()
    # Traced, not run: a Mosaic kernel cannot run here.
    out = jax.eval_shape(functools.partial(ssm.chunked_ssd, chunk=chunk), *args)
    assert out.shape == (1, 700, H, P) and out.dtype == jnp.dtype(dtype)
    after = _route_counts()
    assert (after[0] - before[0], after[1] - before[1]) == ((1, 0) if want == "kernel" else (0, 1))


def test_one_dtype_for_x_b_and_c_or_the_plain_route(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    args = lambda bc: (
        spec((1, 512, 64, 64), jnp.bfloat16), spec((1, 512, 64)), spec((64,)),
        spec((1, 512, 128), bc), spec((1, 512, 128), bc),
    )
    assert ssm.ssd_route(*args(jnp.bfloat16), chunk=256) == "kernel"
    assert ssm.ssd_route(*args(jnp.float32), chunk=256) == "plain"


def test_under_a_mesh_with_an_automatic_axis_the_scan_stays_plain(monkeypatch):
    """A Mosaic kernel cannot be partitioned automatically: with a mesh in
    scope the kernels are for a ``shard_map`` that makes every axis manual."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)
    args = (
        spec((2, 512, 64, 64), jnp.bfloat16), spec((2, 512, 64)), spec((64,)),
        spec((2, 512, 128), jnp.bfloat16), spec((2, 512, 128), jnp.bfloat16),
    )
    mesh = jax.make_mesh((2,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    routes = []

    def inside(*a):
        routes.append(ssm.ssd_route(*a, chunk=256))
        return a[0]

    rows, whole = jax.P("data"), jax.P()
    with jax.set_mesh(mesh):
        assert ssm.ssd_route(*args, chunk=256) == "plain"
        jax.eval_shape(
            jax.shard_map(
                inside, in_specs=(rows, rows, whole, rows, rows), out_specs=rows
            ),
            *args,
        )
    assert routes == ["kernel"]  # every axis manual: one device's program


def test_the_entry_runs_the_kernels_where_it_would_on_the_chip(monkeypatch):
    """``chunked_ssd`` itself, taken down the kernel route (the backend
    described as one TPU, the kernels interpreted): the recurrence, one
    count of ``ssd/route_kernel``, and the ``ssd_core`` scope on the call."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    kernels = ssm.kernel_ssd
    monkeypatch.setattr(ssm, "kernel_ssd", lambda *a: kernels(*a[:6], a[6], True))
    args = _inputs(5, 300, "model")
    before = _route_counts()
    got = ssm.chunked_ssd(*args)
    after = _route_counts()
    assert (after[0] - before[0], after[1] - before[1]) == (1, 0)
    with jax.default_matmul_precision("highest"):
        want = ssm.recurrent_ssd(*args)
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max())
    grad = jax.jit(jax.grad(lambda *a: jnp.sum(ssm.chunked_ssd(*a)), argnums=(0, 1)))
    text = grad.lower(*args).as_text(debug_info=True)
    assert "plain_ssd" not in text and "ssd_core" in text
