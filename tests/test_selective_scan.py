"""Mamba-1's selective scan (``ops/selective_scan.py``): the three routes
against each other with gradients (the Pallas kernels in interpret mode at
a toy size), lengths that are no multiple of a chunk, decays near 0, the
route a call takes and its counters."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.ops import selective_scan as sscan
from distributed_tensorflow_models_tpu.telemetry import registry as reglib


def _inputs(B=2, T=20, D=1024, N=4, dtype=jnp.float32, seed=0, dt_shift=-1.0):
    ks = jax.random.split(jax.random.key(seed), 6)
    x = jax.random.normal(ks[0], (B, T, D)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, T, D)) + dt_shift)
    a_log = jnp.log(jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (D, N)))
    a_log = a_log + 0.1 * jax.random.normal(ks[2], (D, N))
    b, c = (jax.random.normal(k, (B, T, N)).astype(dtype) for k in ks[3:5])
    return x, dt, a_log, b, c, jax.random.normal(ks[5], (D,))


_ROUTES = {
    "plain": lambda *a: sscan.plain_selective_scan(*a, chunk=8),
    "kernel": lambda *a: sscan.kernel_selective_scan(*a, 8, True),
}


@pytest.fixture(scope="module")
def oracle():
    args = _inputs()
    weight = jax.random.normal(jax.random.key(9), args[0].shape)
    run = lambda f: jax.jit(
        lambda *a: (f(*a), jax.grad(lambda *b: jnp.sum(f(*b) * weight), argnums=tuple(range(6)))(*a))
    )
    return args, run, run(sscan.recurrent_selective_scan)(*args)


@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_a_route_is_the_recurrence_with_its_gradients(oracle, route):
    """20 tokens in chunks of 8: two whole chunks and a rest that is
    padded with tokens that leave the state alone; 1,024 channels: one
    block of the kernels'.  float32 on every route: reduction order only
    (the chunk-wise forms sum a chunk's writes in another order)."""
    args, run, (want, want_grads) = oracle
    got, grads = run(_ROUTES[route])(*args)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    for name, g, w in zip(("x", "dt", "a_log", "b", "c", "d_skip"), grads, want_grads):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert float(jnp.linalg.norm(g - w) / jnp.linalg.norm(w)) < 1e-5, name


@pytest.mark.parametrize("route", sorted(_ROUTES))
def test_large_steps_take_no_positive_exponent(route):
    """``dt`` up to 60 and ``A`` down to -16: decays of e^-900.  A form
    that divided by a running product of decays would leave float32;
    every route stays finite and at the recurrence."""
    args = _inputs(B=1, T=16, N=16, dt_shift=40.0)
    want = sscan.recurrent_selective_scan(*args)
    got = _ROUTES[route](*args)
    assert bool(jnp.all(jnp.isfinite(got)))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-5)


def test_bfloat16_inputs_keep_a_float32_state_and_come_back_bfloat16():
    args = _inputs(B=1, T=24, dtype=jnp.bfloat16, seed=3)
    want = sscan.recurrent_selective_scan(*args)
    for route in _ROUTES.values():
        got = route(*args)
        assert got.dtype == jnp.bfloat16
        # One rounding of the result to bfloat16 (2^-8 of it), no more.
        assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want) / (jnp.abs(want) + 1.0))) < 2**-7


def test_the_route_is_the_backend_s_and_the_shape_s_and_is_counted(monkeypatch):
    x, dt, a_log, b, c, d = _inputs(B=1, T=8)
    assert sscan.selective_scan_route(x, a_log) == "plain"  # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(sscan, "mosaic_can_lower", lambda: True)
    assert sscan.selective_scan_route(x, a_log) == "kernel"
    assert sscan.selective_scan_route(x[..., :1000], a_log[:1000]) == "plain"  # no whole block of channels
    assert sscan.selective_scan_route(x, jnp.zeros((1024, 64))) == "plain"  # too large a state to unroll
    monkeypatch.setattr(sscan, "mosaic_can_lower", lambda: False)
    assert sscan.selective_scan_route(x, a_log) == "plain"
    monkeypatch.undo()
    reg = reglib.get_registry()
    before = [reg.counter(k).value for k in (reglib.SSCAN_ROUTE_KERNEL, reglib.SSCAN_ROUTE_PLAIN)]
    got = sscan.selective_scan(x, dt, a_log, b, c, d, chunk=4)
    after = [reg.counter(k).value for k in (reglib.SSCAN_ROUTE_KERNEL, reglib.SSCAN_ROUTE_PLAIN)]
    assert [a - b for a, b in zip(after, before)] == [0, 1]
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(sscan.recurrent_selective_scan(x, dt, a_log, b, c, d)), atol=2e-5
    )
    np.testing.assert_allclose(  # without the skip
        np.asarray(sscan.selective_scan(x, dt, a_log, b, c, chunk=4)),
        np.asarray(got - d * x), atol=2e-5,
    )


@pytest.mark.parametrize("wrong", ["dt", "a_log", "b", "d_skip"])
def test_shapes_that_do_not_fit_are_refused(wrong):
    x, dt, a_log, b, c, d = _inputs(B=1, T=8, D=16, N=4)
    args = dict(x=x, dt=dt, a_log=a_log, b=b, c=c, d_skip=d)
    args[wrong] = args[wrong][..., :-1]
    with pytest.raises(ValueError, match="selective_scan wants"):
        sscan.selective_scan(*args.values())
