"""PTB LSTM through the generic train loop: truncated-BPTT carry threading
(SURVEY.md §7.4.5) on the 8-fake-device mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import (
    sharding as shardlib,
    train_loop,
)
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.ops import optim

VOCAB, B, T = 50, 16, 8


def make_state(mesh, dropout=0.0):
    model = get_model(
        "ptb_lstm", config="small", vocab_size=VOCAB, dropout_rate=dropout
    )
    import optax

    # PTB recipe: clip-by-global-norm then SGD (SURVEY.md §2.1 R8).
    tx = optax.chain(optim.clip_by_global_norm(5.0), optim.sgd(0.5))
    tokens = jnp.zeros((B, T), jnp.int32)
    state = TrainState.create(
        model,
        tx,
        jax.random.key(0),
        tokens,
        carry=model.initial_carry(B),
    )
    return model, train_loop.place_state(state, mesh)


def make_batch(seed=0):
    rng = np.random.RandomState(seed)
    seq = rng.randint(0, VOCAB, (B, T + 1))
    return {"inputs": seq[:, :-1], "targets": seq[:, 1:]}


def test_lm_loss_decreases_and_carry_updates(mesh8):
    model, state = make_state(mesh8)
    step = train_loop.make_train_step(train_loop.lm_loss_fn(model.apply))
    batch = shardlib.shard_batch(mesh8, make_batch())
    rng = jax.random.key(0)
    carry0 = jax.tree.map(np.asarray, state.carry)
    losses = []
    for _ in range(15):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0], losses
    # carry must have been threaded (non-zero after steps)
    carry1 = jax.tree.map(np.asarray, state.carry)
    diffs = [
        np.abs(a - b).max()
        for a, b in zip(jax.tree.leaves(carry0), jax.tree.leaves(carry1))
    ]
    assert max(diffs) > 0
    # perplexity = exp(nll) sane: below vocab-uniform after training
    assert np.exp(losses[-1]) < VOCAB


def test_carry_is_data_sharded(mesh8):
    from distributed_tensorflow_models_tpu.core.mesh import AxisNames

    model, state = make_state(mesh8)
    for leaf in jax.tree.leaves(state.carry):
        assert leaf.sharding.spec[0] == AxisNames.DATA


# ------------------------------------------------- fused unembed + xent


def test_chunked_unembed_xent_exact_in_f32():
    """compute_dtype=f32: fused == two-stage head + xent to float
    round-off, values and all grads, including a non-dividing chunk."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    rng = np.random.RandomState(0)
    Bc, Tc, d, V = 2, 7, 16, 33  # B*T=14, chunk 4 -> padded tail
    hidden = jnp.asarray(rng.randn(Bc, Tc, d).astype(np.float32))
    kernel = jnp.asarray(rng.randn(d, V).astype(np.float32) * 0.1)
    bias = jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
    targets = jnp.asarray(rng.randint(0, V, (Bc, Tc)))

    def ref(h, k, b):
        logits = h.reshape(-1, d) @ k + b
        return jnp.mean(
            losslib.softmax_cross_entropy(logits, targets.reshape(-1))
        )

    def fused(h, k, b):
        return jnp.mean(
            losslib.chunked_unembed_xent(
                h, k, b, targets, chunk_rows=4,
                compute_dtype=jnp.float32,
            )
        )

    np.testing.assert_allclose(
        fused(hidden, kernel, bias), ref(hidden, kernel, bias),
        rtol=1e-6, atol=1e-6,
    )
    g_ref = jax.grad(ref, argnums=(0, 1, 2))(hidden, kernel, bias)
    g_fus = jax.grad(fused, argnums=(0, 1, 2))(hidden, kernel, bias)
    for a, b_ in zip(g_ref, g_fus):
        np.testing.assert_allclose(a, b_, rtol=1e-5, atol=1e-6)


def _head_case(with_bias, rows=(2, 7), d=16, V=33, seed=0):
    rng = np.random.RandomState(seed)
    Bc, Tc = rows
    hidden = jnp.asarray(rng.randn(Bc, Tc, d).astype(np.float32))
    kernel = jnp.asarray(rng.randn(d, V).astype(np.float32) * 0.1)
    bias = (
        jnp.asarray(rng.randn(V).astype(np.float32) * 0.1)
        if with_bias else None
    )
    targets = jnp.asarray(rng.randint(0, V, (Bc, Tc)))
    return hidden, kernel, bias, targets


def _value_and_grads(loss, hidden, kernel, bias):
    """(value, dhidden, dkernel[, dbias]) of ``loss(hidden, kernel, bias)``."""
    argnums = (0, 1) if bias is None else (0, 1, 2)
    value, grads = jax.value_and_grad(loss, argnums=argnums)(
        hidden, kernel, bias
    )
    return (value, *grads)


def _normalized_err(got, want):
    # chip_smoke.py's measure: the largest difference over the
    # reference's largest magnitude.
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("cotangent", [1.0, 0.37])
@pytest.mark.parametrize("chunk_rows", [7, 4], ids=["divides", "padded"])
@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
def test_fused_unembed_mean_xent_exact_in_f32(
    with_bias, chunk_rows, cotangent
):
    """In f32 the gradient-in-forward head equals the mean of the
    per-token op and the two-stage head to round-off: value, dhidden,
    dkernel, dbias; B*T=14, so chunk 4 pads its tail."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    hidden, kernel, bias, targets = _head_case(with_bias)

    def new(h, k, b):
        return cotangent * losslib.fused_unembed_mean_xent(
            h, k, b, targets, chunk_rows=chunk_rows,
            compute_dtype=jnp.float32,
        )

    def per_token(h, k, b):
        return cotangent * jnp.mean(
            losslib.chunked_unembed_xent(
                h, k, b, targets, chunk_rows=chunk_rows,
                compute_dtype=jnp.float32,
            )
        )

    def two_stage(h, k, b):
        logits = h.reshape(-1, h.shape[-1]) @ k
        if b is not None:
            logits = logits + b
        return cotangent * jnp.mean(
            losslib.softmax_cross_entropy(logits, targets.reshape(-1))
        )

    got = _value_and_grads(new, hidden, kernel, bias)
    # The undifferentiated call (evaluation) is the same value.
    np.testing.assert_allclose(
        new(hidden, kernel, bias), got[0], rtol=1e-6, atol=1e-6
    )
    for ref in (per_token, two_stage):
        want = _value_and_grads(ref, hidden, kernel, bias)
        assert len(got) == len(want) == (4 if with_bias else 3)
        for g, w in zip(got, want):
            assert g.shape == w.shape and g.dtype == w.dtype
            np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def _vocab_products(jaxpr, V):
    """``dot_general``s with a vocabulary-sized operand or result, at any
    depth of the jaxpr."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and any(
            V in v.aval.shape for v in (*eqn.invars, *eqn.outvars)
        ):
            count += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _vocab_products(sub, V)
    return count


@pytest.mark.parametrize("chunk_rows", [14, 7, 4])
def test_fused_unembed_mean_xent_products_per_chunk(chunk_rows):
    """Three vocabulary-sized products a chunk under differentiation
    (logits, dlogits . W^T, x^T . dlogits: no recomputed forward), one
    when only the value is wanted; the per-token op under autodiff
    holds four."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    hidden, kernel, bias, targets = _head_case(True)
    V = kernel.shape[1]
    chunks = -(-targets.size // chunk_rows)

    def new(h, k, b):
        return losslib.fused_unembed_mean_xent(
            h, k, b, targets, chunk_rows=chunk_rows
        )

    def per_token(h, k, b):
        return jnp.mean(
            losslib.chunked_unembed_xent(
                h, k, b, targets, chunk_rows=chunk_rows
            )
        )

    def products(fn):
        return _vocab_products(
            jax.make_jaxpr(fn)(hidden, kernel, bias).jaxpr, V
        )

    grad = lambda f: jax.value_and_grad(f, argnums=(0, 1, 2))
    assert products(new) == chunks
    assert products(grad(new)) == 3 * chunks
    assert products(grad(per_token)) == 4 * chunks


@pytest.mark.parametrize("with_bias", [True, False], ids=["bias", "nobias"])
def test_fused_unembed_mean_xent_bf16_products_near_f32(with_bias):
    """bf16 products (f32 accumulation, f32 softmax) stay within
    chip_smoke.py's KERNEL_TOL of the f32 op, value and gradients."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    hidden, kernel, bias, targets = _head_case(
        with_bias, rows=(4, 32), d=64, V=257, seed=3
    )

    def at(dtype):
        return _value_and_grads(
            lambda h, k, b: losslib.fused_unembed_mean_xent(
                h, k, b, targets, chunk_rows=48, compute_dtype=dtype
            ),
            hidden, kernel, bias,
        )

    got, want = at(jnp.bfloat16), at(jnp.float32)
    assert all(g.dtype == jnp.float32 for g in got)
    errs = [_normalized_err(g, w) for g, w in zip(got, want)]
    assert 0.0 < max(errs) <= 2e-2, errs


@pytest.mark.parametrize("chunk_rows", [128, 48, 7])
def test_fused_unembed_independent_of_chunk_rows(chunk_rows):
    """The chunk is the code's choice (4,096 rows, clamped to B*T), not
    a knob: loss and gradients, bf16 products as ``fit`` runs them, do
    not depend on it."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    assert losslib.UNEMBED_CHUNK_ROWS == 4096
    hidden, kernel, bias, targets = _head_case(
        True, rows=(8, 16), d=32, V=50, seed=5
    )

    def at(**kw):
        return _value_and_grads(
            lambda h, k, b: losslib.fused_unembed_mean_xent(
                h, k, b, targets, **kw
            ),
            hidden, kernel, bias,
        )

    want = at()  # the default: one chunk of the 128 rows there are
    for g, w in zip(at(chunk_rows=chunk_rows), want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


def test_unembed_grad_in_forward_counter():
    """``unembed/grad_in_forward`` counts traced differentiated calls of
    the fused head, and nothing else."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib
    from distributed_tensorflow_models_tpu.telemetry import (
        registry as reglib,
    )

    hidden, kernel, bias, targets = _head_case(True)
    counter = reglib.get_registry().counter(reglib.UNEMBED_GRAD_IN_FORWARD)
    new = lambda h, k, b: losslib.fused_unembed_mean_xent(
        h, k, b, targets, chunk_rows=4
    )
    start = counter.value
    new(hidden, kernel, bias)  # evaluation: no gradient work
    jax.jit(new)(hidden, kernel, bias)
    jax.grad(
        lambda h: jnp.mean(
            losslib.chunked_unembed_xent(h, kernel, bias, targets)
        )
    )(hidden)
    logits = hidden.reshape(-1, hidden.shape[-1]) @ kernel + bias
    jax.grad(
        lambda lg: jnp.mean(losslib.token_xent(lg, targets.reshape(-1)))
    )(logits)
    assert counter.value == start
    step = jax.jit(jax.value_and_grad(new, argnums=(0, 1, 2)))
    step(hidden, kernel, bias)
    assert counter.value == start + 1
    step(hidden, kernel, bias)  # compiled: not traced again
    assert counter.value == start + 1
    jax.grad(new)(hidden, kernel, bias)
    assert counter.value == start + 2


def test_chunked_unembed_xent_no_bias():
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    rng = np.random.RandomState(1)
    hidden = jnp.asarray(rng.randn(2, 8, 16).astype(np.float32))
    kernel = jnp.asarray(rng.randn(16, 20).astype(np.float32) * 0.1)
    targets = jnp.asarray(rng.randint(0, 20, (2, 8)))
    logits = hidden.reshape(-1, 16) @ kernel
    ref = losslib.softmax_cross_entropy(logits, targets.reshape(-1))
    out = losslib.chunked_unembed_xent(
        hidden, kernel, None, targets, chunk_rows=8,
        compute_dtype=jnp.float32,
    )
    np.testing.assert_allclose(
        out.reshape(-1), ref, rtol=1e-6, atol=1e-6
    )


def test_fused_unembed_fit_matches_two_stage(mesh8, tmp_path):
    """fused_unembed through fit: same trajectory as the two-stage head
    within bf16-matmul tolerance (the fused path's only numeric change is
    the bf16 MXU projection with f32 accumulation)."""
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    kwargs = dict(
        model_kwargs={
            "num_layers": 2, "num_heads": 4, "d_model": 64,
            "d_ff": 128, "max_len": 32, "dropout_rate": 0.0,
        },
        num_steps=32,
        global_batch_size=8,
        train_steps=3,
        log_every_steps=1,
        checkpoint_every_secs=1e9,
    )
    # Explicit False: the transformer_lm family defaults fused, and a
    # defaulted "plain" arm would silently compare fused vs fused.
    res_plain = trainlib.fit(
        get_config("transformer_lm", fused_unembed=False, **kwargs),
        str(tmp_path / "plain"), mesh=mesh8,
    )
    res_fused = trainlib.fit(
        get_config("transformer_lm", fused_unembed=True, **kwargs),
        str(tmp_path / "fused"), mesh=mesh8,
    )
    assert (
        abs(
            res_fused.final_metrics["loss"]
            - res_plain.final_metrics["loss"]
        )
        < 5e-2
    )


def test_fused_unembed_rejects_model_without_hidden_path():
    import pytest

    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    # Both shipped LM models support return_hidden; fake a future one
    # that doesn't — the guard must fire before tracing produces an
    # opaque TypeError deep inside jit.
    cfg = get_config("ptb_small", fused_unembed=True).replace(
        model="some_new_lm"
    )
    with pytest.raises(ValueError, match="fused_unembed"):
        trainlib.build_lm_loss(cfg, apply_fn=None)


def test_ptb_bf16_fused_fit_trains(mesh8, tmp_path):
    """bf16 compute + f32 cell state + fused head through fit: loss must
    fall on the learnable synthetic PTB stream (not just stay finite) —
    the mixed-precision recipe has to actually train."""
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    cfg = get_config(
        "ptb_small",
        model_kwargs={"config": "small", "dtype": jnp.bfloat16},
        fused_unembed=True,
        global_batch_size=16,
        num_steps=8,
        train_steps=30,
        log_every_steps=10,
        checkpoint_every_secs=1e9,
    )
    res = trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    assert res.steps_run == 30
    last = res.final_metrics["loss"]
    # Starts at ~ln(10000)=9.21 on the synthetic Zipfian stream; 30 SGD
    # steps must make real progress, not just stay finite.
    assert np.isfinite(last) and last < 8.5, last
