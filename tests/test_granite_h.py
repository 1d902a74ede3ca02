"""What granite-4.0-h-micro brought to the program, at a small size on the
CPU in float32: the chunk-wise state-space dual scan of Mamba-2 against
its recurrence (decays of the model's range, near 0 and near 1, a length
the chunk does not divide), the Mamba-2 mixer against a hand-written one,
Granite's four scalars against a hand-written block, the tied head (no
``head`` leaf, the fused and the plain loss agree, the embedding's
gradient is the sum of both uses), grouped key/value heads on the fused
attention route, and the refusals.  ``fit`` through the program config is
a case of ``tests/test_lm_fit_smoke.py``; the command line's way in is here.

The plain reference's side of it (logits, loss, every gradient) is
``tests/benchmark/test_bench_reference_granite_h.py``.
"""

import json
import os
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.models import get_model, mixers
from distributed_tensorflow_models_tpu.models import transformer_lm as tlm
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import ssm
from distributed_tensorflow_models_tpu.telemetry import registry as reglib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL = get_config("granite_h_micro").model_kwargs
SMALL = {
    **FULL,
    "vocab_size": 97, "num_layers": 4,
    "layer_mixers": ("ssm", "ssm", "attention", "ssm"),
    "num_heads": 4, "num_kv_heads": 2, "d_model": 64, "d_ff": 96, "max_len": 40,
    "ssm_num_heads": 4, "ssm_head_dim": 8, "ssm_state_dim": 16, "ssm_chunk": 16,
    "dtype": jnp.float32,
}


# --- the chunk-wise state-space scan ----------------------------------------

def _ssd_inputs(seed, T, decay, B=2, H=3, P=8, N=16, G=0):
    """``G`` 0: ``B`` and ``C`` ``[B, T, N]``, Granite's call; else ``[B,
    T, G, N]``, a vector a group of ``H / G`` heads."""
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
    b, c = (jax.random.normal(k, (B, T, G, N) if G else (B, T, N)) for k in ks[3:5])
    d_skip = 1.0 + 0.1 * jax.random.normal(ks[5], (H,))
    return x, dt, a_log, b, c, d_skip


# chunk, length, decay, groups of heads (0: one, B and C without the axis;
# three heads, or with two groups four).
SSD_CASES = [
    (64, 128, "model", 0), (64, 150, "model", 0), (16, 150, "model", 0), (256, 300, "model", 0),
    (64, 150, "near_0", 0), (16, 40, "near_0", 0), (64, 150, "near_1", 0), (64, 10, "model", 0),
    (16, 40, "model", 2), (16, 40, "near_0", 2),
]


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("chunk,T,decay,groups", SSD_CASES)
def test_chunked_ssd_is_the_recurrence(chunk, T, decay, groups, what):
    """``chunked_ssd`` against the recurrence token by token, float32 at
    ``highest``: whole chunks and a length the chunk does not divide, a
    sequence shorter than a chunk, decays near 0 (every exponent taken is
    of a difference <= 0, so nothing overflows) and near 1, and ``B`` and
    ``C`` a group of heads (Nemotron 3 Nano's; Granite's one group is the
    call without the axis)."""
    args = _ssd_inputs(1, T, decay, H=4 if groups else 3, G=groups)
    if decay == "near_0":
        G = np.cumsum(np.asarray(-jnp.exp(args[2]) * args[1], np.float64), axis=1)
        assert np.exp(-G[:, min(chunk, T) - 1]).max() > 1e38  # exp(-G) alone is beyond float32
    with jax.default_matmul_precision("highest"):
        # Jitted here and below: one compile a side, where op by op is some hundred.
        if what == "forward":
            got = jax.jit(lambda *a: ssm.chunked_ssd(*a, chunk=chunk))(*args)
            want = jax.jit(ssm.recurrent_ssd)(*args)
            assert got.shape == want.shape and bool(jnp.all(jnp.isfinite(got)))
            scale = float(jnp.abs(want).max())
            assert float(jnp.abs(got - want).max()) <= 2e-5 * scale
            return
        loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
        got = jax.jit(jax.grad(loss(lambda *a: ssm.chunked_ssd(*a, chunk=chunk)), argnums=range(6)))(*args)
        want = jax.jit(jax.grad(loss(ssm.recurrent_ssd), argnums=range(6)))(*args)
    for name, g, w in zip(("x", "dt", "a_log", "b", "c", "d_skip"), got, want):
        assert bool(jnp.all(jnp.isfinite(g))), name
        g, w = np.asarray(g, np.float64), np.asarray(w, np.float64)
        # The norm of the leaf's gradient, with room where it is itself tiny
        # (dt large: the decay's gradient is a difference of vanishing terms).
        assert np.linalg.norm(g - w) <= 2e-4 * np.linalg.norm(w) + 1e-6, name


def test_ssd_is_linear_attention_without_the_delta_rule():
    """In the ops' terms: queries ``C``, keys ``B``, values ``dt x``, log
    decay ``-exp(A_log) dt``, scale 1: ``recurrent_kda`` with ``beta`` 0
    never writes, so the delta rule cannot be its oracle; with the write
    in hand it is a running sum of decayed outer products."""
    x, dt, a_log, b, c, _ = _ssd_inputs(2, 40, "model")
    got = ssm.recurrent_ssd(x, dt, a_log, b, c)
    g = np.asarray(-jnp.exp(a_log) * dt, np.float64)  # [B, T, H]
    v = np.asarray(dt[..., None] * x, np.float64)
    bb, cc = np.asarray(b, np.float64), np.asarray(c, np.float64)
    G = np.cumsum(g, axis=1)
    want = np.zeros(v.shape)
    for t in range(40):
        for s in range(t + 1):
            w = np.exp(G[:, t] - G[:, s]) * np.einsum("bn,bn->b", cc[:, t], bb[:, s])[:, None]
            want[:, t] += w[..., None] * v[:, s]
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4, rtol=1e-4)


def test_ssd_refuses_groups_that_do_not_divide_the_heads_by_shape():
    x, dt, a_log, b, c, d = _ssd_inputs(3, 32, "model")
    per_group = lambda y, groups: jnp.broadcast_to(y[:, :, None], (2, 32, groups, 16))
    with pytest.raises(ValueError, match="G dividing H"):
        ssm.chunked_ssd(x, dt, a_log, per_group(b, 2), per_group(c, 2), d)  # three heads
    with pytest.raises(ValueError, match="G dividing H"):
        ssm.chunked_ssd(x, dt, a_log, per_group(b, 3), c, d)  # B with groups, C without
    with pytest.raises(ValueError, match="one group"):
        ssm.chunked_ssd(x, dt[..., None], a_log, b, c, d)


@pytest.mark.parametrize("route", [ssm.recurrent_ssd, ssm.plain_ssd], ids=["recurrence", "chunk_wise"])
def test_a_scan_with_groups_is_one_scan_a_group_over_that_group_s_heads(route):
    """Head ``h`` reads group ``h // (H / G)`` and nothing of another
    group: the scan over six heads in three groups is three one-group
    scans, each over its group's two heads with its group's ``B`` and
    ``C`` (the call without the group axis), side by side."""
    x, dt, a_log, b, c, d = _ssd_inputs(6, 40, "model", H=6, G=3)
    f = route if route is ssm.recurrent_ssd else lambda *a: route(*a, chunk=16)
    with jax.default_matmul_precision("highest"):
        got = f(x, dt, a_log, b, c, d)
        heads = lambda g: slice(2 * g, 2 * g + 2)
        want = jnp.concatenate(
            [
                f(x[:, :, heads(g)], dt[:, :, heads(g)], a_log[heads(g)], b[:, :, g], c[:, :, g], d[heads(g)])
                for g in range(3)
            ],
            axis=2,
        )
        other = f(x, dt, a_log, b[:, :, ::-1], c, d)  # another group's B: another function
    assert float(jnp.abs(got - want).max()) <= 1e-5 * float(jnp.abs(want).max())
    assert float(jnp.abs(got - other).max()) > 1e-2 * float(jnp.abs(want).max())


def _ssd_traced_calls():
    return reglib.get_registry().counter(reglib.SSD_ROUTE_PLAIN).value


def test_the_route_is_counted_once_per_traced_call_and_the_body_traced_once():
    args = _ssd_inputs(4, 64, "model")
    f = jax.jit(lambda *a: ssm.chunked_ssd(*a, chunk=32) + ssm.chunked_ssd(*a, chunk=32))
    before = _ssd_traced_calls()
    f(*args)
    f(*args)  # the second call traces nothing
    assert _ssd_traced_calls() - before == 2
    text = f.lower(*args).as_text(debug_info=True)
    assert "ssd_core" in text and "gdn_core" not in text
    # Two calls, one lowered body: the chunk-wise form is bound under jit.
    assert text.count("func.func private @plain_ssd") == 1 and text.count("call @plain_ssd") == 2


# --- the Mamba-2 mixer ------------------------------------------------------

def _moved(params, seed=5):
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(tree, [x + 0.1 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _hand_mamba(p, h, H, P, N, eps=1e-5, G=1):
    """The layer as ISSUE 38 writes it (``G`` 1) and as ISSUE 40 does
    (``B`` and ``C`` a group of ``H / G`` heads, the gated norm over each
    group's channels), the recurrence token by token."""
    B, T, _ = h.shape
    inner = H * P
    zxbcdt = h @ p["in_proj"]["kernel"]
    z, xbc, dt = zxbcdt[..., :inner], zxbcdt[..., inner : 2 * inner + 2 * G * N], zxbcdt[..., -H:]
    padded = jnp.pad(xbc, ((0, 0), (3, 0), (0, 0)))
    conv = sum(padded[:, j : j + T] * p["conv"][j] for j in range(4)) + p["conv_bias"]
    xbc = jax.nn.silu(conv)
    x, b, c = xbc[..., :inner], xbc[..., inner : inner + G * N], xbc[..., inner + G * N :]
    x = x.reshape(B, T, H, P)
    # A head reads its group's vector.
    b, c = (jnp.repeat(y.reshape(B, T, G, N), H // G, axis=2) for y in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)
    S = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(T):
        S = a[:, t, :, None, None] * S + jnp.einsum("bhn,bhp->bhnp", b[:, t], dt[:, t, :, None] * x[:, t])
        ys.append(jnp.einsum("bhn,bhnp->bhp", c[:, t], S) + p["D"][:, None] * x[:, t])
    y = jnp.stack(ys, axis=1).reshape(B, T, inner) * jax.nn.silu(z)
    y = _rms(y.reshape(B, T, G, inner // G), 1.0, eps).reshape(B, T, inner) * p["norm"]["scale"]
    return y @ p["out_proj"]["kernel"]


@pytest.mark.parametrize("G", [1, 2], ids=["one_group", "two_groups"])
def test_the_mamba2_mixer_against_a_hand_written_layer(G):
    H, P, N, D = 4, 8, 16, 24
    mixer = mixers.Mamba2Mixer(
        num_heads=H, head_dim=P, state_dim=N, num_groups=G, d_model=D, chunk=16, dtype=jnp.float32
    )
    h = jax.random.normal(jax.random.key(3), (2, 40, D))
    params = _moved(jax.jit(mixer.init)(jax.random.key(0), h)["params"])
    assert sorted(params) == ["A_log", "D", "conv", "conv_bias", "dt_bias", "in_proj", "norm", "out_proj"]
    assert params["in_proj"]["kernel"].shape == (D, 2 * H * P + 2 * G * N + H)
    assert params["conv"].shape == (4, H * P + 2 * G * N) and params["conv_bias"].shape == (H * P + 2 * G * N,)
    assert params["norm"]["scale"].shape == (H * P,)  # one weight, whatever the groups of the mean square
    assert params["A_log"].shape == params["dt_bias"].shape == params["D"].shape == (H,)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: mixer.apply({"params": p}, h))(params)
        want = jax.jit(lambda p: _hand_mamba(p, h, H, P, N, G=G))(params)
        grads = jax.jit(jax.grad(lambda p: jnp.sum(jnp.sin(mixer.apply({"params": p}, h)))))(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))
    # The gate comes before the norm: gating after it is another function.
    fresh = jax.jit(mixer.init)(jax.random.key(0), h)["params"]
    assert bool(jnp.all(fresh["D"] == 1.0)) and float(jnp.abs(fresh["conv_bias"]).max()) <= 0.5


# --- the stack: four scalars, a tied head, grouped heads --------------------

@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputing_each_half"])
@pytest.mark.parametrize("mixer", ["attention", "ssm"])
def test_granite_block_against_a_hand_written_block(mixer, remat):
    """``u = h + 0.22 mixer(norm_a(h))``, ``h' = u + 0.22 ffn(norm_f(u))``;
    in the attention layer 4 query heads over 2 key/value heads, no
    positions, scores times 1/64."""
    D = 32
    block = tlm.Block(
        num_heads=4, d_model=D, d_ff=48, dropout_rate=0.0, dtype=jnp.float32, attn_impl="blockwise",
        attention_fn=None, num_kv_heads=2, norm="rmsnorm", norm_eps=1e-5, use_bias=False, mlp="gated_silu",
        mixer=mixer, remat=remat, attn_scale=0.015625, residual_multiplier=0.22,
        mixer_kwargs=(("num_heads", 4), ("head_dim", 8), ("state_dim", 16), ("chunk", 16)) if mixer == "ssm" else None,
    )
    x = jax.random.normal(jax.random.key(3), (2, 24, D))
    params = _moved(jax.jit(block.init)(jax.random.key(0), x)["params"])
    assert sorted(params) == sorted(["attn" if mixer == "attention" else "ssm", "ln1", "ln2", "mlp"])
    with jax.default_matmul_precision("highest"):
        got = block.apply({"params": params}, x)
        h = _rms(x, params["ln1"]["scale"])
        if mixer == "ssm":
            mixed = _hand_mamba(params["ssm"], h, 4, 8, 16)
        else:
            a = params["attn"]
            q = (h @ a["query"]["kernel"]).reshape(2, 24, 4, 8)
            k = jnp.repeat((h @ a["key"]["kernel"]).reshape(2, 24, 2, 8), 2, axis=2)
            v = jnp.repeat((h @ a["value"]["kernel"]).reshape(2, 24, 2, 8), 2, axis=2)
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 0.015625
            scores = jnp.where(jnp.tril(jnp.ones((24, 24), bool)), scores, -jnp.inf)
            mixed = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v).reshape(2, 24, 32) @ a["out"]["kernel"]
        u = x + 0.22 * mixed
        m = params["mlp"]
        f = _rms(u, params["ln2"]["scale"])
        want = u + 0.22 * ((jax.nn.silu(f @ m["gate"]["kernel"]) * (f @ m["up"]["kernel"])) @ m["down"]["kernel"])
        plain = block.clone(residual_multiplier=1.0, attn_scale=None).apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(plain - got).max()) > 1e-2


def test_the_four_scalars_against_a_hand_written_stack():
    """``h_0 = 12 E[tokens]``, the blocks, ``logits = E norm(h_L) / 8``
    with the same ``E``: the model against its own blocks applied by hand."""
    kw = {**SMALL, "num_layers": 2, "layer_mixers": ("ssm", "attention"), "remat": False}
    model = get_model("transformer_lm", **kw)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    params = _moved(jax.jit(model.init)(jax.random.key(0), tokens)["params"], seed=6)
    assert sorted(params) == ["blocks_0", "blocks_1", "embedding", "ln_f"]
    table = params["embedding"]["embedding"]
    logits = lambda m: jax.jit(lambda p: m.apply({"params": p}, tokens)[0])(params)

    @jax.jit
    def by_hand(params):
        x = 12.0 * table[tokens]
        for i, mixer in enumerate(kw["layer_mixers"]):
            block = tlm.Block(
                num_heads=4, d_model=64, d_ff=96, dropout_rate=0.0, dtype=jnp.float32, attn_impl="auto",
                attention_fn=None, num_kv_heads=2, norm="rmsnorm", norm_eps=1e-5, use_bias=False,
                mlp="gated_silu", mixer=mixer, attn_scale=0.015625, residual_multiplier=0.22,
                mixer_kwargs=(("num_heads", 4), ("head_dim", 8), ("state_dim", 16), ("conv_size", 4), ("chunk", 16))
                if mixer == "ssm" else None,
            )
            x = block.apply({"params": params[f"blocks_{i}"]}, x)
        normed = _rms(x, params["ln_f"]["scale"])
        return normed, (normed @ table.T) / 8.0

    with jax.default_matmul_precision("highest"):
        got = logits(model)
        hidden, _ = jax.jit(lambda p: model.apply({"params": p}, tokens, return_hidden=True))(params)
        normed, want = by_hand(params)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    # The fused head's hidden states carry the logit scale already.
    np.testing.assert_allclose(np.asarray(hidden), np.asarray(normed / 8.0), atol=1e-6, rtol=1e-6)
    for name, value in (("embedding_multiplier", 1.0), ("residual_multiplier", 1.0),
                        ("attention_multiplier", None), ("logits_scaling", 1.0)):
        other = logits(get_model("transformer_lm", **{**kw, name: value}))
        assert float(jnp.abs(other - got).max()) > 1e-4, name


def _losses(model, params, tokens, targets, fused):
    from distributed_tensorflow_models_tpu.core import train_loop

    state = types.SimpleNamespace(apply_fn=model.apply, carry=None)
    fn = train_loop.lm_loss_fn(model.apply, fused_unembed=fused)
    # Jitted: one compile, where op by op is some hundred.
    (loss, _), grads = jax.jit(
        lambda p: jax.value_and_grad(fn, has_aux=True)(p, state, {"inputs": tokens, "targets": targets}, {})
    )(params)
    return loss, grads


def test_the_tied_head_has_no_leaf_and_both_losses_agree():
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    model = get_model("transformer_lm", **SMALL)
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    targets = jnp.roll(tokens, -1, axis=1)
    params = _moved(jax.jit(model.init)(jax.random.key(0), tokens)["params"], seed=7)
    assert "head" not in params and params["embedding"]["embedding"].shape == (97, 64)
    untied = get_model("transformer_lm", **{**SMALL, "tie_embeddings": False})
    assert "head" in jax.eval_shape(lambda: untied.init(jax.random.key(0), tokens))["params"]
    with jax.default_matmul_precision("highest"):
        plain, g_plain = _losses(model, params, tokens, targets, fused=False)
        # The fused head multiplies in bfloat16 by default; in float32 it is
        # the same loss to rounding.
        hidden, _ = jax.jit(lambda p: model.apply({"params": p}, tokens, return_hidden=True))(params)
        table = params["embedding"]["embedding"]
        f32 = jax.jit(
            lambda h, k: losslib.fused_unembed_mean_xent(h, k, None, targets, compute_dtype=jnp.float32)
        )(hidden, table.T)
        fused, g_fused = _losses(model, params, tokens, targets, fused=True)
    assert float(f32) == pytest.approx(float(plain), rel=1e-6)
    assert float(fused) == pytest.approx(float(plain), rel=2e-3)  # bf16 products in the head alone
    assert jax.tree.structure(g_plain) == jax.tree.structure(g_fused) == jax.tree.structure(params)
    for a, b in zip(jax.tree.leaves(g_plain), jax.tree.leaves(g_fused)):
        assert float(jnp.linalg.norm(a - b)) <= 0.05 * float(jnp.linalg.norm(a)) + 1e-6


def test_the_embedding_s_gradient_is_the_sum_of_the_gather_s_and_the_head_s():
    """The tie: one matrix used twice.  With the two uses given a matrix
    each (an untied model holding ``E`` as its embedding and ``E^T`` as its
    head's kernel, everything else the same), the tied gradient is the
    embedding's plus the head's transposed."""
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    targets = jnp.roll(tokens, -1, axis=1)
    tied = get_model("transformer_lm", **SMALL)
    untied = get_model("transformer_lm", **{**SMALL, "tie_embeddings": False})
    params = _moved(jax.jit(tied.init)(jax.random.key(0), tokens)["params"], seed=8)
    table = params["embedding"]["embedding"]
    twice = {**params, "head": {"kernel": table.T}}
    for fused in (False, True):
        with jax.default_matmul_precision("highest"):
            loss, got = _losses(tied, params, tokens, targets, fused)
            loss2, parts = _losses(untied, twice, tokens, targets, fused)
        assert float(loss) == pytest.approx(float(loss2), rel=1e-6)
        want = parts["embedding"]["embedding"] + parts["head"]["kernel"].T
        gather, head = (float(jnp.linalg.norm(x)) for x in (parts["embedding"]["embedding"], parts["head"]["kernel"]))
        assert gather > 0 and head > 0  # both uses count
        err = float(jnp.linalg.norm(got["embedding"]["embedding"] - want)) / float(jnp.linalg.norm(want))
        assert err < 1e-5, (fused, err)


_FUSED = lambda q, k, v, scale=None: attnlib.fused_attention(q, k, v, True, scale, 128, 128, True)


def test_grouped_heads_take_the_fused_route_and_calls_without_groups_are_routed_as_before(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    spec = lambda heads, d=64, t=256: jax.ShapeDtypeStruct((1, t, heads, d), jnp.bfloat16)
    # The cell's call, 32 query heads of 64 over 8 key/value heads.
    big = lambda heads: jax.ShapeDtypeStruct((1, 8192, heads, 64), jnp.bfloat16)
    assert attnlib.auto_route(big(32), big(8), big(8)) == "fused"
    assert attnlib.fused_admissible(spec(4), spec(2), spec(2))
    assert attnlib.fused_admissible(spec(4), spec(1), spec(1))  # one key/value head for all
    # What the fused route still does not take, groups or not.
    assert not attnlib.fused_admissible(spec(4), spec(3), spec(3))  # no whole groups
    assert not attnlib.fused_admissible(spec(4), spec(2), spec(4))  # keys and values disagree
    assert attnlib.fused_admissible(spec(4), spec(2), spec(2), window=64)  # since PR 44, under the causal mask
    assert not attnlib.fused_admissible(spec(4), spec(2), spec(2), window=64, causal=False)
    assert not attnlib.fused_admissible(spec(3), spec(1), spec(1))  # half a lane block of queries
    assert not attnlib.fused_admissible(spec(4, t=200), spec(2, t=200), spec(2, t=200))
    # Without groups: as before.
    assert attnlib.fused_admissible(spec(4), spec(4), spec(4))
    assert attnlib.fused_admissible(spec(2, 128), spec(2, 128), spec(2, 128))
    assert not attnlib.fused_admissible(spec(4, 32), spec(4, 32), spec(4, 32))
    # The call: the kernels are handed equal head counts (K and V repeated
    # over their groups), and a call without groups repeats nothing.
    seen = []

    def fake(q, k, v, causal, scale, window=None, kept_as=None):
        seen.append((q.shape, k.shape, v.shape, scale))
        return q

    monkeypatch.setattr(attnlib, "fused_attention", fake)
    fused0 = reglib.get_registry().counter(reglib.ATTN_ROUTE_FUSED).value
    jaxpr = jax.make_jaxpr(lambda q, k, v: attnlib.attention(q, k, v, causal=True, scale=0.015625))(
        spec(4), spec(2), spec(2))
    plain = jax.make_jaxpr(lambda q, k, v: attnlib.attention(q, k, v, causal=True))(spec(4), spec(4), spec(4))
    assert seen == [((1, 256, 4, 64),) * 3 + (0.015625,), ((1, 256, 4, 64),) * 3 + (None,)]
    assert reglib.get_registry().counter(reglib.ATTN_ROUTE_FUSED).value - fused0 == 2
    assert len(jaxpr.eqns) > 0 and len(plain.eqns) == 0  # nothing is made for a call without groups


def test_grouped_heads_on_the_route_the_chip_takes_against_the_reference():
    """``attention(impl="auto")``'s fused route in interpret mode: keys and
    values repeated over their groups into the fused kernels, forward and
    backward (autodiff's sum over a group is their gradient), against
    ``reference_attention`` on the grouped arguments, at Granite's scale."""
    ks = jax.random.split(jax.random.key(0), 3)
    q = jax.random.normal(ks[0], (1, 256, 4, 64))
    k, v = (jax.random.normal(key, (1, 256, 2, 64)) for key in ks[1:])
    scale = 0.015625

    def route(q, k, v):
        k, v = attnlib._expand_kv(q, k, v)
        return _FUSED(q, k, v, scale)

    ref = lambda q, k, v: attnlib.reference_attention(q, k, v, causal=True, scale=scale)
    np.testing.assert_allclose(np.asarray(route(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    loss = lambda f: lambda *a: jnp.sum(jnp.sin(f(*a)))
    got = jax.grad(loss(route), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        assert g.shape == w.shape
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-5, rtol=2e-4)


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"decode": True}, "kda, gdn, mla and ssm mixers neither decode"),
        ({"decode": True}, "3-token tail of 4352 channels"),
        ({"attention_fn": lambda q, k, v, causal: q}, "ssm mixers neither decode nor take"),
        ({"layer_mixers": ("ssm", "ssm", "mamba", "attention")}, "unknown layer_mixers"),
        ({"use_bias": True}, "tie_embeddings shares the embedding matrix"),
        ({"layer_mixers": None, "pipelined": True}, "GPT-2 block only"),
        ({"layer_mixers": None, "norm": "layernorm", "norm_eps": None, "use_bias": True, "mlp": "gelu",
          "pos_encoding": "learned", "tie_embeddings": False, "pipelined": True}, "four multipliers"),
    ],
    ids=["ssm_decodes_not", "and_says_why", "no_attention_fn", "mixer", "tied_bias", "pipelined", "pipelined_scalars"],
)
def test_settings_the_stack_does_not_have_are_refused(kwargs, match):
    model = get_model("transformer_lm", **{**SMALL, **kwargs})
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32)))


def test_a_model_without_the_new_fields_traces_what_it_traced_before():
    """The four scalars at their defaults, no tie, no groups: no
    multiplication, no division and no transposed table is in the jaxpr
    (the lowered step of every other configuration is the parent's)."""
    kw = dict(vocab_size=97, num_layers=2, num_heads=4, d_model=64, d_ff=96, max_len=40, dropout_rate=0.0,
              dtype=jnp.float32)
    tokens = jnp.zeros((2, 16), jnp.int32)
    model = get_model("transformer_lm", **kw)
    params = jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))["params"]
    assert "head" in params
    text = lambda m: str(jax.make_jaxpr(lambda p: m.apply({"params": p}, tokens)[0])(params))
    base = text(model)
    assert base == text(get_model("transformer_lm", **kw, embedding_multiplier=1.0, residual_multiplier=1.0,
                                  attention_multiplier=None, logits_scaling=1.0, tie_embeddings=False))
    assert base != text(get_model("transformer_lm", **kw, residual_multiplier=0.5))


def test_recomputing_each_half_changes_no_value_and_no_leaf():
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    on = get_model("transformer_lm", **SMALL)
    off = get_model("transformer_lm", **{**SMALL, "remat": False})
    assert on.remat and not off.remat
    params = jax.jit(on.init)(jax.random.key(0), tokens)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(off.init, jax.random.key(0), tokens)["params"]
    )
    loss = lambda m: lambda p: jnp.sum(jnp.sin(m.apply({"params": p}, tokens)[0]))
    with jax.default_matmul_precision("highest"):
        # Jitted: one compile a side, where op by op is some hundred.
        (a, ga), (b, gb) = (jax.jit(jax.value_and_grad(loss(m)))(params) for m in (on, off))
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        assert float(jnp.abs(x - y).max()) <= 1e-5 * float(jnp.abs(y).max()) + 1e-7


def _count(kwargs):
    model = get_model("transformer_lm", **kwargs)
    tree = jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((1, 128), jnp.int32)))["params"]
    return tree, sum(x.size for x in jax.tree.leaves(tree))


def test_the_published_configuration_and_the_cut_count_what_the_issue_reckoned():
    assert [i + 1 for i, m in enumerate(FULL["layer_mixers"]) if m == "attention"] == [6, 16, 26, 36]
    assert set(FULL["layer_mixers"]) == {"ssm", "attention"} and len(FULL["layer_mixers"]) == 40
    tree, total = _count(FULL)
    per = lambda t: sum(x.size for x in jax.tree.leaves(t))
    # ISSUE 38's table: 25.85 M a Mamba-2 mixer, 10.49 M an attention, 50.33 M a feed-forward.
    assert per(tree["blocks_0"]["ssm"]) == 25_847_232
    assert per(tree["blocks_5"]["attn"]) == 10_485_760
    assert per(tree["blocks_0"]["mlp"]) == 50_331_648
    assert per(tree["blocks_0"]) == 76_182_976 and per(tree["blocks_5"]) == 60_821_504
    assert total == 36 * 76_182_976 + 4 * 60_821_504 + 100352 * 2048 + 2048
    assert 3.1e9 < total < 3.3e9  # "3B"
    with open(os.path.join(REPO, "benchmark", "configs", "granite_h_micro.json")) as f:
        cut = json.load(f)
    kw = {**cut["overrides"]["model_kwargs"], "layer_mixers": tuple(cut["overrides"]["model_kwargs"]["layer_mixers"])}
    tree, total = _count({**FULL, **kw})
    assert sorted(k for k in tree if "ssm" in tree[k]) == sorted(f"blocks_{i}" for i in (0, 1, 2, 3, 4, 6, 7, 8, 9))
    assert total == cut["parameters"]["count"] == 772_160_448  # x 16 B = 12.35 GB


def test_cli_train_runs_the_granite_h_program_config(tmp_path, monkeypatch, capsys):
    """``cli train --config granite_h_micro``: the entry point users type
    finds the configuration by its name and trains it (the registered
    configuration swapped for its small size: the command line has no
    option for a model's sizes, and 3.19 B parameters do not fit a test)."""
    from distributed_tensorflow_models_tpu.harness import cli
    from distributed_tensorflow_models_tpu.harness import config as configlib

    assert "granite_h_micro" in configlib.list_configs()
    kw = {k: v for k, v in SMALL.items() if k != "dtype"}
    small = get_config("granite_h_micro", model_kwargs=kw, vocab_size=97, num_steps=40, log_every_steps=2)
    monkeypatch.setitem(configlib._CONFIGS, "granite_h_micro", small)
    rc = cli.main([
        "train", "--config", "granite_h_micro", "--workdir", str(tmp_path), "--train-steps", "4",
        "--batch-size", "8",
    ])
    assert rc == 0
    assert "final_metrics" in capsys.readouterr().out
