"""What Olmo-Hybrid brought to the program, at a small size on the CPU in
float32: the chunk-wise gated delta rule (one decay a head, key and value
widths that differ, ``b`` up to 2) against its recurrence, a mixer and a
full attention that hold a share of a layer's heads (and the shares
adding up to the uncut layer), the norm on each sub-layer's output, RoPE
in the attention layers of a stack whose other layers take no positions.

The plain reference's side of it (logits, loss, every gradient) is
``tests/benchmark/test_bench_reference_olmo_hybrid.py``; ``fit`` through
the program config is a case of ``tests/test_lm_fit_smoke.py``.
"""

import functools
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.models import get_model, mixers
from distributed_tensorflow_models_tpu.models import transformer_lm as tlm
from distributed_tensorflow_models_tpu.ops import linear_attention as linattn
from distributed_tensorflow_models_tpu.telemetry import registry as reglib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL = get_config("olmo_hybrid").model_kwargs
SMALL = {
    **FULL,
    "vocab_size": 97, "num_layers": 4,
    "layer_mixers": ("gdn", "gdn", "gdn", "attention"),
    "num_heads": 3, "head_dim": 16, "d_model": 64, "d_ff": 96, "max_len": 40,
    "gdn_num_heads": 3, "gdn_key_dim": 12, "gdn_value_dim": 24,
    "dtype": jnp.float32,
}


def _reference():
    if REPO not in sys.path:
        sys.path.insert(0, REPO)
    from benchmark.lib import cells

    return cells.load_module("references", "olmo_hybrid")


# --- the chunk-wise gated delta rule ---------------------------------------

def _gdn_inputs(seed, T, decay, b_max, B=2, H=3, dk=12, dv=24):
    ks = jax.random.split(jax.random.key(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk)))
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    noise = jax.random.normal(ks[3], (B, T, H))
    g = {
        # what the mixer's fresh weights give: a_t about 0.9 .. 0.999
        "model": -jnp.exp(noise - 4.0),
        # many a_t below e^-20, some below e^-100: e^{-G} leaves float32
        # inside one chunk, the quotients do not
        "near_zero": -jnp.exp(1.5 * noise + 2.0),
        # both in one sequence, token by token
        "mixed": jnp.where(noise > 0, -jnp.exp(noise + 3.0), -jnp.exp(noise - 12.0)),
    }[decay]
    # b in (0, b_max), with a sixth of the tokens within 1e-3 of either end.
    beta = b_max * jax.nn.sigmoid(4.0 * jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


def _recurrence(q, k, v, g, beta):
    """The oracle: ``recurrent_kda`` with ``g`` spread over the key
    channels is the gated delta rule token by token."""
    return linattn.recurrent_kda(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)


GDN_CASES = [
    # chunk, sub, length, decay, largest b
    (64, 16, 128, "model", 2.0),
    (64, 16, 70, "near_zero", 2.0),   # a length the chunk does not divide
    (64, 16, 150, "mixed", 2.0),
    (64, 16, 128, "model", 1.0),      # the rule without negative eigenvalues
    (32, 8, 100, "near_zero", 2.0),
    (16, 16, 50, "mixed", 2.0),       # one block a chunk
    (128, 16, 130, "model", 2.0),
    (8, 8, 5, "mixed", 2.0),          # shorter than a chunk
]


def _probed(f, *x):
    """``(f(*x), its gradients by every argument)`` of a loss that weighs
    every output entry differently, as one jitted program: one compile
    where op by op is some hundred, the forward pass once for both."""
    w = jax.random.normal(jax.random.key(7), jax.eval_shape(f, *x).shape)
    loss = lambda *a: (lambda out: (jnp.sum(w * out), out))(f(*a))
    with jax.default_matmul_precision("highest"):
        (_, out), grads = jax.jit(jax.value_and_grad(loss, argnums=tuple(range(len(x))), has_aux=True))(*x)
    return out, grads


@functools.cache
def _chunked_and_recurrence(chunk, sub, T, decay, b_max):
    x = _gdn_inputs(chunk + T, T, decay, b_max)
    assert float(x[4].max()) > 0.99 * b_max and float(x[4].min()) < 0.01 * b_max
    return _probed(lambda *a: linattn.chunked_gdn(*a, chunk=chunk, sub=sub), *x), _probed(_recurrence, *x)


@pytest.mark.parametrize("what", ["forward", "gradient"])
@pytest.mark.parametrize("chunk,sub,T,decay,b_max", GDN_CASES)
def test_chunked_gated_delta_rule_is_the_recurrence(chunk, sub, T, decay, b_max, what):
    (got, got_grads), (want, want_grads) = _chunked_and_recurrence(chunk, sub, T, decay, b_max)
    if what == "forward":
        assert got.shape == want.shape == (2, T, 3, 24) and bool(jnp.isfinite(got).all())
        pairs = [(got, want)]
    else:
        pairs = list(zip(got_grads, want_grads, strict=True))
    for got, want in pairs:
        assert bool(jnp.isfinite(got).all())
        # float32 on both sides, sums in another order: 1e-4 of the largest
        # entry (the near-zero decays read 2e-5, the others 1e-6).
        scale = float(jnp.abs(want).max()) + 1e-30
        assert float(jnp.abs(got - want).max()) <= 1e-4 * scale


def test_the_scalar_decay_form_is_the_per_channel_form_with_the_decay_spread():
    """``plain_gdn`` against ``plain_kda`` given the same decay on every
    key channel: one algorithm, the pair loop replaced by a mask."""
    x = _gdn_inputs(3, 150, "mixed", 2.0)
    q, k, v, g, beta = x
    with jax.default_matmul_precision("highest"):
        got = jax.jit(linattn.plain_gdn)(*x)
        want = jax.jit(linattn.plain_kda)(q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)


def test_a_decay_per_channel_is_refused_by_shape():
    q, k, v, g, beta = _gdn_inputs(1, 64, "model", 2.0)
    # Traced, not run: both are refused by what the call shows.
    with pytest.raises((ValueError, TypeError)):
        jax.eval_shape(linattn.chunked_gdn, q, k, v, jnp.broadcast_to(g[..., None], q.shape), beta)
    with pytest.raises(ValueError, match="power-of-two"):
        jax.eval_shape(functools.partial(linattn.chunked_gdn, chunk=48, sub=16), q, k, v, g, beta)


def _gdn_traced_calls():
    return reglib.get_registry().counter(reglib.GDN_ROUTE_PLAIN).value


def test_the_route_is_counted_once_per_traced_call():
    x = _gdn_inputs(2, 64, "model", 2.0)
    f = jax.jit(linattn.chunked_gdn)
    before = _gdn_traced_calls()
    f(*x)
    f(*x)  # the second call traces nothing
    assert _gdn_traced_calls() - before == 1
    text = jax.jit(linattn.chunked_gdn).lower(*x).as_text(debug_info=True)
    assert "gdn_core" in text and "kda_core" not in text


# --- a mixer that holds a share of a layer's heads --------------------------

H_ALL, DK, DV, D = 6, 12, 24, 64


def _moved(params, seed=5):
    """Every leaf off its initial value (norm scales, ``A_log`` and
    ``dt_bias`` among them), so that a dropped term shows."""
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(
        tree, [x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)]
    )


def _gdn_half(p, first, count):
    """Heads ``first .. first + count`` of a delta-rule mixer's tree: the
    columns of every projection but ``out``, whose rows; the output
    norm's weight is every head's."""
    cols = lambda w, d: w.reshape(*w.shape[:-1], H_ALL, d)[..., first : first + count, :].reshape(
        *w.shape[:-1], count * d
    )
    return {
        "query": {"kernel": cols(p["query"]["kernel"], DK)},
        "key": {"kernel": cols(p["key"]["kernel"], DK)},
        "value": {"kernel": cols(p["value"]["kernel"], DV)},
        "gate": {"kernel": cols(p["gate"]["kernel"], DV)},
        "conv_query": cols(p["conv_query"], DK),
        "conv_key": cols(p["conv_key"], DK),
        "conv_value": cols(p["conv_value"], DV),
        "a": {"kernel": p["a"]["kernel"][:, first : first + count]},
        "beta": {"kernel": p["beta"]["kernel"][:, first : first + count]},
        "A_log": p["A_log"][first : first + count],
        "dt_bias": p["dt_bias"][first : first + count],
        "o_norm": p["o_norm"],
        "out": {"kernel": p["out"]["kernel"].reshape(H_ALL, DV, D)[first : first + count].reshape(count * DV, D)},
    }


def test_two_halves_of_the_delta_rule_mixer_add_up_to_the_uncut_layer():
    """One chip of 2 that share a layer's heads runs the mixer at its 3 of
    6 heads and gives a partial sum of ``W_o``: the two add up to the
    uncut layer, the program's and the reference's, with nothing reduced
    across the chips on the way."""
    mixer = lambda heads: mixers.GatedDeltaNetMixer(
        num_heads=heads, key_dim=DK, value_dim=DV, d_model=D, dtype=jnp.float32
    )
    x = jax.random.normal(jax.random.key(1), (2, 70, D))
    params = _moved(jax.jit(mixer(H_ALL).init)(jax.random.key(0), x)["params"])
    reference = jax.jit(lambda x, p: _reference().linear_attention(x, p, 1e-6))

    @jax.jit
    def whole_and_halves(params, x):
        with jax.default_matmul_precision("highest"):
            return mixer(H_ALL).apply({"params": params}, x), [
                mixer(3).apply({"params": _gdn_half(params, first, 3)}, x) for first in (0, 3)
            ]

    whole, halves = whole_and_halves(params, x)
    want = reference(x, params)
    assert float(jnp.abs(halves[0]).max()) > 1e-2 and float(jnp.abs(halves[1]).max()) > 1e-2
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1]), np.asarray(whole), atol=2e-6, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), np.asarray(want), atol=2e-5, rtol=1e-4)
    # And a half is the reference's half.
    np.testing.assert_allclose(
        np.asarray(halves[1]),
        np.asarray(reference(x, _gdn_half(params, 3, 3))),
        atol=2e-5, rtol=1e-4,
    )


class _ParentGatedDeltaNetMixer(mixers.nn.Module):
    """``GatedDeltaNetMixer`` as PR 32 committed it (``f685f19``), with the
    helper ``_short_conv_silu`` it shared with ``KDAMixer`` written out:
    PR 33 split that helper for ``KDAMixer``'s fused route, and this
    mixer has to run what it ran."""

    num_heads: int
    key_dim: int
    value_dim: int
    d_model: int
    conv_size: int = 4
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    def _short_conv_silu(self, x, name, heads, dim):
        nn = mixers.nn
        width, taps = heads * dim, self.conv_size
        weight = self.param(
            f"conv_{name}",
            lambda rng: jax.random.uniform(
                rng, (taps, width), jnp.float32, -(taps**-0.5), taps**-0.5
            ),
        )
        y = nn.Dense(width, dtype=self.dtype, use_bias=False, name=name)(x)
        y = mixers.causal_depthwise_conv(y, weight)
        return jax.nn.silu(y).reshape(*x.shape[:2], heads, dim)

    @mixers.nn.compact
    def __call__(self, x):
        nn = mixers.nn
        B, T, _ = x.shape
        H, dk, dv = self.num_heads, self.key_dim, self.value_dim
        dense = lambda features, name: nn.Dense(
            features, dtype=self.dtype, use_bias=False, name=name
        )
        q = mixers.l2norm(self._short_conv_silu(x, "query", H, dk)).astype(self.dtype)
        k = mixers.l2norm(self._short_conv_silu(x, "key", H, dk)).astype(self.dtype)
        v = self._short_conv_silu(x, "value", H, dv)
        a_log = self.param("A_log", mixers._a_log_init(H))
        dt_bias = self.param("dt_bias", mixers._dt_bias_init(H))
        per_head = lambda name: dense(H, name)(x).astype(jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(per_head("a") + dt_bias)
        beta = 2.0 * jax.nn.sigmoid(per_head("beta"))
        o = linattn.chunked_gdn(q, k, v, g, beta)
        gate = dense(H * dv, "gate")(x)
        o = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32, name="o_norm")(o)
        o = o * jax.nn.silu(gate.astype(jnp.float32).reshape(B, T, H, dv))
        return dense(self.d_model, "out")(o.astype(self.dtype).reshape(B, T, H * dv))


def test_the_delta_rule_mixer_runs_what_it_ran_before_kimi_s_mixer_was_fused(monkeypatch):
    """PR 33 gave ``KDAMixer`` a fused route and split the helper the two
    mixers share.  ``GatedDeltaNetMixer``'s heads are 96 and 192 channels,
    no lane blocks, and it stays on the plain code: at Olmo-Hybrid's
    widths, described as on the chip, its jaxpr (forward and gradient) is
    the parent's to the letter, and at a small size its output and every
    gradient are the parent's bit for bit."""
    # The cell's: 15 of the published 30 heads held.
    widths = {"num_heads": 15, "key_dim": 96, "value_dim": 192, "d_model": 3840}
    assert (FULL["gdn_num_heads"], FULL["gdn_key_dim"], FULL["gdn_value_dim"], FULL["d_model"]) == (30, 96, 192, 3840)

    def traced(cls, x, **kwargs):
        mixer = cls(**kwargs)
        params = jax.eval_shape(mixer.init, jax.random.key(0), x)
        loss = lambda p, x: jnp.sum(mixer.apply(p, x).astype(jnp.float32))
        return params, str(jax.make_jaxpr(jax.value_and_grad(loss, (0, 1)))(params, x))

    x = jax.ShapeDtypeStruct((1, 256, 3840), jnp.bfloat16)
    with monkeypatch.context() as on_the_chip:
        on_the_chip.setattr(jax, "default_backend", lambda: "tpu")
        on_the_chip.setattr(jax, "device_count", lambda: 1)
        params, now = traced(mixers.GatedDeltaNetMixer, x, **widths)
        parent_params, parent = traced(_ParentGatedDeltaNetMixer, x, **widths)
    assert jax.tree.structure(params) == jax.tree.structure(parent_params)
    assert now == parent and "pallas_call" not in now

    small = {"num_heads": 3, "key_dim": DK, "value_dim": DV, "d_model": D, "dtype": jnp.float32}
    x = jax.random.normal(jax.random.key(1), (2, 70, D))
    params = jax.jit(mixers.GatedDeltaNetMixer(**small).init)(jax.random.key(0), x)
    probe = jax.random.normal(jax.random.key(2), x.shape)
    both = lambda cls: jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(cls(**small).apply(p, x) * probe), (0, 1)
    ))(params, x)
    got, want = both(mixers.GatedDeltaNetMixer), both(_ParentGatedDeltaNetMixer)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


DH = 16


def _attention(heads):
    return tlm.SelfAttention(
        num_heads=heads, d_model=D, dtype=jnp.float32, attn_impl="blockwise", use_rope=True,
        rope_theta=500000.0, use_bias=False, qk_norm=True, norm_eps=1e-6, head_dim=DH,
    )


def _attention_half(p, first, count):
    cols = lambda w: w.reshape(*w.shape[:-1], H_ALL, DH)[..., first : first + count, :].reshape(
        *w.shape[:-1], count * DH
    )
    return {
        "query": {"kernel": cols(p["query"]["kernel"])},
        "key": {"kernel": cols(p["key"]["kernel"])},
        "value": {"kernel": cols(p["value"]["kernel"])},
        "q_norm": {"scale": cols(p["q_norm"]["scale"])},
        "k_norm": {"scale": cols(p["k_norm"]["scale"])},
        "out": {"kernel": p["out"]["kernel"].reshape(H_ALL, DH, D)[first : first + count].reshape(count * DH, D)},
    }


def test_two_halves_of_full_attention_add_up_given_the_uncut_norm_statistic():
    """Full attention's one quantity that is not per head: the mean square
    under the query and key RMSNorms, over the whole projection.  A
    deployment reduces it across the two chips (a scalar per token and
    projection); given the uncut layer's, the reference's halves add up to
    the uncut layer.  The program (and the configuration) norm over the
    held channels: a half of the program is the reference's half with its
    own statistic, and the uncut program the uncut reference."""
    ref = _reference()
    x = jax.random.normal(jax.random.key(2), (2, 40, D))
    params = _moved(jax.jit(_attention(H_ALL).init)(jax.random.key(0), x)["params"])

    @jax.jit
    def the_reference_s(params, x):
        whole = ref.full_attention(x, params, H_ALL, 1e-6, 500000.0)
        mean_square = lambda name: jnp.mean(
            jnp.square(jnp.matmul(x, params[name]["kernel"], precision="highest")), axis=-1, keepdims=True
        )
        stats = (mean_square("query"), mean_square("key"))
        halves = [
            ref.full_attention(x, _attention_half(params, first, 3), 3, 1e-6, 500000.0, qk_mean_squares=stats)
            for first in (0, 3)
        ]
        return whole, halves, ref.full_attention(x, _attention_half(params, 0, 3), 3, 1e-6, 500000.0)

    @jax.jit
    def the_program_s(params, x):
        with jax.default_matmul_precision("highest"):
            return (
                _attention(3).apply({"params": _attention_half(params, 0, 3)}, x),
                _attention(H_ALL).apply({"params": params}, x),
            )

    whole, halves, own = the_reference_s(params, x)
    np.testing.assert_allclose(np.asarray(halves[0] + halves[1]), np.asarray(whole), atol=2e-6, rtol=1e-5)
    assert float(jnp.abs(own - halves[0]).max()) > 1e-3  # the statistic matters
    got_half, got_whole = the_program_s(params, x)
    np.testing.assert_allclose(np.asarray(got_half), np.asarray(own), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(got_whole), np.asarray(whole), atol=2e-5, rtol=1e-4)


def test_self_attention_at_15_heads_of_128_in_a_width_of_3840():
    """The held share at published sizes: the projections are 3840 x 1920
    and back, the head size stays 128 (3840 / 15 would be 256)."""
    attn = tlm.SelfAttention(
        num_heads=15, d_model=3840, use_rope=True, use_bias=False, qk_norm=True, head_dim=128,
    )
    x = jax.ShapeDtypeStruct((1, 256, 3840), jnp.bfloat16)
    variables = jax.eval_shape(attn.init, jax.random.key(0), x)
    shapes = {k: jax.tree.leaves(v)[0].shape for k, v in variables["params"].items()}
    assert shapes == {
        "query": (3840, 1920), "key": (3840, 1920), "value": (3840, 1920), "out": (1920, 3840),
        "q_norm": (1920,), "k_norm": (1920,),
    }
    out = jax.eval_shape(attn.apply, variables, x)
    assert out.shape == (1, 256, 3840)
    # Without ``head_dim`` the block is the parent's: d_model / num_heads.
    plain = tlm.SelfAttention(num_heads=4, d_model=64)
    got = jax.eval_shape(plain.init, jax.random.key(0), jnp.zeros((1, 8, 64)))["params"]
    assert got["query"]["kernel"].shape == (64, 64) and got["out"]["kernel"].shape == (64, 64)


# --- the stack --------------------------------------------------------------

def _rms(x, scale, eps=1e-6):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "recomputing_each_half"])
def test_post_norm_block_against_a_hand_written_block(remat):
    """``h = x + norm_a(mixer(x))``, ``y = h + norm_f(ffn(h))``, the two
    halves recomputed or not."""
    block = tlm.Block(
        num_heads=3, d_model=D, d_ff=96, dropout_rate=0.0, dtype=jnp.float32, attn_impl="blockwise",
        attention_fn=None, use_rope=True, rope_theta=500000.0, norm="rmsnorm", norm_eps=1e-6,
        use_bias=False, qk_norm=True, head_dim=DH, norm_placement="post", mlp="gated_silu", remat=remat,
    )
    x = jax.random.normal(jax.random.key(3), (2, 24, D))
    params = _moved(jax.jit(block.init)(jax.random.key(0), x)["params"])
    assert sorted(params) == ["attn", "ln1", "ln2", "mlp"]

    @jax.jit
    def every_side(params, x):
        with jax.default_matmul_precision("highest"):
            loss = lambda p: (lambda y: (jnp.sum(jnp.sin(y)), y))(block.apply({"params": p}, x))
            (_, got), grads = jax.value_and_grad(loss, has_aux=True)(params)
            mixed = _attention(3).apply({"params": params["attn"]}, x)
            h = x + _rms(mixed, params["ln1"]["scale"])
            m = params["mlp"]
            ffn = (jax.nn.silu(h @ m["gate"]["kernel"]) * (h @ m["up"]["kernel"])) @ m["down"]["kernel"]
            want = h + _rms(ffn, params["ln2"]["scale"])
            # The pre-norm block on the same weights is another function.
            pre = block.clone(norm_placement="pre").apply({"params": params}, x)
        return got, grads, want, pre

    got, grads, want, pre = every_side(params, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5)
    assert float(jnp.abs(pre - got).max()) > 1e-2
    assert all(float(jnp.abs(g).max()) > 0 for g in jax.tree.leaves(grads))


def test_only_the_attention_layers_rotate():
    """``pos_encoding="rope"`` over ("gdn", "attention"): no position
    table, the delta-rule layer's output does not depend on the setting,
    the attention layer's does."""
    kw = {**SMALL, "num_layers": 2, "layer_mixers": ("gdn", "attention"), "remat": False}
    tokens = jax.random.randint(jax.random.key(1), (2, 40), 0, 97)
    rope = get_model("transformer_lm", **kw)
    none = get_model("transformer_lm", **{**kw, "pos_encoding": "none"})
    params = jax.jit(rope.init)(jax.random.key(0), tokens)["params"]
    assert "pos_embedding" not in params
    assert jax.tree.structure(params) == jax.tree.structure(
        jax.eval_shape(none.init, jax.random.key(0), tokens)["params"]
    )

    def blocks(m):
        """The two blocks' outputs."""
        captured = lambda p: m.apply(
            {"params": p}, tokens, capture_intermediates=lambda mdl, _: isinstance(mdl, tlm.Block),
            mutable=["intermediates"],
        )[1]["intermediates"]
        return jax.jit(lambda p: [captured(p)[f"blocks_{i}"]["__call__"][0] for i in (0, 1)])(params)

    (first_a, second_a), (first_b, second_b) = blocks(rope), blocks(none)
    np.testing.assert_array_equal(np.asarray(first_a), np.asarray(first_b))
    assert float(jnp.abs(second_a - second_b).max()) > 1e-3


@pytest.mark.parametrize(
    "kwargs,match",
    [
        ({"decode": True}, "kda, gdn, mla and ssm mixers neither decode"),
        ({"decode": True, "layer_mixers": None}, "norm_placement='post' does not decode"),
        ({"norm_placement": "sandwich"}, "unknown norm_placement"),
        ({"layer_mixers": ("gdn", "gdn", "gla", "attention")}, "unknown layer_mixers"),
        ({"layer_mixers": None, "pipelined": True}, "GPT-2 block only"),
        ({"layer_mixers": None, "norm": "layernorm", "norm_eps": None, "use_bias": True, "qk_norm": False,
          "mlp": "gelu", "norm_placement": "pre", "pipelined": True}, "GPT-2 block only"),  # head_dim alone
    ],
    ids=["gdn_decodes_not", "post_norm_decodes_not", "placement", "mixer", "pipelined_post", "pipelined_head_dim"],
)
def test_settings_the_stack_does_not_have_are_refused(kwargs, match):
    model = get_model("transformer_lm", **{**SMALL, **kwargs})
    with pytest.raises(ValueError, match=match):
        jax.eval_shape(lambda: model.init(jax.random.key(0), jnp.zeros((2, 16), jnp.int32)))


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
    assert FULL["layer_mixers"] == ("gdn", "gdn", "gdn", "attention") * 8
    assert [i + 1 for i, m in enumerate(FULL["layer_mixers"]) if m == "attention"] == list(range(4, 33, 4))
    tree, total = _count(FULL)
    per = lambda t: sum(x.size for x in jax.tree.leaves(t))
    # ISSUE 32's table: 88.75 M a delta-rule mixer, 58.99 M a full attention, 126.81 M a feed-forward.
    assert per(tree["blocks_0"]["linear_attn"]) == 88_750_332
    assert per(tree["blocks_3"]["attn"]) == 58_990_080
    assert per(tree["blocks_0"]["mlp"]) == 126_812_160
    assert total == 24 * (88_750_332 + 126_812_160 + 7680) + 8 * (58_990_080 + 126_812_160 + 7680) + 2 * 100352 * 3840 + 3840
    assert 7.4e9 < total < 7.5e9  # "7B"
    with open(os.path.join(REPO, "benchmark", "configs", "olmo_hybrid.json")) as f:
        cut = json.load(f)
    kw = {**cut["overrides"]["model_kwargs"], "layer_mixers": tuple(cut["overrides"]["model_kwargs"]["layer_mixers"])}
    tree, total = _count({**FULL, **kw})
    assert per(tree["blocks_0"]["linear_attn"]) == 44_375_262 and per(tree["blocks_3"]["attn"]) == 29_495_040
    assert total == cut["parameters"]["count"] == 766_241_946  # x 16 B = 12.26 GB


def test_olmo_hybrid_parameter_tree():
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = jax.eval_shape(lambda: get_model("transformer_lm", **SMALL).init(jax.random.key(0), tokens))["params"]
    assert sorted(params) == ["blocks_0", "blocks_1", "blocks_2", "blocks_3", "embedding", "head", "ln_f"]
    assert sorted(params["blocks_0"]) == ["linear_attn", "ln1", "ln2", "mlp"]
    assert sorted(params["blocks_3"]) == ["attn", "ln1", "ln2", "mlp"]
    assert sorted(params["blocks_0"]["linear_attn"]) == [
        "A_log", "a", "beta", "conv_key", "conv_query", "conv_value", "dt_bias", "gate", "key", "o_norm",
        "out", "query", "value",
    ]
    la = params["blocks_0"]["linear_attn"]
    assert la["dt_bias"].shape == la["A_log"].shape == (3,) and la["o_norm"]["scale"].shape == (24,)
    assert la["query"]["kernel"].shape == (64, 36) and la["gate"]["kernel"].shape == (64, 72)
    assert sorted(params["blocks_3"]["attn"]) == ["k_norm", "key", "out", "q_norm", "query", "value"]
    assert not any("bias" == str(p[-1].key) for p, _ in jax.tree_util.tree_leaves_with_path(params))
