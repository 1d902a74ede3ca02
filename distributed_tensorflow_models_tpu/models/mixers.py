"""Token mixers beside full attention, for stacks whose layers differ:
two delta rules, a latent attention, two state-space layers and a unit
that reads an earlier layer's scan.

Kimi Linear (Kimi Linear technical report, Moonshot AI 2025,
arXiv:2510.26692; ``config.json`` and the published modelling code of
moonshotai/Kimi-Linear-48B-A3B-Instruct) alternates two mixers, three to
one:

- :class:`KDAMixer`: Kimi Delta Attention, a gated delta-rule linear
  attention with a decay per channel.  Per head of ``head_dim``: ``q, k =
  l2norm(silu(conv(W x)))``, ``v = silu(conv(W_v x))`` (causal depthwise
  convolutions of ``conv_size``, no bias); the log decay ``g_t =
  -exp(A_log) * softplus(W_f2 W_f1 x + dt_bias)`` per channel (``A_log``
  per head), ``b_t = sigmoid(W_b x)`` per head; the state and the read of
  :mod:`...ops.linear_attention`; ``y = W_o (rmsnorm_head(o_t) *
  sigmoid(W_g2 W_g1 x))``.  The two low-rank gates go through
  ``head_dim`` channels.  On a TPU, for heads of whole lane blocks
  (``head_dim`` a multiple of 128), everything between the projections
  and the core and between the core and ``W_o`` runs as fused Pallas
  passes over the flat ``[B, T, H * head_dim]`` views the projections
  write and the core's kernels read
  (:func:`...ops.linear_attention.kda_prologue`, ``kda_epilogue``), so no
  ``[B, T, H, head_dim]`` array exists in HBM; elsewhere plain
  ``jax.numpy`` on that view.  Same parameters, same mathematics.
- :class:`LatentAttention`: multi-head latent attention without query
  compression and **without positions** (``mla_use_nope``): ``q = W_q x``
  (``nope_dim + rope_dim`` channels a head; the names are the config's,
  nothing is rotated), ``c, k_r = split(W_kva x)``, ``c = rmsnorm(c)``,
  ``k_n, v = split(W_kvb c)``, the key of a head ``[k_n, k_r]`` with
  ``k_r`` shared by the heads, causal softmax at ``(nope_dim +
  rope_dim)^-0.5``.  The core is :func:`...ops.attention.attention`,
  with more query/key channels than value channels.

Olmo-Hybrid (allenai/Olmo-Hybrid-7B ``config.json``: ``layer_types``,
the ``linear_*`` keys) alternates, three to one with rotary full
attention (``transformer_lm.SelfAttention``), a third:

- :class:`GatedDeltaNetMixer`: the gated delta rule (Yang, Kautz,
  Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464) with **one
  decay a head and step** and key and value widths that differ (96 and
  192).  Per head: ``q, k = l2norm(silu(conv(W x)))`` over ``key_dim``
  channels, ``v = silu(conv(W_v x))`` over ``value_dim``; ``g_t =
  -exp(A_log) * softplus(W_a x + dt_bias)`` and ``b_t = 2 sigmoid(W_b
  x)``, one number each a head (``b`` up to 2: ``linear_allow_neg_eigval``,
  the transition ``I - b k k^T`` then takes eigenvalues down to -1); the
  state ``[key_dim, value_dim]`` and the read of
  :func:`...ops.linear_attention.chunked_gdn` at ``key_dim^-0.5``; ``y =
  W_o (rmsnorm_head(o_t) * silu(W_g x))``, the gate full rank.
  ``num_heads`` is how many heads this program holds: every piece but
  ``W_o``'s sum is per head, so a chip that shares a layer's heads with
  others runs this module at its count and its output is its partial sum.

Granite 4.0-H (ibm-granite/granite-4.0-h-micro ``config.json``:
``layer_types``, the ``mamba_*`` keys; the ``granitemoehybrid`` Mamba
layer of transformers, which follows ``mamba_ssm``'s Mamba-2 block)
alternates, nine to one with full attention over grouped key/value heads,
a fourth:

- :class:`Mamba2Mixer`: the Mamba-2 state-space layer (Dao and Gu 2024,
  arXiv:2405.21060).  ``[z, xBC, dt] = W_in x`` (``d_inner``, ``d_inner +
  2 G N`` and ``H`` channels, in that order, ``d_inner = H P``); ``xBC =
  silu(conv(xBC) + b_conv)``, **one** causal depthwise convolution **with
  a bias** over ``x``, ``B`` and ``C`` together; ``dt = softplus(dt +
  dt_bias)`` and the decay ``exp(-exp(A_log) dt)``, one number a head
  and token; ``B`` and ``C`` ``[N]`` **one vector a group of heads**
  (``num_groups``: Granite's one group for all 64 heads, so ``xBC`` is
  ``d_inner + 2 N`` wide; Nemotron 3 Nano's eight, ``d_inner + 2 G N``,
  head ``h`` reading group ``h // (H / G)``); the state ``[N, P]`` a head
  and the read of :func:`...ops.ssm.chunked_ssd` with its ``D`` skip; ``y
  = W_out (w * rmsnorm(y * silu(z)))``, the gate **before** the norm and
  the mean square over all ``d_inner`` channels with one group, over each
  group's ``d_inner / G`` with more (the delta-rule mixers normalise per
  head and gate after).

Nemotron 3 Nano (nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16
``config.json``, ``model_type`` ``nemotron_h``: ``hybrid_override_pattern``,
``n_groups``) runs the same mixer with eight groups, in layers that are
this mixer alone (``transformer_lm``'s ``layer_mixers``).

Phi-4-mini-flash-reasoning (microsoft/Phi-4-mini-flash-reasoning
``config.json``, ``model_type`` ``phi4flash``; SambaY: Ren et al. 2025,
arXiv:2507.06607) is a self-decoder of Mamba-1 and attention layers
(Samba, arXiv:2406.07522) under a cross-decoder whose layers compute no
state of their own:

- :class:`Mamba1Mixer`: the Mamba-1 layer (Gu and Dao 2023,
  arXiv:2312.00752).  ``[x, z] = W_in u`` (``d_inner`` each); ``x =
  silu(conv(x) + b_conv)``; ``[r, B, C] = W_x x`` (``dt_rank``, ``N``,
  ``N``); ``dt = softplus(W_dt r + b_dt)`` a channel; ``A = -exp(A_log)``
  ``[d_inner, N]``, **a decay for every channel and state**; the scan of
  :func:`...ops.selective_scan.selective_scan` with its ``D`` skip; ``out
  = W_out (y * silu(z))``, no norm.  With ``hand_on`` it also returns
  ``y`` before the gate: the **memory** later layers read.
- :class:`GatedMemoryUnit`: ``out = W_out (m * silu(W_in u))`` with ``m``
  an earlier Mamba-1 layer's memory: a mixer without a scan, a
  convolution or a state, two projections around an element-wise gate.

All compute in ``dtype`` over float32 parameters; the norms, the decay,
``b_t``, ``dt``, the l2 norms and the output gate's norm are float32 (the
fused passes of :class:`KDAMixer` hold float32 from the projections'
outputs to ``q``, ``k``, ``v``, where the plain code rounds the
convolution and the SiLU to ``dtype`` on the way).  None decodes: the
recurrent states (a decay per channel at 128 x 128, a scalar decay at 96
x 192, a state-space state at 128 x 64 a head with its convolution's
tail of 3 tokens) and the latent cache have no place in
``serving/kv_slots.py`` yet (ROADMAP Queue 2).
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_models_tpu.models import remat as rematlib
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import linear_attention as linattn
from distributed_tensorflow_models_tpu.ops import selective_scan as sscanlib
from distributed_tensorflow_models_tpu.ops import ssm as ssmlib
from distributed_tensorflow_models_tpu.telemetry.registry import (
    KDA_MIXER_FUSED,
    KDA_MIXER_PLAIN,
    get_registry,
)

# ``jax.named_scope`` (and flax module) name of a whole delta-rule mixer,
# either kind; the chunk-wise core inside it is
# ``ops/linear_attention.py::KDA_CORE_SCOPE`` or ``GDN_CORE_SCOPE``.
LINEAR_ATTN_SCOPE = "linear_attn"
# The same of a whole state-space mixer (:class:`Mamba2Mixer`,
# :class:`Mamba1Mixer`) and of the unit that reads one's memory
# (:class:`GatedMemoryUnit`); the scan inside is
# ``ops/ssm.py::SSD_CORE_SCOPE`` or
# ``ops/selective_scan.py::SSCAN_CORE_SCOPE``, the unit's own work
# :data:`GMU_SCOPE`.
SSM_SCOPE = "ssm"
GMU_SCOPE = "gmu"


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, dtype=dtype, use_bias=False, name=name)


def causal_depthwise_conv(x, w):
    """``y_t = sum_j w[j] * x_{t - (K-1) + j}`` per channel: ``x`` ``[B,
    T, C]``, ``w`` ``[K, C]``; positions before the sequence are zeros."""
    K, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(padded[:, j : j + T] * w[j].astype(x.dtype) for j in range(K))


def l2norm(x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    return x32 * jax.lax.rsqrt(jnp.sum(x32 * x32, axis=-1, keepdims=True) + eps)


def _projection_and_taps(mdl: nn.Module, x, name: str, width: int):
    """A bias-free projection ``name`` of ``x`` to ``width`` channels and
    the taps ``conv_<name>`` ``[mdl.conv_size, width]`` of its causal
    depthwise convolution."""
    taps = mdl.conv_size
    # torch's Conv1d default: uniform(+-1/sqrt(fan_in)), fan_in K.
    weight = mdl.param(
        f"conv_{name}",
        lambda rng: jax.random.uniform(
            rng, (taps, width), jnp.float32, -(taps**-0.5), taps**-0.5
        ),
    )
    return _dense(width, mdl.dtype, name)(x), weight


def _short_conv_silu(mdl: nn.Module, x, name: str, heads: int, dim: int):
    """``silu(conv(W x))`` as ``[B, T, heads, dim]``: a bias-free
    projection ``name``, its causal depthwise convolution ``conv_<name>``
    of ``mdl.conv_size`` taps, SiLU."""
    y = causal_depthwise_conv(*_projection_and_taps(mdl, x, name, heads * dim))
    return jax.nn.silu(y).reshape(*x.shape[:2], heads, dim)


class _HeadScale(nn.Module):
    """The ``scale`` ``[dim]`` of a per-head ``nn.RMSNorm``, under the
    norm's own name and initialisation, for a caller that applies it
    itself (the fused route of :class:`KDAMixer`)."""

    dim: int

    @nn.compact
    def __call__(self):
        return self.param("scale", nn.initializers.ones, (self.dim,), jnp.float32)


def _a_log_init(heads: int):
    return lambda rng: jnp.log(
        jax.random.uniform(rng, (heads,), jnp.float32, 1.0, 16.0)
    )


def _dt_bias_init(count: int):
    """The inverse softplus of a step drawn log-uniformly from [1e-3,
    1e-1] (the published layers' initialisation)."""

    def init(rng):
        dt = jnp.exp(
            jax.random.uniform(
                rng, (count,), jnp.float32, math.log(1e-3), math.log(1e-1)
            )
        )
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


class KDAMixer(nn.Module):
    """Kimi Delta Attention (module docstring).  Two ways to place one
    mathematics, chosen from what the call shows
    (:func:`...ops.linear_attention.kda_mixer_route`) and counted once per
    traced call (``kda/mixer_fused``, ``kda/mixer_plain``): on a TPU, for
    heads of whole lane blocks, everything between the projections and
    the chunk-wise core and between the core and the output projection
    runs as fused passes over the flat ``[B, T, H * head_dim]`` views the
    projections write and the core's kernels read, and no ``[B, T, H,
    head_dim]`` array exists in HBM; everywhere else plain ``jax.numpy``
    on the ``[B, T, H, head_dim]`` view.  The parameters are the same,
    name for name."""

    num_heads: int
    head_dim: int
    d_model: int
    conv_size: int = 4
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, D = self.num_heads, self.head_dim
        width = H * D
        (xq, wq), (xk, wk), (xv, wv) = (
            _projection_and_taps(self, x, name, width)
            for name in ("query", "key", "value")
        )
        a_log = self.param("A_log", _a_log_init(H))
        dt_bias = self.param("dt_bias", _dt_bias_init(width))
        f = _dense(width, self.dtype, "f_b")(_dense(D, self.dtype, "f_a")(x))
        beta = jax.nn.sigmoid(_dense(H, self.dtype, "beta")(x).astype(jnp.float32))
        gate = _dense(width, self.dtype, "g_b")(_dense(D, self.dtype, "g_a")(x))

        fused = (
            linattn.kda_mixer_route(xq, xk, xv, heads=H, taps=self.conv_size)
            == "fused"
        )
        get_registry().counter(KDA_MIXER_FUSED if fused else KDA_MIXER_PLAIN).inc()
        if fused:
            q, k, v, g = linattn.kda_prologue(
                xq, xk, xv, f, wq, wk, wv, dt_bias, a_log
            )
            o = linattn.chunked_kda_flat(q, k, v, g, beta, keep=rematlib.kept_core)
            scale = _HeadScale(D, name="o_norm")()
            o = linattn.kda_epilogue(o, gate, scale, eps=self.norm_eps)
            return _dense(self.d_model, self.dtype, "out")(o)

        mixed = lambda y, w: jax.nn.silu(causal_depthwise_conv(y, w)).reshape(
            B, T, H, D
        )
        q = l2norm(mixed(xq, wq)).astype(self.dtype)
        k = l2norm(mixed(xk, wk)).astype(self.dtype)
        v = mixed(xv, wv)
        # The decay: -exp(A_log) * softplus(low-rank(x) + dt_bias), float32.
        g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
            (f.astype(jnp.float32) + dt_bias).reshape(B, T, H, D)
        )

        o = linattn.chunked_kda(q, k, v, g, beta)

        o = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32, name="o_norm")(o)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32).reshape(B, T, H, D))
        return _dense(self.d_model, self.dtype, "out")(
            o.astype(self.dtype).reshape(B, T, width)
        )


class GatedDeltaNetMixer(nn.Module):
    num_heads: int  # the heads held here
    key_dim: int
    value_dim: int
    d_model: int
    conv_size: int = 4
    norm_eps: float = 1e-6
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, dk, dv = self.num_heads, self.key_dim, self.value_dim
        q = l2norm(_short_conv_silu(self, x, "query", H, dk)).astype(self.dtype)
        k = l2norm(_short_conv_silu(self, x, "key", H, dk)).astype(self.dtype)
        v = _short_conv_silu(self, x, "value", H, dv)

        # One decay and one b a head and token, float32.
        a_log = self.param("A_log", _a_log_init(H))
        dt_bias = self.param("dt_bias", _dt_bias_init(H))
        per_head = lambda name: _dense(H, self.dtype, name)(x).astype(jnp.float32)
        g = -jnp.exp(a_log) * jax.nn.softplus(per_head("a") + dt_bias)
        beta = 2.0 * jax.nn.sigmoid(per_head("beta"))

        o = linattn.chunked_gdn(q, k, v, g, beta)

        gate = _dense(H * dv, self.dtype, "gate")(x)
        o = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32, name="o_norm")(o)
        o = o * jax.nn.silu(gate.astype(jnp.float32).reshape(B, T, H, dv))
        return _dense(self.d_model, self.dtype, "out")(
            o.astype(self.dtype).reshape(B, T, H * dv)
        )


class Mamba2Mixer(nn.Module):
    """The Mamba-2 state-space layer (module docstring): ``num_heads``
    heads of ``head_dim`` channels over a state of ``state_dim``, ``B``
    and ``C`` one vector for each of ``num_groups`` groups of heads."""

    num_heads: int
    head_dim: int
    state_dim: int
    d_model: int
    num_groups: int = 1
    conv_size: int = 4
    norm_eps: float = 1e-5
    chunk: int = 256  # the scan's, not the model's (ops/ssm.py)
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, P, N, K = self.num_heads, self.head_dim, self.state_dim, self.conv_size
        groups = self.num_groups
        inner, mixed = H * P, H * P + 2 * groups * N
        zxbcdt = rematlib.kept(_dense(inner + mixed + H, self.dtype, "in_proj")(x))
        z, xbc, dt = jnp.split(zxbcdt, [inner, inner + mixed], axis=-1)
        taps = self.param("conv", _conv_uniform(K, (K, mixed)))
        bias = self.param("conv_bias", _conv_uniform(K, (mixed,)))
        xbc = jax.nn.silu(causal_depthwise_conv(xbc, taps) + bias.astype(self.dtype))
        xs, b, c = jnp.split(xbc, [inner, inner + groups * N], axis=-1)
        if groups > 1:
            b, c = (y.reshape(B, T, groups, N) for y in (b, c))

        a_log = self.param("A_log", _a_log_init(H))
        dt_bias = self.param("dt_bias", _dt_bias_init(H))
        d_skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)

        y = ssmlib.chunked_ssd(
            xs.reshape(B, T, H, P), dt, a_log, b, c, d_skip, chunk=self.chunk
        ).reshape(B, T, inner)

        # The gate first, then the norm: over all the channels, or with
        # groups over each group's own (``mamba_ssm``'s ``RMSNormGated``
        # with ``group_size = d_inner / n_groups``).
        y = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        if groups > 1:
            # Each channel's group as a 0/1 matrix: the groups' sums and
            # their way back over the channels are two thin products on
            # the flat ``[B, T, d_inner]`` view (exact in float32 at
            # ``highest``); a ``[B, T, G, d_inner / G]`` view would put the
            # groups in the sublanes, a relayout of the whole activation.
            scale = _HeadScale(inner, name="norm")()
            member = jnp.repeat(jnp.eye(groups, dtype=jnp.float32), inner // groups, axis=0)
            thin = lambda a, m: jnp.dot(a, m, precision=jax.lax.Precision.HIGHEST)
            mean_square = thin(jnp.square(y), member) / (inner // groups)
            y = y * thin(jax.lax.rsqrt(mean_square + self.norm_eps), member.T) * scale
        else:
            y = nn.RMSNorm(epsilon=self.norm_eps, dtype=jnp.float32, name="norm")(y)
        return _dense(self.d_model, self.dtype, "out_proj")(y.astype(self.dtype))


def _conv_uniform(taps: int, shape):
    """torch's Conv1d default, weight and bias: uniform(+-1/sqrt(fan_in)),
    fan_in ``taps`` for a depth-wise convolution."""
    return lambda rng: jax.random.uniform(
        rng, shape, jnp.float32, -(taps**-0.5), taps**-0.5
    )


class Mamba1Mixer(nn.Module):
    """The Mamba-1 layer (module docstring): ``d_inner`` channels, each
    over a state of ``state_dim``; ``dt`` through ``dt_rank`` channels."""

    d_inner: int
    state_dim: int
    dt_rank: int
    d_model: int
    conv_size: int = 4
    chunk: int = 256  # the plain scan's, not the model's (ops/selective_scan.py)
    hand_on: bool = False
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u):
        inner, N, R, K = self.d_inner, self.state_dim, self.dt_rank, self.conv_size
        xz = rematlib.kept(_dense(2 * inner, self.dtype, "in_proj")(u))
        x, z = jnp.split(xz, 2, axis=-1)
        taps = self.param("conv", _conv_uniform(K, (K, inner)))
        bias = self.param("conv_bias", _conv_uniform(K, (inner,)))
        x = jax.nn.silu(causal_depthwise_conv(x, taps) + bias.astype(self.dtype))
        r, b, c = jnp.split(_dense(R + 2 * N, self.dtype, "x_proj")(x), [R, R + N], axis=-1)
        # mamba_ssm's defaults: W_dt uniform(+-dt_rank^-0.5), the bias the
        # inverse softplus of a step drawn log-uniformly from [1e-3, 1e-1],
        # A_log = log(1..N) in every channel, D ones.
        dt = nn.Dense(
            inner, dtype=self.dtype, use_bias=False, name="dt_proj",
            kernel_init=lambda rng, shape, dtype=jnp.float32: jax.random.uniform(
                rng, shape, dtype, -(R**-0.5), R**-0.5
            ),
        )(r)
        dt_bias = self.param("dt_bias", _dt_bias_init(inner))
        dt = jax.nn.softplus(dt.astype(jnp.float32) + dt_bias)
        a_log = self.param(
            "A_log",
            lambda rng: jnp.broadcast_to(
                jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (inner, N)
            ),
        )
        d_skip = self.param("D", nn.initializers.ones, (inner,), jnp.float32)

        y = sscanlib.selective_scan(x, dt, a_log, b, c, d_skip, chunk=self.chunk)

        gated = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
        out = _dense(self.d_model, self.dtype, "out_proj")(gated.astype(self.dtype))
        return (out, rematlib.kept(y)) if self.hand_on else out


class GatedMemoryUnit(nn.Module):
    """``W_out (m * silu(W_in u))``, ``m`` ``[B, T, width]`` the memory an
    earlier :class:`Mamba1Mixer` handed on (module docstring)."""

    d_model: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, u, memory):
        with jax.named_scope(GMU_SCOPE):
            gate = rematlib.kept(_dense(memory.shape[-1], self.dtype, "in_proj")(u))
            gated = memory.astype(jnp.float32) * jax.nn.silu(gate.astype(jnp.float32))
            return _dense(self.d_model, self.dtype, "out_proj")(gated.astype(self.dtype))


class LatentAttention(nn.Module):
    num_heads: int
    d_model: int
    kv_lora_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"

    @nn.compact
    def __call__(self, x):
        B, T, _ = x.shape
        H, qk = self.num_heads, self.nope_dim + self.rope_dim
        q = _dense(H * qk, self.dtype, "query")(x).reshape(B, T, H, qk)
        kv = _dense(self.kv_lora_rank + self.rope_dim, self.dtype, "kv_a")(x)
        c, k_r = kv[..., : self.kv_lora_rank], kv[..., self.kv_lora_rank :]
        c = nn.RMSNorm(
            epsilon=self.norm_eps, dtype=jnp.float32, name="kv_a_norm"
        )(c).astype(self.dtype)
        kv = _dense(H * (self.nope_dim + self.v_dim), self.dtype, "kv_b")(c)
        kv = kv.reshape(B, T, H, self.nope_dim + self.v_dim)
        k_n, v = kv[..., : self.nope_dim], kv[..., self.nope_dim :]
        k_r = jnp.broadcast_to(k_r[:, :, None, :], (B, T, H, self.rope_dim))
        k = jnp.concatenate([k_n, k_r], axis=-1)
        out = attnlib.attention(
            q, k, v, causal=True, scale=qk**-0.5, impl=self.attn_impl,
            keep=rematlib.kept_core,
        )
        return _dense(self.d_model, self.dtype, "out")(
            out.reshape(B, T, H * self.v_dim)
        )
