"""Decoder-only transformer LM — the consumer of the long-context stack.

The reference's only sequence model is the PTB LSTM (SURVEY.md §2.1 R8);
this model is the framework's beyond-parity flagship for the reserved
``seq``/``model``/``expert`` mesh axes (SURVEY.md §5.7, §7.5): a standard
pre-LN causal transformer whose attention is routed through
:mod:`...ops.attention` (reference / blockwise / fused Pallas) or, when the
harness passes an ``attention_fn``, through the sequence-parallel layer
(:func:`...parallel.ring.ring_attention` / :func:`ulysses_attention`), and
whose FFN blocks can be Switch-MoE layers over the ``expert`` axis
(:func:`...parallel.moe.moe_ffn`).

Parameter naming is pinned to :func:`...parallel.tensor.transformer_tp_rules`
(attn/query|key|value|out, mlp/up|down, embedding, head) so tensor
parallelism is a placement rule set, not a model change.

The block takes what an architecture states: the norm (LayerNorm, or
RMSNorm with its epsilon), biases on or off, RMSNorm on the query and key
projections, and the feed-forward kind: the GELU MLP, Switch experts in
every other block, or softmax-then-top-k routing over gated experts in
every block (:func:`...parallel.moe.topk_moe_ffn`; OLMoE, ROADMAP R1).
These are a model's published settings, not tuning options.

The layers of a stack may differ (Kimi Linear, Olmo-Hybrid, Granite
4.0-H and Nemotron 3 Nano: ``harness/config.py::kimi_linear``,
``olmo_hybrid``, ``granite_h_micro``, ``nemotron3_nano``):
``layer_mixers`` names each layer's token mixer
(full attention, or one of the two delta-rule linear attentions, the
latent attention or the Mamba-2 state-space layer of :mod:`.mixers`;
under ``pos_encoding="rope"`` only the full-attention layers rotate, the
others take no positions), ``norm_placement`` puts each norm before its
sub-layer (pre-norm) or on its output inside the residual branch (the
OLMo family's), ``head_dim`` frees the attention's head size from
``d_model / num_heads`` (a chip that holds a share of a layer's heads),
``moe_first_dense`` leading layers keep a dense
feed-forward (gated SiLU, ``dense_d_ff`` wide) before the expert layers
start, an expert layer may have shared experts beside the routed ones,
sigmoid scores renormalised and scaled, and hold a range of the router's
experts only (``moe_held``: one chip's share of an expert-parallel job).
A layer may also be **one sub-layer alone** behind its one norm, a mixer
without a feed-forward or a feed-forward without a mixer (``layer_mixers``
entries ``"<mixer>_only"`` and ``"ffn_only"``: the Nemotron-H family,
whose 52 layers are a Mamba-2 mixer, an expert feed-forward or attention
each), and its feed-forwards, dense, shared and routed, may be **two
matrices around a squared ReLU without a gate** (``mlp="relu2"``,
``moe_expert="relu2"``; the experts of the other models are three with a
SiLU gate).
What a layer makes may also be read by later layers
(Phi-4-mini-flash-reasoning, ``harness/config.py::phi4_mini_flash``: a
self-decoder of Mamba-1 and differential-attention layers, a window in
all but its last, under a cross-decoder): a ``"gmu"`` layer reads the scan
output (the **memory**) of the nearest earlier ``"mamba1"`` layer, a
``"cross"`` layer the keys and values of the nearest earlier
``"attention_full"`` layer and projects a query alone.  What those source
layers hand on travels down the stack beside the residual stream, is an
input of a recomputed half (kept, not recomputed) and takes its gradient
as the sum over its readers.  ``attn_window`` is the one span: an
``"attention"`` layer runs under it, an ``"attention_full"`` layer never.
``attn_differential`` makes every attention of the stack, cross-attention
included, the differential one (:class:`SelfAttention`).
Granite's four scalars (``embedding_multiplier`` on the embedding,
``residual_multiplier`` on each sub-layer's output before it joins the
residual, ``attention_multiplier`` for the scores' scale,
``logits_scaling`` dividing the logits) and ``tie_embeddings`` (the head
is the embedding matrix: no ``head`` parameter) default to a model
without them, whose traced program they leave as it was.

TPU notes: bf16 compute with fp32 LayerNorm and logits; attention and MLP
matmuls are [B·T, d]-shaped for the MXU; causal masking is positional (no
materialized [T, T] mask when the blockwise/fused paths run).
"""

from __future__ import annotations

import math
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_tensorflow_models_tpu.models import mixers, register, remat as rematlib
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops.embed import TokenEmbed


def make_norm(kind: str, eps: Optional[float], name: str) -> nn.Module:
    """The normalisation an architecture states, computed in float32.
    ``eps`` None keeps flax's default (1e-6, what the GPT-2 block has
    always used here)."""
    kwargs = {} if eps is None else {"epsilon": eps}
    if kind == "layernorm":
        return nn.LayerNorm(dtype=jnp.float32, name=name, **kwargs)
    if kind == "rmsnorm":
        return nn.RMSNorm(dtype=jnp.float32, name=name, **kwargs)
    raise ValueError(f"unknown norm {kind!r} (want 'layernorm' or 'rmsnorm')")


class SelfAttention(nn.Module):
    """Causal multi-head self-attention with pluggable attention impl.

    ``decode=True`` switches to autoregressive KV-cache mode: each call
    appends the new tokens' K/V into ``cache`` collection variables sized
    ``[B, max_len, H, Dh]`` (written with ``lax.dynamic_update_slice`` so
    the program stays static-shaped under ``lax.scan``) and attends over
    the cache with global-position causal masking — the TPU-idiomatic
    decode loop (one compiled step, no growing shapes)."""

    num_heads: int
    d_model: int
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"
    # Sequence-parallel override: (q, k, v, causal=...) -> out, BTHD.
    attention_fn: Optional[Callable] = None
    decode: bool = False
    max_len: int = 0
    # Grouped-query attention: KV projections (and the decode cache)
    # carry num_kv_heads < num_heads heads; 0 = standard MHA.  The
    # attention impls infer the grouping from the shapes (ops/attention).
    num_kv_heads: int = 0
    # Sliding-window (local) attention span; None = full causal.
    attn_window: Any = None
    # Rotary position embeddings: q/k rotate by global position before
    # attention (ops/rotary.py); keys are cached post-rotation in decode.
    use_rope: bool = False
    rope_theta: float = 10000.0
    use_bias: bool = True
    # RMSNorm over the whole query and key projections, each with its own
    # weight, before the head split and the rotation (OLMoE).
    qk_norm: bool = False
    norm_eps: Optional[float] = None
    # A head's channels where that is not ``d_model / num_heads`` (0): a
    # program that holds ``num_heads`` of a layer's heads keeps the
    # published head size, and its ``out`` projection gives a partial sum.
    head_dim: int = 0
    # The scores' scale where it is not ``head size ** -0.5`` (None).
    scale: Optional[float] = None
    # Differential attention (Ye et al. 2024, arXiv:2410.05258) where set,
    # to the layer's ``lambda_init``: heads pair up (even and odd query
    # heads ``q1``, ``q2``; likewise ``k1``, ``k2``; a pair's values its two
    # heads' side by side), ``a = softmax(q1 k1^T) v - lambda softmax(q2
    # k2^T) v`` with ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
    # lambda_init`` from four learned vectors, and ``(1 - lambda_init)
    # rmsnorm(a)`` (a weight, per pair) goes to ``out``.  None: softmax
    # attention, and the traced program of before.
    diff_lambda_init: Optional[float] = None
    # ``query_only``: no key and value projections, the call's ``kv`` (an
    # earlier layer's) instead; ``hand_on_kv``: the call also returns its
    # keys and values, for such layers.
    query_only: bool = False
    hand_on_kv: bool = False

    def _differential(self, q, k, v):
        """``[B, T, H * Dh]`` of the differential attention of ``q`` ``[B,
        T, H, Dh]`` over ``k``, ``v`` ``[B, T, Hkv, Dh]``.  One call of the
        core: query head ``2 j + s`` reads key head ``2 m + s`` and the
        value pair ``m`` (``2 Dh`` wide), ``m = j // (H / Hkv)`` the pair's
        group; under 128 value channels the fused kernels pad the 64
        query/key channels to a lane block (``ops/attention.py``), which
        costs 256 multiply-adds a score where two calls on the values'
        halves would cost 512 and take every exponential twice."""
        B, T, H, Dh = q.shape
        pairs, kv_pairs = H // 2, k.shape[2] // 2
        group = pairs // kv_pairs
        over_group = lambda y, *per_head: jnp.broadcast_to(
            y.reshape(B, T, kv_pairs, 1, *per_head),
            (B, T, kv_pairs, group, 2, per_head[-1]),
        ).reshape(B, T, H, per_head[-1])
        a = attnlib.attention(
            q, over_group(k, 2, Dh), over_group(v, 1, 2 * Dh), causal=True,
            impl=self.attn_impl, window=self.attn_window,
            scale=self.scale if self.scale is not None else Dh**-0.5,
            keep=rematlib.kept_core,
        ).astype(jnp.float32).reshape(B, T, pairs, 2, 2 * Dh)
        vector = lambda name: self.param(
            name, nn.initializers.normal(0.1), (Dh,), jnp.float32
        )
        lam = (
            jnp.exp(jnp.sum(vector("lambda_q1") * vector("lambda_k1")))
            - jnp.exp(jnp.sum(vector("lambda_q2") * vector("lambda_k2")))
            + self.diff_lambda_init
        )
        a = a[:, :, :, 0] - lam * a[:, :, :, 1]
        a = nn.RMSNorm(
            epsilon=self.norm_eps or 1e-5, dtype=jnp.float32, name="subln"
        )(a) * (1.0 - self.diff_lambda_init)
        return a.astype(self.dtype).reshape(B, T, H * Dh)

    def _core(self, q, k, v):
        """``[B, T, H * Dh]`` of a differential layer, or of one that hands
        on or reads keys and values: no positions, no cache."""
        if self.diff_lambda_init is not None:
            return self._differential(q, k, v)
        return attnlib.attention(
            q, k, v, causal=True, impl=self.attn_impl, window=self.attn_window,
            scale=self.scale, keep=rematlib.kept_core,
        ).reshape(*q.shape[:2], -1)

    @nn.compact
    def __call__(self, x, train: bool = False, kv=None):
        from distributed_tensorflow_models_tpu.ops import rotary

        B, T, _ = x.shape
        H = self.num_heads
        Hkv = self.num_kv_heads or H
        Dh = self.head_dim or self.d_model // H
        dense = lambda name, feats: nn.Dense(
            feats, dtype=self.dtype, use_bias=self.use_bias, name=name
        )
        q = dense("query", H * Dh)(x)
        if self.query_only:
            q, (k, v) = q.reshape(B, T, H, Dh), kv
        else:
            k = dense("key", Hkv * Dh)(x)
            if self.qk_norm:
                norm = lambda name, y: make_norm("rmsnorm", self.norm_eps, name)(
                    y
                ).astype(self.dtype)
                q, k = norm("q_norm", q), norm("k_norm", k)
            q = q.reshape(B, T, H, Dh)
            k = k.reshape(B, T, Hkv, Dh)
            v = dense("value", Hkv * Dh)(x).reshape(B, T, Hkv, Dh)
        if self.query_only or self.hand_on_kv or self.diff_lambda_init is not None:
            out = dense("out", self.d_model)(self._core(q, k, v))
            if self.dropout_rate:
                out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
            return (out, (rematlib.kept(k), rematlib.kept(v))) if self.hand_on_kv else out
        if self.use_rope and not self.decode:
            pos = jnp.arange(T)
            q = rotary.apply_rope(q, pos, self.rope_theta)
            k = rotary.apply_rope(k, pos, self.rope_theta)
        if self.decode:
            ck = self.variable(
                "cache", "cached_key",
                lambda: jnp.zeros((B, self.max_len, Hkv, Dh), k.dtype),
            )
            cv = self.variable(
                "cache", "cached_value",
                lambda: jnp.zeros((B, self.max_len, Hkv, Dh), v.dtype),
            )
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            idx = ci.value
            if self.use_rope:
                pos = idx + jnp.arange(T)
                q = rotary.apply_rope(q, pos, self.rope_theta)
                k = rotary.apply_rope(k, pos, self.rope_theta)
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k, (0, idx, 0, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v, (0, idx, 0, 0)
            )
            ci.value = idx + T
            # Causal mask in global positions (q rows sit at idx..idx+T-1)
            # also hides the cache's not-yet-written tail: unwritten slots
            # are all at positions > the last query row.
            out = attnlib.reference_attention(
                q, ck.value, cv.value, causal=True, q_offset=idx,
                window=self.attn_window, scale=self.scale,
            )
        elif self.attention_fn is not None:
            out = self.attention_fn(q, k, v, causal=True)
        else:
            out = attnlib.attention(
                q, k, v, causal=True, impl=self.attn_impl,
                window=self.attn_window, scale=self.scale,
                keep=rematlib.kept_core,
            )
        out = out.reshape(B, T, H * Dh)
        out = dense("out", self.d_model)(out)
        if self.dropout_rate:
            out = nn.Dropout(self.dropout_rate, deterministic=not train)(out)
        return out


class MLP(nn.Module):
    """``down(act(up(x)))``: the GELU feed-forward of the GPT-2 block, or
    with ``activation="relu2"`` the squared-ReLU one of the Nemotron-H
    family (its dense layers and its shared expert)."""

    d_model: int
    d_ff: int
    dropout_rate: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    use_bias: bool = True
    activation: str = "gelu"

    @nn.compact
    def __call__(self, x, train: bool = False):
        dense = lambda name, feats: nn.Dense(
            feats, dtype=self.dtype, use_bias=self.use_bias, name=name
        )
        h = rematlib.kept(dense("up", self.d_ff)(x))
        if self.activation == "relu2":
            from distributed_tensorflow_models_tpu.parallel.moe import squared_relu

            h = squared_relu(h)
        else:
            h = nn.gelu(h)
        h = dense("down", self.d_model)(h)
        if self.dropout_rate:
            h = nn.Dropout(self.dropout_rate, deterministic=not train)(h)
        return h


class GatedMLP(nn.Module):
    """``down(silu(gate(x)) * up(x))`` without biases: the dense
    feed-forward, and the shared expert, of the models that state it."""

    d_model: int
    d_ff: int
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        dense = lambda name, feats: nn.Dense(
            feats, dtype=self.dtype, use_bias=False, name=name
        )
        wide = lambda name: rematlib.kept(dense(name, self.d_ff)(x))
        return dense("down", self.d_model)(nn.silu(wide("gate")) * wide("up"))


# "attention" runs under ``attn_window`` where the stack states one,
# "attention_full" never; "cross" projects a query alone and reads the
# nearest earlier "attention_full" layer's keys and values; "gmu" reads the
# nearest earlier "mamba1" layer's scan output.
_ATTENTIONS = ("attention", "attention_full", "cross")
_MIXERS = _ATTENTIONS + ("kda", "gdn", "mla", "ssm", "mamba1", "gmu")
# What a reader reads, by its kind: the source layer's kind.
_READS = {"gmu": "mamba1", "cross": "attention_full"}
# What an entry of ``TransformerLM.layer_mixers`` may say.
_LAYER_KINDS = _MIXERS + tuple(f"{m}_only" for m in _MIXERS) + ("ffn_only",)

# ``jax.named_scope`` of the shared experts of an expert layer (inside
# the layer's ``moe``): what every token goes through beside its routed
# experts.
MOE_SHARED_SCOPE = "moe_shared"


def _first(fn, res):
    """``fn`` on a half's output, beside what the half hands on."""
    return (fn(res[0]), *res[1:]) if isinstance(res, tuple) else fn(res)


class MoEFFN(nn.Module):
    """Switch-MoE FFN block: flax param declaration around
    :func:`...parallel.moe.moe_ffn` (expert-parallel all_to_all exchange
    over the ``expert`` axis).  The load-balancing aux loss is sowed into
    the ``losses`` collection, which :func:`...core.train_loop.lm_loss_fn`
    sums into the objective."""

    num_experts: int
    d_model: int
    d_ff: int
    mesh: Any  # jax.sharding.Mesh; static module attribute
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    dtype: jnp.dtype = jnp.bfloat16

    @nn.compact
    def __call__(self, x, train: bool = False):
        from distributed_tensorflow_models_tpu.parallel import moe as moelib

        B, T, d = x.shape
        scale_in = 1.0 / jnp.sqrt(jnp.float32(d))
        scale_out = 1.0 / jnp.sqrt(jnp.float32(self.d_ff))
        params = {
            "router": self.param(
                "router",
                lambda rng: jax.random.normal(rng, (d, self.num_experts))
                * scale_in,
            ),
            "w_in": self.param(
                "w_in",
                lambda rng: jax.random.normal(
                    rng, (self.num_experts, d, self.d_ff)
                )
                * scale_in,
            ),
            "w_out": self.param(
                "w_out",
                lambda rng: jax.random.normal(
                    rng, (self.num_experts, self.d_ff, d)
                )
                * scale_out,
            ),
        }
        if self.mesh is None:
            # Mesh-free path (init/eval_shape): the single-rank oracle with
            # identical routing semantics.
            res = moelib.moe_ffn_reference(
                params, x.reshape(B * T, d), num_ranks=1,
                capacity_factor=self.capacity_factor,
            )
        else:
            res = moelib.moe_ffn(
                params,
                x.reshape(B * T, d),
                mesh=self.mesh,
                capacity_factor=self.capacity_factor,
            )
        self.sow(
            "losses",
            "moe_aux",
            self.aux_loss_weight * res.aux_loss,
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        return res.out.reshape(B, T, d).astype(x.dtype)


class TopKExpertsFFN(nn.Module):
    """Top-k routing over experts without a capacity: flax parameter
    declaration around :func:`...parallel.moe.topk_moe_ffn`.  ``expert``:
    ``"gated_silu"`` (three matrices an expert, ``W_down (silu(W_gate h) *
    W_up h)``) or ``"relu2"`` (two, ``W_down relu(W_up h)^2``: no
    ``w_gate`` leaf); the shared experts are of the same kind.
    The weighted load-balancing loss and router z-loss go into the
    ``losses`` collection (summed into the objective by
    :func:`...core.train_loop.lm_loss_fn`; a weight of 0 puts nothing
    there); the unweighted values and the load statistic into
    ``moe_stats``, which the loss function averages over layers into the
    step's metrics.  ``held = (first, count)``: the expert stacks hold
    that range of the router's ``num_experts`` (``held_share`` and
    ``held_slabs`` join the statistics, and a recomputed half keeps the
    layer's routing plan: ``models/remat.py``); ``shared_experts``: a feed-forward that many experts
    wide (each ``shared_d_ff`` where that is not an expert's ``d_ff``) on
    every token, added to the routed result."""

    num_experts: int
    top_k: int
    d_model: int
    d_ff: int  # one expert's width
    mesh: Any = None
    aux_loss_weight: float = 0.01
    z_loss_weight: float = 0.0
    dtype: jnp.dtype = jnp.bfloat16
    routing: Any = None  # parallel.moe.Routing; None: softmax as it is
    held: Any = None
    shared_experts: int = 0
    expert: str = "gated_silu"
    shared_d_ff: int = 0

    @nn.compact
    def __call__(self, x, train: bool = False):
        from distributed_tensorflow_models_tpu.parallel import moe as moelib

        E, d, f = self.num_experts, self.d_model, self.d_ff
        here = E if self.held is None else self.held[1]
        gated = self.expert == "gated_silu"

        def normal(name, shape, fan_in):
            return self.param(
                name,
                lambda rng: jax.random.normal(rng, shape) * fan_in**-0.5,
            )

        params = {"router": normal("router", (d, E), d)}
        if gated:
            params["w_gate"] = normal("w_gate", (here, d, f), d)
        params["w_up"] = normal("w_up", (here, d, f), d)
        params["w_down"] = normal("w_down", (here, f, d), f)
        res = moelib.topk_moe_ffn(
            params, x, top_k=self.top_k, mesh=self.mesh, dtype=self.dtype,
            routing=self.routing or moelib.Routing(), held=self.held,
            keep=rematlib.kept_plan,
        )
        scalar = dict(
            reduce_fn=lambda a, b: a + b,
            init_fn=lambda: jnp.zeros((), jnp.float32),
        )
        if self.aux_loss_weight:
            self.sow("losses", "moe_aux", self.aux_loss_weight * res.aux_loss, **scalar)
        if self.z_loss_weight:
            self.sow("losses", "moe_z", self.z_loss_weight * res.z_loss, **scalar)
        stats = [
            ("aux_loss", res.aux_loss),
            ("z_loss", res.z_loss),
            ("load_max_over_mean", res.load_max_over_mean),
        ]
        if self.held is not None:
            stats += [("held_share", res.held_share), ("held_slabs", res.held_slabs)]
        for name, value in stats:
            self.sow("moe_stats", name, value, **scalar)
        out = res.out.astype(x.dtype)
        if self.shared_experts:
            width = (self.shared_d_ff or f) * self.shared_experts
            with jax.named_scope(MOE_SHARED_SCOPE):
                if gated:
                    shared = GatedMLP(d, width, self.dtype, name="shared")
                else:
                    shared = MLP(
                        d, width, dtype=self.dtype, use_bias=False,
                        activation="relu2", name="shared",
                    )
                out = out + shared(x)
        return out


class Block(nn.Module):
    num_heads: int
    d_model: int
    d_ff: int
    dropout_rate: float
    dtype: jnp.dtype
    attn_impl: str
    attention_fn: Optional[Callable]
    use_moe: bool = False
    num_experts: int = 0
    moe_mesh: Any = None
    moe_capacity_factor: float = 1.25
    decode: bool = False
    max_len: int = 0
    num_kv_heads: int = 0
    attn_window: Any = None
    use_rope: bool = False
    rope_theta: float = 10000.0
    norm: str = "layernorm"
    norm_eps: Optional[float] = None
    use_bias: bool = True
    qk_norm: bool = False
    # Experts' routing where ``use_moe``: "switch" (top-1 with a capacity,
    # ReLU experts) or "topk" (softmax then top-k, experts of the kind
    # ``moe_expert`` says, exact).
    moe_router: str = "switch"
    moe_top_k: int = 1
    moe_z_loss_weight: float = 0.0
    moe_aux_loss_weight: float = 0.01
    moe_routing: Any = None
    moe_held: Any = None
    moe_shared_experts: int = 0
    moe_expert: str = "gated_silu"
    moe_shared_d_ff: int = 0
    # The token mixer: "attention" (SelfAttention), "kda", "gdn", "mla" or
    # "ssm" (models/mixers.py; ``mixer_kwargs`` are that module's sizes),
    # or "none": a layer that is its feed-forward alone (no ``ln1``, no
    # mixer leaf).  ``feed`` False: a layer that is its mixer alone (no
    # ``ln2``, no feed-forward leaf).
    mixer: str = "attention"
    mixer_kwargs: Any = None
    feed: bool = True
    head_dim: int = 0
    # The attention scores' scale (None: head size ** -0.5) and what each
    # sub-layer's output is multiplied by before it joins the residual.
    attn_scale: Optional[float] = None
    residual_multiplier: float = 1.0
    # "pre": ``x + f(norm(x))``; "post": ``x + norm(f(x))`` (OLMo 2's
    # block, arXiv:2501.00656: the norm on the sub-layer's output, inside
    # the residual branch).
    norm_placement: str = "pre"
    # The dense feed-forward: the "gelu" MLP, the "gated_silu" one or the
    # "relu2" one (the MLP with a squared ReLU).
    mlp: str = "gelu"
    # Recompute in the backward pass, the mixer's half of the block and
    # the feed-forward's each on its own (each with its norm): a half keeps
    # its input and its wide input products (models/remat.py), no more.
    # A layer of one sub-layer has one half.
    remat: bool = False
    # A source layer ("mamba1", "attention_full") that later layers read:
    # the call returns ``(x, handed)``.  A reader ("gmu", "cross") takes
    # what its source handed on as the call's ``read``.
    hands_on: bool = False
    # Differential attention's ``lambda_init`` of this layer (None: softmax
    # attention); biases on the attention projections where that is not
    # ``use_bias`` (None).
    diff_lambda_init: Optional[float] = None
    attn_bias: Optional[bool] = None

    def _mix(self, h, train, *read):
        if self.mixer in _ATTENTIONS:
            return self._attention(h, train, *read)
        sizes = dict(self.mixer_kwargs or ())
        if self.mixer == "mamba1":
            out = mixers.Mamba1Mixer(
                d_model=self.d_model, dtype=self.dtype, hand_on=self.hands_on,
                name=mixers.SSM_SCOPE, **sizes,
            )(h)
        elif self.mixer == "gmu":
            out = mixers.GatedMemoryUnit(
                self.d_model, self.dtype, name=mixers.SSM_SCOPE
            )(h, *read)
        elif self.mixer in ("kda", "gdn"):
            kind = mixers.KDAMixer if self.mixer == "kda" else mixers.GatedDeltaNetMixer
            out = kind(
                d_model=self.d_model, norm_eps=self.norm_eps or 1e-6,
                dtype=self.dtype, name=mixers.LINEAR_ATTN_SCOPE, **sizes,
            )(h)
        elif self.mixer == "ssm":
            out = mixers.Mamba2Mixer(
                d_model=self.d_model, norm_eps=self.norm_eps or 1e-6,
                dtype=self.dtype, name=mixers.SSM_SCOPE, **sizes,
            )(h)
        else:
            out = mixers.LatentAttention(
                num_heads=self.num_heads, d_model=self.d_model,
                norm_eps=self.norm_eps or 1e-6, dtype=self.dtype,
                attn_impl=self.attn_impl, name="attn", **sizes,
            )(h)
        if self.dropout_rate:
            out = _first(nn.Dropout(self.dropout_rate, deterministic=not train), out)
        return out

    @nn.compact
    def __call__(self, x, train: bool = False, read=None):
        norm = lambda name, y: make_norm(self.norm, self.norm_eps, name)(
            y
        ).astype(self.dtype)
        if self.norm_placement == "post":
            mix = lambda mdl, y, *r: _first(
                lambda out: norm("ln1", out), mdl._mix(y, train, *r)
            )
            feed = lambda mdl, y: norm("ln2", rematlib.kept(mdl._ffn()(y, train=train)))
        else:
            mix = lambda mdl, y, *r: mdl._mix(norm("ln1", y), train, *r)
            feed = lambda mdl, y: mdl._ffn()(norm("ln2", y), train=train)
        if self.residual_multiplier != 1.0:
            branch = lambda half: lambda mdl, *ys: _first(
                lambda out: self.residual_multiplier * out, half(mdl, *ys)
            )
            mix, feed = branch(mix), branch(feed)
        if self.remat:
            mix, feed = rematlib.half(mix), rematlib.half(feed)
        handed = None
        if self.mixer != "none":
            out = mix(self, x, *(() if read is None else (read,)))
            if self.hands_on:
                out, handed = out
            x = x + out
        if self.feed:
            x = x + feed(self, x)
        return (x, handed) if self.hands_on else x

    def _ffn(self) -> nn.Module:
        if self.use_moe and self.moe_router == "topk":
            return TopKExpertsFFN(
                self.num_experts,
                self.moe_top_k,
                self.d_model,
                self.d_ff,
                self.moe_mesh,
                aux_loss_weight=self.moe_aux_loss_weight,
                z_loss_weight=self.moe_z_loss_weight,
                dtype=self.dtype,
                routing=self.moe_routing,
                held=self.moe_held,
                shared_experts=self.moe_shared_experts,
                expert=self.moe_expert,
                shared_d_ff=self.moe_shared_d_ff,
                name="moe",
            )
        if self.use_moe:
            return MoEFFN(
                self.num_experts,
                self.d_model,
                self.d_ff,
                self.moe_mesh,
                capacity_factor=self.moe_capacity_factor,
                dtype=self.dtype,
                name="moe",
            )
        if self.mlp == "gated_silu":
            return GatedMLP(self.d_model, self.d_ff, self.dtype, name="mlp")
        return MLP(
            self.d_model,
            self.d_ff,
            self.dropout_rate,
            self.dtype,
            use_bias=self.use_bias,
            activation="relu2" if self.mlp == "relu2" else "gelu",
            name="mlp",
        )

    def _attention(self, h, train, *read):
        return SelfAttention(
            self.num_heads,
            self.d_model,
            self.dropout_rate,
            self.dtype,
            self.attn_impl,
            self.attention_fn,
            decode=self.decode,
            max_len=self.max_len,
            num_kv_heads=self.num_kv_heads,
            attn_window=self.attn_window if self.mixer == "attention" else None,
            use_rope=self.use_rope,
            rope_theta=self.rope_theta,
            use_bias=self.use_bias if self.attn_bias is None else self.attn_bias,
            qk_norm=self.qk_norm,
            norm_eps=self.norm_eps,
            head_dim=self.head_dim,
            scale=self.attn_scale,
            diff_lambda_init=self.diff_lambda_init,
            query_only=self.mixer == "cross",
            hand_on_kv=self.hands_on,
            name="attn",
        )(h, train, *read)


class PipelinedBlocks(nn.Module):
    """The block stack with per-layer-stacked parameters, executed as a
    GPipe microbatch pipeline over the ``pipe`` axis
    (:func:`...parallel.pipeline.pipeline_apply`) when ``pipe_mesh`` is
    set, and by the sequential reference schedule otherwise — the same
    parameter structure either way, so the two paths are interchangeable
    on identical variables (pinned by tests).

    Parameters are declared stacked ``[L, ...]`` (per-layer fan-correct
    init via vmapped initializers), reshaped to ``[n_stages, L/n, ...]``
    at call time; each pipeline stage applies its ``L/n`` pre-LN blocks.
    Dropout works through the stages: the step's dropout key rides with
    the stage parameter slices (raw uint32) and masks are derived per
    (layer, sublayer, global batch row), so the pipelined and sequential
    schedules produce identical masks and data-shards stay independent.
    Restrictions of the pipelined path: dense FFN only; tensor-parallel
    rules don't target the stacked layout.
    """

    num_layers: int
    num_heads: int
    d_model: int
    d_ff: int
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"
    pipe_mesh: Any = None
    num_microbatches: int = 4
    dropout_rate: float = 0.0

    @nn.compact
    def __call__(self, x, train: bool = False):
        from distributed_tensorflow_models_tpu.parallel import (
            pipeline as pplib,
        )

        L, d, f = self.num_layers, self.d_model, self.d_ff

        def stacked(name, shape, stddev):
            def init(rng):
                ks = jax.random.split(rng, L)
                return jax.vmap(
                    lambda k: jax.random.normal(k, shape, jnp.float32)
                    * stddev
                )(ks)

            return self.param(name, init)

        params = {
            "ln1_scale": self.param(
                "ln1_scale", lambda _: jnp.ones((L, d), jnp.float32)
            ),
            "ln1_bias": self.param(
                "ln1_bias", lambda _: jnp.zeros((L, d), jnp.float32)
            ),
            "wq": stacked("wq", (d, d), d**-0.5),
            "wk": stacked("wk", (d, d), d**-0.5),
            "wv": stacked("wv", (d, d), d**-0.5),
            "wo": stacked("wo", (d, d), d**-0.5),
            "ln2_scale": self.param(
                "ln2_scale", lambda _: jnp.ones((L, d), jnp.float32)
            ),
            "ln2_bias": self.param(
                "ln2_bias", lambda _: jnp.zeros((L, d), jnp.float32)
            ),
            "w_up": stacked("w_up", (d, f), d**-0.5),
            "w_down": stacked("w_down", (f, d), f**-0.5),
        }

        H = self.num_heads
        Dh = d // H
        dtype = self.dtype
        attn_impl = self.attn_impl
        rate = self.dropout_rate if train else 0.0
        dropout_key = (
            jax.random.key_data(self.make_rng("dropout")) if rate else None
        )

        def _ln(x, scale, bias):
            x32 = x.astype(jnp.float32)
            mu = x32.mean(-1, keepdims=True)
            var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
            return (x32 - mu) * jax.lax.rsqrt(var + 1e-6) * scale + bias

        def _dropout(x, p, row_ids, sub):
            # Keyed per (layer, sublayer, GLOBAL batch row): row-level
            # keying makes masks identical between the pipelined and
            # sequential schedules AND independent across data-shards —
            # inside shard_map each data-rank holds different rows of the
            # microbatch, so shape-keyed generation from the shared key
            # would hand every rank the same mask (caught by the
            # oracle-equality test).
            if rate == 0.0:
                return x
            key = jax.random.wrap_key_data(p["dropout_key"])
            key = jax.random.fold_in(key, p["layer_id"] * 2 + sub)
            keep = jax.vmap(
                lambda r: jax.random.bernoulli(
                    jax.random.fold_in(key, r), 1.0 - rate, x.shape[1:]
                )
            )(row_ids)
            return jnp.where(keep, x / (1.0 - rate), 0).astype(x.dtype)

        def one_layer(p, x, row_ids):
            B, T, _ = x.shape
            h = _ln(x, p["ln1_scale"], p["ln1_bias"]).astype(dtype)
            q = (h @ p["wq"].astype(dtype)).reshape(B, T, H, Dh)
            k = (h @ p["wk"].astype(dtype)).reshape(B, T, H, Dh)
            v = (h @ p["wv"].astype(dtype)).reshape(B, T, H, Dh)
            a = attnlib.attention(q, k, v, causal=True, impl=attn_impl)
            a = a.reshape(B, T, d) @ p["wo"].astype(dtype)
            x = x + _dropout(a, p, row_ids, 0)
            h = _ln(x, p["ln2_scale"], p["ln2_bias"]).astype(dtype)
            h = nn.gelu(h @ p["w_up"].astype(dtype))
            h = h @ p["w_down"].astype(dtype)
            return x + _dropout(h, p, row_ids, 1)

        n_stages = (
            self.pipe_mesh.shape["pipe"] if self.pipe_mesh is not None else 1
        )
        if L % n_stages:
            raise ValueError(
                f"num_layers {L} not divisible by pipe axis {n_stages}"
            )
        per_stage = L // n_stages
        staged = jax.tree.map(
            lambda a: a.reshape((n_stages, per_stage) + a.shape[1:]), params
        )
        # Non-parameter constants riding with the stage slices: global
        # layer ids (dropout keying) and the step's dropout key (raw
        # uint32 so it shards/permutes like any other leaf).
        staged["layer_id"] = jnp.arange(L, dtype=jnp.int32).reshape(
            n_stages, per_stage
        )
        if dropout_key is not None:
            staged["dropout_key"] = jnp.broadcast_to(
                dropout_key, (n_stages,) + dropout_key.shape
            )

        def stage_fn(stage_params, xm):
            sp = dict(stage_params)
            # The dropout key is per-stage, not per-layer: keep it out of
            # the per-layer slice.
            dk = sp.pop("dropout_key", None)
            x, row_ids = xm["x"], xm["rid"]
            for i in range(per_stage):
                p = jax.tree.map(lambda a: a[i], sp)
                if dk is not None:
                    p["dropout_key"] = dk
                x = one_layer(p, x, row_ids)
            return {"x": x, "rid": xm["rid"]}

        m = self.num_microbatches
        if self.pipe_mesh is None and x.shape[0] % m:
            # Mesh-free path (init on a tiny sample / oracle runs): the
            # schedule is sequential anyway, so clamp rather than reject —
            # parameters do not depend on the microbatch count.
            m = 1
        mbs = pplib.split_microbatches(x, m)
        mb_size = mbs.shape[1]
        # Global batch-row ids travel with their rows (contiguous blocks,
        # matching split_microbatches' reshape).
        row_ids = jnp.arange(m * mb_size, dtype=jnp.int32).reshape(
            m, mb_size
        )
        tree = {"x": mbs, "rid": row_ids}
        if self.pipe_mesh is None:
            out = pplib.sequential_apply(stage_fn, staged, tree)
        else:
            out = pplib.pipeline_apply(
                stage_fn, staged, tree, mesh=self.pipe_mesh
            )
        return pplib.merge_microbatches(out["x"])


class TransformerLM(nn.Module):
    """Input ``tokens [B, T]`` int32; returns ``(logits [B, T, V], carry)``
    — the ``carry`` passthrough keeps the LM train-step contract shared
    with the PTB LSTM (:func:`...core.train_loop.lm_loss_fn`); a
    transformer has no recurrent state, so it is returned unchanged."""

    vocab_size: int = 10000
    num_layers: int = 4
    num_heads: int = 8
    d_model: int = 256
    d_ff: int = 1024
    max_len: int = 1024
    dropout_rate: float = 0.1
    dtype: jnp.dtype = jnp.bfloat16
    attn_impl: str = "auto"
    attention_fn: Optional[Callable] = None
    # Every other block becomes a Switch-MoE FFN when num_experts > 0
    # (the standard Switch placement).
    num_experts: int = 0
    moe_mesh: Any = None
    moe_capacity_factor: float = 1.25
    # Pipeline parallelism: stacked-parameter block stack scheduled by
    # GPipe over the ``pipe`` axis.  ``pipelined=True`` switches the
    # parameter layout (also without a mesh, for oracle comparisons).
    pipelined: bool = False
    pipe_mesh: Any = None
    pipeline_microbatches: int = 4
    # Rematerialize each half of a block (mixer, feed-forward) in backward
    # (jax.checkpoint): trades ~1/3 more FLOPs for O(num_layers) less
    # activation HBM — the standard TPU long-context memory lever
    # (SURVEY.md TPU notes).
    remat: bool = False
    # Autoregressive decode mode: KV caches in the ``cache`` variable
    # collection (see SelfAttention); drive with harness/generate.py.
    decode: bool = False
    # Grouped-query attention (0 = MHA); shrinks KV projections and the
    # decode cache by num_heads/num_kv_heads.
    num_kv_heads: int = 0
    # Sliding-window (local) attention span; None = full causal.  Applies
    # to the dense non-pipelined stack (and decode): to every "attention"
    # layer of ``layer_mixers``, to no "attention_full" or "cross" layer.
    attn_window: Any = None
    # Position encoding: "learned" absolute table (the default), "rope"
    # rotary relative positions applied inside full attention (the other
    # mixers of ``layer_mixers`` take no positions: Olmo-Hybrid), or
    # "none" (a stack whose mixers carry the order themselves: Kimi
    # Linear).
    pos_encoding: str = "learned"
    rope_theta: float = 10000.0
    # What the architecture states beyond the GPT-2 block (defaults: that
    # block).  ``norm``: "layernorm" or "rmsnorm", ``norm_eps`` None =
    # flax's 1e-6; ``use_bias`` on every projection and the head;
    # ``qk_norm``: RMSNorm on the query and key projections.
    norm: str = "layernorm"
    norm_eps: Optional[float] = None
    use_bias: bool = True
    qk_norm: bool = False
    # Each norm before its sub-layer ("pre") or on its output inside the
    # residual branch ("post", the OLMo family's); full attention's head
    # size where it is not ``d_model / num_heads`` (0).
    norm_placement: str = "pre"
    head_dim: int = 0
    # Experts (``num_experts`` > 0, each ``d_ff`` wide): "switch" routing
    # (top-1, capacity, ReLU experts) or "topk" (softmax then
    # ``moe_top_k``, experts of ``moe_expert``'s kind, no token dropped); in every other
    # block ("alternate", the Switch placement) or in "all".
    moe_router: str = "switch"
    moe_top_k: int = 1
    moe_layers: str = "alternate"
    moe_z_loss_weight: float = 0.0
    moe_aux_loss_weight: float = 0.01
    # Under ``moe_layers="all"``: this many leading layers keep a dense
    # feed-forward.  The top-k router's scores ("softmax", or a "sigmoid"
    # of each logit), renormalised over the chosen experts or not, times
    # ``moe_routed_scale``; shared experts beside the routed ones; and the
    # range ``(first, count)`` of the ``num_experts`` router outputs whose
    # experts this program holds (None: all of them).
    moe_first_dense: int = 0
    moe_scoring: str = "softmax"
    moe_renormalize: bool = False
    moe_routed_scale: float = 1.0
    moe_shared_experts: int = 0
    moe_held: Any = None
    # An expert (routed or shared): "gated_silu", three matrices, or
    # "relu2", two around a squared ReLU; a shared expert's width where it
    # is not a routed one's ``d_ff`` (0).
    moe_expert: str = "gated_silu"
    moe_shared_d_ff: int = 0
    # The dense feed-forward: the "gelu" MLP, the bias-free "gated_silu"
    # one or the "relu2" MLP, ``dense_d_ff`` wide where that differs from
    # an expert's ``d_ff`` (0: the same).
    mlp: str = "gelu"
    dense_d_ff: int = 0
    # Each layer's token mixer, "attention" | "attention_full" | "cross" |
    # "kda" | "gdn" | "mla" | "ssm" | "mamba1" | "gmu" (None: "attention"
    # everywhere), and the sizes models/mixers.py takes.  A "gmu" layer
    # reads the nearest earlier "mamba1" layer's scan output, a "cross"
    # layer the nearest earlier "attention_full" layer's keys and values.
    # A layer named so is the mixer and then the feed-forward;
    # "<mixer>_only" is a layer that is the mixer alone behind its one
    # norm, "ffn_only" one that is the feed-forward alone (dense or
    # experts, by ``moe_layers`` as everywhere): the Nemotron-H family's
    # layers are one sub-layer each.
    layer_mixers: Any = None
    kda_num_heads: int = 0  # 0: num_heads
    kda_head_dim: int = 128
    kda_conv_size: int = 4
    gdn_num_heads: int = 0  # 0: num_heads; the heads held here
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    gdn_conv_size: int = 4
    mla_kv_lora_rank: int = 512
    mla_nope_dim: int = 128
    mla_rope_dim: int = 64
    mla_v_dim: int = 128
    ssm_num_heads: int = 64
    ssm_head_dim: int = 64
    ssm_state_dim: int = 128
    ssm_num_groups: int = 1  # of heads that share a B and a C
    ssm_conv_size: int = 4
    ssm_chunk: int = 256  # the scan's chunk: the program's, not the model's
    mamba1_inner: int = 0  # 0: 2 x d_model
    mamba1_state_dim: int = 16
    mamba1_conv_size: int = 4
    mamba1_dt_rank: int = 0  # 0: ceil(d_model / 16)
    mamba1_chunk: int = 256  # the plain scan's chunk: the program's
    # Differential attention in every attention layer, cross-attention
    # included (``SelfAttention.diff_lambda_init``); ``lambda_init`` is 0.8
    # - 0.6 exp(-0.3 l) at the layer's published index l: ``layer_ids``
    # where this stack is a cut of a deeper one (None: 0, 1, 2, ...).
    attn_differential: bool = False
    layer_ids: Any = None
    # Biases on the attention projections where that is not ``use_bias``
    # (None): a tied head has none, the attention of the model may.
    attn_bias: Optional[bool] = None
    # Granite's four scalars: the embedding times ``embedding_multiplier``,
    # each sub-layer's output times ``residual_multiplier`` before it
    # joins the residual, attention scores times ``attention_multiplier``
    # (None: head size ** -0.5), logits over ``logits_scaling``.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: Optional[float] = None
    logits_scaling: float = 1.0
    # The head is the embedding matrix (no ``head`` parameter, no bias):
    # ``logits = E norm(h)``.
    tie_embeddings: bool = False

    def _mixers(self) -> tuple:
        return tuple(self.layer_mixers or ("attention",) * self.num_layers)

    @staticmethod
    def _halves(entry: str) -> tuple:
        """``(mixer, feed)`` of one entry of ``layer_mixers``."""
        if entry == "ffn_only":
            return "none", True
        if entry.endswith("_only"):
            return entry[: -len("_only")], False
        return entry, True

    def _sources(self) -> dict:
        """``{reader layer: source layer}``: for each layer that reads
        what an earlier one made, the nearest earlier layer of the kind it
        reads (``_READS``); a reader without one raises."""
        kinds = [self._halves(entry)[0] for entry in self._mixers()]
        sources = {}
        for i, kind in enumerate(kinds):
            if kind in _READS:
                earlier = [j for j in range(i) if kinds[j] == _READS[kind]]
                if not earlier:
                    raise ValueError(
                        f"layer_mixers[{i}] {kind!r} reads an earlier "
                        f"{_READS[kind]!r} layer and there is none"
                    )
                sources[i] = earlier[-1]
        return sources

    def _check_settings(self):
        """Refusals that depend on no input: raised when the model is
        first called, before anything is traced."""
        for name, value, known in (
            ("pos_encoding", self.pos_encoding, ("learned", "rope", "none")),
            ("norm", self.norm, ("layernorm", "rmsnorm")),
            ("norm_placement", self.norm_placement, ("pre", "post")),
            ("moe_router", self.moe_router, ("switch", "topk")),
            ("moe_layers", self.moe_layers, ("alternate", "all")),
            ("moe_scoring", self.moe_scoring, ("softmax", "sigmoid")),
            ("mlp", self.mlp, ("gelu", "gated_silu", "relu2")),
            ("moe_expert", self.moe_expert, ("gated_silu", "relu2")),
            *(
                (f"layer_mixers[{i}]", m, _LAYER_KINDS)
                for i, m in enumerate(self._mixers())
            ),
        ):
            if value not in known:
                raise ValueError(f"unknown {name} {value!r} (want one of {known})")
        if len(self._mixers()) != self.num_layers:
            raise ValueError(
                f"layer_mixers names {len(self._mixers())} layers, "
                f"num_layers is {self.num_layers}"
            )
        kinds = {self._halves(entry)[0] for entry in self._mixers()}
        handing = kinds & {"attention_full", "cross", "mamba1", "gmu"}
        if (handing or self.attn_differential) and (
            self.decode or self.attention_fn is not None
            or self.pipelined or self.pipe_mesh is not None
        ):
            raise ValueError(
                f"{sorted(handing) or 'differential attention'} neither "
                "decodes nor runs in the pipelined stack or under a "
                "sequence-parallel attention_fn: serving/kv_slots.py gives "
                "every layer keys and values of its own (here one layer's "
                "are read by several, and a query-only layer has none), has "
                "no per-channel recurrent state (d_inner x state with the "
                "convolution's tail) nor the scan output a gated memory "
                "unit reads, and the cache holds no pair of softmaxes; "
                "PipelinedBlocks ships the residual stream alone between "
                "stages, not what a source layer hands on (ROADMAP Queue 2, "
                "M11)"
            )
        self._sources()
        if ("cross" in kinds or self.attn_differential) and (
            self.pos_encoding == "rope" or self.qk_norm
        ):
            raise ValueError(
                "differential attention and the layers that hand on or read "
                "keys and values rotate and norm no query or key "
                "(SelfAttention._core): pos_encoding='rope' and qk_norm "
                "would be dropped in silence"
            )
        if self.layer_ids is not None and len(self.layer_ids) != self.num_layers:
            raise ValueError(
                f"layer_ids names {len(self.layer_ids)} layers, "
                f"num_layers is {self.num_layers}"
            )
        plain = set(self._mixers()) == {"attention"}
        if not plain and (self.decode or self.attention_fn is not None):
            raise ValueError(
                "the kda, gdn, mla and ssm mixers neither decode nor take a "
                "sequence-parallel attention_fn: the recurrent states (a "
                "state-space layer's 128 x 64 a head with its convolution's "
                "3-token tail of 4352 channels among them) and the latent "
                "cache have no place in serving/kv_slots.py yet (ROADMAP "
                "Queue 2)"
            )
        if self.decode and self.norm_placement != "pre":
            raise ValueError(
                "norm_placement='post' does not decode: the serving "
                "programs and harness/generate.py have run pre-norm blocks "
                "only, and the one model that states it (olmo_hybrid) has "
                "mixers without a decode path (ROADMAP Queue 2)"
            )
        gpt2_block = (
            self.norm == "layernorm"
            and self.norm_eps is None
            and self.use_bias
            and not self.qk_norm
            and plain
            and self.mlp == "gelu"
            and self.pos_encoding != "none"
            and self.norm_placement == "pre"
            and not self.head_dim
            and not self.tie_embeddings
            and (
                self.embedding_multiplier, self.residual_multiplier,
                self.attention_multiplier, self.logits_scaling,
            ) == (1.0, 1.0, None, 1.0)
        )
        if (self.pipelined or self.pipe_mesh is not None) and not gpt2_block:
            raise ValueError(
                "the pipelined block stack is the GPT-2 block only "
                "(pre-LayerNorm, biases, GELU MLP): norm/norm_eps/"
                "norm_placement/use_bias/qk_norm/head_dim, the four "
                "multipliers, a tied head and experts are "
                "not plumbed into the stacked layout (ROADMAP D3)"
            )
        if self.decode and self.num_experts and self.moe_router != "topk":
            raise ValueError(
                "decode mode does not run Switch experts (capacity is "
                "counted per training batch); top-k experts decode"
            )
        if self.tie_embeddings and self.use_bias:
            raise ValueError(
                "tie_embeddings shares the embedding matrix with the head, "
                "which then has no bias: set use_bias=False"
            )

    @nn.compact
    def __call__(
        self, tokens, carry=None, train: bool = False,
        return_hidden: bool = False,
    ):
        """``return_hidden=True`` returns the post-``ln_f`` hidden states
        instead of logits, for the fused chunked unembed+xent loss
        (:func:`...ops.losses.fused_unembed_mean_xent`) — the head parameters
        still exist (init uses the default path) and the loss consumes
        them directly from ``params`` (under ``tie_embeddings`` the
        embedding matrix, there is no ``head``); ``logits_scaling`` is
        already in the hidden states."""
        self._check_settings()
        B, T = tokens.shape
        # TokenEmbed == nn.Embed (same param path/init/dtype promotion)
        # plus the selectable backward lowering: DTM_EMBED_GRAD=matmul
        # swaps the gather's scatter-add gradient for the chunked
        # one-hot matmul (ops/embed.py) — the A/B the transformer_parts
        # frozen_embed ablation motivates.
        embed = TokenEmbed(
            self.vocab_size,
            self.d_model,
            dtype=self.dtype,
            name="embedding",
        )
        x = embed(tokens)
        if self.embedding_multiplier != 1.0:
            x = self.embedding_multiplier * x
        if self.pos_encoding in ("rope", "none"):
            # Relative positions enter inside attention (q/k rotation), or
            # nowhere; no absolute table.  Decode still tracks pos_index: the
            # attention blocks' cache_index carries the offset, but
            # keeping this counter preserves one cache layout invariant
            # across both encodings.
            if self.decode:
                pi = self.variable(
                    "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
                )
                pi.value = pi.value + T
        else:
            pos = self.param(
                "pos_embedding",
                nn.initializers.normal(0.02),
                (self.max_len, self.d_model),
            )
            if self.decode:
                # Tokens sit at global positions pos_index..pos_index+T-1.
                pi = self.variable(
                    "cache", "pos_index", lambda: jnp.zeros((), jnp.int32)
                )
                x = x + jax.lax.dynamic_slice_in_dim(
                    pos, pi.value, T, 0
                ).astype(self.dtype)
                pi.value = pi.value + T
            else:
                x = x + pos[:T].astype(self.dtype)
        if self.dropout_rate:
            x = nn.Dropout(self.dropout_rate, deterministic=not train)(x)
        if self.decode and (
            self.pipelined
            or self.pipe_mesh is not None
            or self.attention_fn is not None
        ):
            raise ValueError(
                "decode mode supports the non-pipelined stack "
                "without a sequence-parallel attention_fn"
            )
        if self.attn_window is not None and self.attention_fn is not None:
            raise ValueError(
                "attn_window is not threaded through the harness's "
                "sequence-parallel attention_fn closures — training "
                "would use full causal attention while decode applies "
                "the window.  (ring_attention/ulysses_attention DO "
                "accept window= at the library level; pass a closure "
                "that sets it and leave attn_window unset here.)"
            )
        if self.pipelined or self.pipe_mesh is not None:
            if (
                self.num_experts
                or self.remat
                or self.num_kv_heads
                or self.attn_window is not None
                or self.pos_encoding != "learned"
            ):
                raise ValueError(
                    "pipelined path supports dense MHA blocks with "
                    "remat=False, full causal attention, and learned "
                    "positions; num_kv_heads/attn_window/rope are not "
                    "plumbed into the stacked layout — training would "
                    "silently diverge from the non-pipelined model"
                )
            x = PipelinedBlocks(
                self.num_layers,
                self.num_heads,
                self.d_model,
                self.d_ff,
                self.dtype,
                self.attn_impl,
                self.pipe_mesh,
                self.pipeline_microbatches,
                self.dropout_rate,
                name="pipeline",
            )(x, train=train)
        else:
            routing = None
            if (self.moe_scoring, self.moe_renormalize, self.moe_routed_scale) != (
                "softmax", False, 1.0,
            ):
                from distributed_tensorflow_models_tpu.parallel import moe as moelib

                routing = moelib.Routing(
                    self.moe_scoring, self.moe_renormalize, self.moe_routed_scale
                )
            mixer_kwargs = {
                "kda": (
                    ("num_heads", self.kda_num_heads or self.num_heads),
                    ("head_dim", self.kda_head_dim),
                    ("conv_size", self.kda_conv_size),
                ),
                "gdn": (
                    ("num_heads", self.gdn_num_heads or self.num_heads),
                    ("key_dim", self.gdn_key_dim),
                    ("value_dim", self.gdn_value_dim),
                    ("conv_size", self.gdn_conv_size),
                ),
                "mla": (
                    ("kv_lora_rank", self.mla_kv_lora_rank),
                    ("nope_dim", self.mla_nope_dim),
                    ("rope_dim", self.mla_rope_dim),
                    ("v_dim", self.mla_v_dim),
                ),
                "ssm": (
                    ("num_heads", self.ssm_num_heads),
                    ("head_dim", self.ssm_head_dim),
                    ("state_dim", self.ssm_state_dim),
                    ("num_groups", self.ssm_num_groups),
                    ("conv_size", self.ssm_conv_size),
                    ("chunk", self.ssm_chunk),
                ),
                "mamba1": (
                    ("d_inner", self.mamba1_inner or 2 * self.d_model),
                    ("state_dim", self.mamba1_state_dim),
                    ("dt_rank", self.mamba1_dt_rank or -(-self.d_model // 16)),
                    ("conv_size", self.mamba1_conv_size),
                    ("chunk", self.mamba1_chunk),
                ),
            }
            # What the source layers hand on, beside ``x`` down the stack:
            # ``handed[j]`` is layer j's memory, or its keys and values.
            sources, handed = self._sources(), {}
            hands_on = set(sources.values())
            layer_ids = self.layer_ids or range(self.num_layers)
            for i, entry in enumerate(self._mixers()):
                mixer, feed = self._halves(entry)
                lambda_init = None
                if self.attn_differential and mixer in _ATTENTIONS:
                    lambda_init = 0.8 - 0.6 * math.exp(-0.3 * layer_ids[i])
                use_moe = self.num_experts > 0 and (
                    i >= self.moe_first_dense
                    if self.moe_layers == "all"
                    else i % 2 == 1
                )
                x = Block(
                    self.num_heads,
                    self.d_model,
                    self.d_ff if use_moe else self.dense_d_ff or self.d_ff,
                    self.dropout_rate,
                    self.dtype,
                    self.attn_impl,
                    self.attention_fn,
                    use_moe=use_moe,
                    num_experts=self.num_experts,
                    moe_mesh=self.moe_mesh,
                    moe_capacity_factor=self.moe_capacity_factor,
                    decode=self.decode,
                    max_len=self.max_len,
                    num_kv_heads=self.num_kv_heads,
                    attn_window=self.attn_window,
                    use_rope=self.pos_encoding == "rope",
                    rope_theta=self.rope_theta,
                    norm=self.norm,
                    norm_eps=self.norm_eps,
                    use_bias=self.use_bias,
                    qk_norm=self.qk_norm,
                    moe_router=self.moe_router,
                    moe_top_k=self.moe_top_k,
                    moe_z_loss_weight=self.moe_z_loss_weight,
                    moe_aux_loss_weight=self.moe_aux_loss_weight,
                    moe_routing=routing,
                    moe_held=self.moe_held and tuple(self.moe_held),
                    moe_shared_experts=self.moe_shared_experts,
                    moe_expert=self.moe_expert,
                    moe_shared_d_ff=self.moe_shared_d_ff,
                    mixer=mixer,
                    mixer_kwargs=mixer_kwargs.get(mixer),
                    feed=feed,
                    head_dim=self.head_dim,
                    attn_scale=self.attention_multiplier,
                    residual_multiplier=self.residual_multiplier,
                    norm_placement=self.norm_placement,
                    mlp=self.mlp,
                    remat=self.remat,
                    hands_on=i in hands_on,
                    diff_lambda_init=lambda_init,
                    attn_bias=self.attn_bias,
                    name=f"blocks_{i}",
                )(x, train, *((handed[sources[i]],) if i in sources else ()))
                if i in hands_on:
                    x, handed[i] = x
        x = make_norm(self.norm, self.norm_eps, "ln_f")(x)
        if self.logits_scaling != 1.0:
            # Folded into the hidden states, so that the fused head sees
            # it too (a power of two, Granite's 8, is exact in any dtype).
            x = x / self.logits_scaling
        if return_hidden:
            return x, carry
        if self.tie_embeddings:
            return embed.attend(x), carry
        logits = nn.Dense(
            self.vocab_size,
            dtype=jnp.float32,
            use_bias=self.use_bias,
            name="head",
        )(x)
        return logits, carry


@register("transformer_lm")
def build_transformer_lm(**kwargs) -> TransformerLM:
    return TransformerLM(**kwargs)
