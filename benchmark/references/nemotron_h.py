"""Plain reference of the ``nemotron3_nano`` configuration: forward, loss,
and through ``jax.grad`` its gradients.

NVIDIA-Nemotron-3-Nano-30B-A3B (``config.json`` of
nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, ``model_type`` ``nemotron_h``;
the block is Nemotron-H's, NVIDIA 2025, arXiv:2504.03624; the state-space
layers are Mamba-2: Dao and Gu 2024, arXiv:2405.21060, laid out as
``mamba_ssm``'s Mamba-2 block; the router is DeepSeek-V3's,
arXiv:2412.19437, with one group; layer equations as in ISSUE 40) in
straightforward ``jax.numpy`` and float32, matrix products at precision
``highest``.  No kernels, no chunks, no sort, no grouped product: the
state-space layer is its recurrence token by token, the attention a full
score matrix a head with the keys and values repeated over their groups,
and every held expert is applied to every token and masked by the choice.

``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w`` with ``eps`` 1e-5, no bias
in any projection, no multiplier anywhere:

- the stack: ``h_0 = E[tokens]``; **each layer is one sub-layer alone**,
  ``h' = h + F(RMSNorm(h))``, ``F`` a Mamba-2 mixer (``M`` of
  ``hybrid_override_pattern``), an expert feed-forward (``E``), attention
  (``*``) or a dense feed-forward (``-``; none in this model), read off
  the layer's parameters; ``logits = W_head RMSNorm(h_L)``, untied.
- ``M`` (``d_inner`` = 64 heads of ``P`` = 64 = 4096, state ``N`` = 128,
  ``G`` = 8 groups): ``[z, xBC, dt] = W_in u`` (4096, 4096 + 2 x 8 x 128
  = 6144 and 64 channels, in that order); ``xBC = silu(conv4(xBC) +
  b_conv)``, causal and depth-wise; ``[x, B, C] = split(xBC)`` (4096,
  1024, 1024; ``B`` and ``C`` ``[G, N]``); ``dt_t = softplus(dt_t +
  dt_bias)`` (no clamp), ``a_t = exp(-exp(A_log) * dt_t)``, one number a
  head and token; per head ``h`` of group ``g = h // 8``: ``S_t = a_t
  S_{t-1} + dt_t B_{g,t} x_{h,t}^T`` (``[N, P]``), ``y_{h,t} = S_t^T
  C_{g,t} + D_h x_{h,t}``; ``y = y * silu(z)``, then ``y = w * y /
  sqrt(mean_512(y^2) + eps)`` **over each group's 512 channels**
  (``mamba_ssm``'s ``RMSNormGated`` with ``group_size = d_inner /
  n_groups``, the gate first); ``out = W_out y``.
- ``*``: 32 query heads and 2 key/value heads of 128 (query head ``i``
  reads key/value head ``i // 16``), **no positions of any kind**, causal
  softmax of ``(q . k) * 128^-0.5``, ``W_o`` from 4096 channels to 2688.
- ``E``: ``s = sigmoid(W_r u)`` over all the router's 128 experts; the
  ``top_k`` (6) largest, ties to the lower index (``n_group`` 1: the
  group limit is no limit; the selection bias is a buffer held at zero);
  weights ``routed_scale`` (2.5) ``* s_e / sum(chosen s)``; ``y = sum over
  the chosen experts that are held of w_e W2_e relu(W1_e u)^2`` (**two**
  matrices, no gate), plus the shared expert ``W2_s relu(W1_s u)^2`` on
  every token.  The expert stacks hold ``count`` experts, ``held_first``
  onwards, of the router's: what the absent ones would add is left out,
  as in the program (one chip's share of the layer).
- ``-``: ``W2 relu(W1 u)^2``.
- loss: mean token cross entropy, no other term.

It takes the parameter tree of ``models/transformer_lm.py`` as it is
(``blocks_<i>/{ln1, ssm | attn}`` or ``blocks_<i>/{ln2, moe | mlp}``,
``embedding``, ``ln_f``, ``head``) and the same held range and vocabulary
slice.  The only structure it shares with the program: the gradient
through the recurrence recomputes in blocks of ``RECOMPUTE`` tokens (the
recurrence itself is token by token), the score matrices are taken one
head after the other and one expert after the other, and each layer is
recomputed in the backward pass (``jax.checkpoint``), so that it fits a
chip.

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, ``dt``, the decay, the
recurrent state, the router, the softmax and the logits.
``benchmark/tools/compare_reference_nemotron_h.py`` runs it once in
bfloat16, the nearest precision below what the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
RECOMPUTE = 128


def _matmul(x, w):
    return jnp.matmul(x, w, precision=_HI)


def _rms_norm(x, p, eps):
    mean_square = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * p["scale"]


def _relu2(h, up, down):
    return _matmul(jnp.square(jax.nn.relu(_matmul(h, up))), down)


def _conv(x, w, bias):
    """Causal depthwise convolution with a bias: ``y_t = sum_j w[j]
    x_{t-(K-1)+j} + b``."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, j : j + t] for j in range(taps)], axis=2)
    return jnp.einsum("btkc,kc->btc", windows, w, precision=_HI) + bias


def state_space(x, dt, a, b, c):
    """``y_t = S_t^T C_t`` of ``S_t = a_t S_{t-1} + dt_t B_t x_t^T``, token
    by token.  ``x`` ``[batch, time, groups, heads a group, P]``, ``dt``,
    ``a`` ``[batch, time, groups, heads a group]``, ``b``, ``c`` ``[batch,
    time, groups, N]``: a group's heads read its ``B`` and ``C``."""

    def token(S, at):
        x_t, dt_t, a_t, b_t, c_t = at
        write = jnp.einsum("bgn,bgjp->bgjnp", b_t, dt_t[..., None] * x_t, precision=_HI)
        S = a_t[..., None, None] * S + write
        return S, jnp.einsum("bgn,bgjnp->bgjp", c_t, S, precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    t = x.shape[1]
    whole = t - t % RECOMPUTE
    xs = [jnp.moveaxis(y, 1, 0) for y in (x, dt, a, b, c)]
    S = jnp.zeros((x.shape[0], *x.shape[2:4], b.shape[-1], x.shape[4]), x.dtype)
    outs = []
    if whole:
        blocks = [y[:whole].reshape(-1, RECOMPUTE, *y.shape[1:]) for y in xs]
        S, out = jax.lax.scan(block, S, blocks)
        outs.append(out.reshape(whole, *out.shape[2:]))
    if t - whole:
        outs.append(block(S, [y[whole:] for y in xs])[1])
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)


def mamba(h, p, groups, eps):
    """The Mamba-2 mixer on ``h`` ``[batch, time, hidden]`` with ``groups``
    groups of heads; the head count, the head size and the state size are
    read off the parameters."""
    bsz, t, _ = h.shape
    heads = p["A_log"].shape[0]
    inner = p["norm"]["scale"].shape[0]
    state = (p["conv"].shape[1] - inner) // (2 * groups)
    zxbcdt = _matmul(h, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, p["conv"].shape[1] + inner], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + groups * state], axis=-1)
    by_group = lambda y: y.reshape(bsz, t, groups, heads // groups, *y.shape[3:])
    x = by_group(x.reshape(bsz, t, heads, -1))
    b, c = (y.reshape(bsz, t, groups, state) for y in (b, c))
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)
    y = state_space(x, by_group(dt), by_group(a), b, c)
    y = y + p["D"].reshape(groups, heads // groups, 1) * x
    # The gate first, then the norm over each group's own channels.
    y = y.reshape(bsz, t, groups, inner // groups) * jax.nn.silu(z).reshape(
        bsz, t, groups, inner // groups
    )
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    return _matmul(y.reshape(bsz, t, inner) * p["norm"]["scale"], p["out_proj"]["kernel"])


def attention(h, p, num_heads, num_kv_heads):
    """Causal softmax attention without positions over grouped key/value
    heads: query head ``i`` reads key/value head ``i // (num_heads /
    num_kv_heads)``; the head size is read off the parameters."""
    b, t, _ = h.shape
    group = num_heads // num_kv_heads
    q = _matmul(h, p["query"]["kernel"]).reshape(b, t, num_heads, -1)
    k = _matmul(h, p["key"]["kernel"]).reshape(b, t, num_kv_heads, -1)
    v = _matmul(h, p["value"]["kernel"]).reshape(b, t, num_kv_heads, -1)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    scale = q.shape[-1] ** -0.5
    causal = jnp.tril(jnp.ones((t, t), bool))

    # One head's [time, time] scores at a time, recomputed in the backward
    # pass: 32 heads of 8192 x 8192 float32 are 8.6 GB.
    @jax.checkpoint
    def one_head(x):
        q_h, k_h, v_h = x  # [b, t, D]
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision=_HI) * scale
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v_h, precision=_HI)

    heads_first = lambda y: jnp.moveaxis(y, 2, 0)
    out = jax.lax.map(one_head, (heads_first(q), heads_first(k), heads_first(v)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, t, -1)
    return _matmul(out, p["out"]["kernel"])


def top_k_mask(scores, top_k):
    """``[n, E]`` bool: the ``top_k`` largest of each row, ties to the
    lower index (a stable sort of the negated scores)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return rank < top_k


def experts(h, p, top_k, routed_scale, held_first):
    """``h`` [n, d] -> the layer's output, the choice ``[n, E]`` and the
    share of the assignments that fell on held experts."""
    count = p["w_up"].shape[0]
    scores = jax.nn.sigmoid(_matmul(h, p["router"]))
    chosen = top_k_mask(scores, top_k)
    weight = jnp.where(chosen, scores, 0.0)
    weight = routed_scale * weight / jnp.sum(weight, axis=-1, keepdims=True)
    held = weight[:, held_first : held_first + count]

    @jax.checkpoint
    def one(w_up, w_down, w):
        return _relu2(h, w_up, w_down) * w[:, None]

    def add(acc, per_expert):
        return acc + one(*per_expert), None

    out, _ = jax.lax.scan(add, jnp.zeros_like(h), (p["w_up"], p["w_down"], held.T))
    if "shared" in p:
        out = out + _relu2(h, p["shared"]["up"]["kernel"], p["shared"]["down"]["kernel"])
    share = jnp.sum(chosen[:, held_first : held_first + count]) / (h.shape[0] * top_k)
    return out, chosen, share


def _forward(params, tokens, *, num_heads=32, num_kv_heads=2, ssm_groups=8, top_k=6,
             routed_scale=2.5, held_first=0, eps=1e-5, dtype=jnp.float32):
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embedding"]["embedding"][tokens]
    b, t, d = x.shape

    @jax.checkpoint
    def sub_layer(x, p):
        """``(F(RMSNorm(x)), the experts' choice or None, held share)``."""
        if "ssm" in p:
            return mamba(_rms_norm(x, p["ln1"], eps), p["ssm"], ssm_groups, eps), None, None
        if "attn" in p:
            h = _rms_norm(x, p["ln1"], eps)
            return attention(h, p["attn"], num_heads, num_kv_heads), None, None
        h = _rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            y, chosen, share = experts(
                h.reshape(b * t, d), p["moe"], top_k, routed_scale, held_first
            )
            return y.reshape(b, t, d), chosen, share
        mlp = p["mlp"]
        return _relu2(h, mlp["up"]["kernel"], mlp["down"]["kernel"]), None, None

    chosen, shares = [], []
    layer = 0
    while f"blocks_{layer}" in params:
        y, c, share = sub_layer(x, params[f"blocks_{layer}"])
        x = x + y
        if c is not None:
            chosen.append(c)
            shares.append(share)
        layer += 1
    logits = _matmul(_rms_norm(x, params["ln_f"], eps), params["head"]["kernel"])
    return logits, chosen, shares


def forward(params, tokens, **kwargs):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``.
    ``kwargs``: ``num_heads`` 32, ``num_kv_heads`` 2, ``ssm_groups`` 8,
    ``top_k`` 6, ``routed_scale`` 2.5, ``held_first`` 0, ``eps`` 1e-5,
    ``dtype`` float32."""
    return _forward(params, tokens, **kwargs)[0]


def routing(params, tokens, **kwargs):
    """Per expert layer, the ``[batch * time, experts]`` bool of chosen
    experts (over all the router's experts, held or not)."""
    return _forward(params, tokens, **kwargs)[1]


def loss(params, tokens, targets, **kwargs):
    """``(total, parts)``: mean next-token cross entropy in nats (there is
    no other term); ``parts`` holds ``nll`` and ``held_share``, the mean
    over the expert layers of the share of assignments on held experts."""
    logits, _, shares = _forward(params, tokens, **kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll, {"nll": nll, "held_share": sum(shares) / max(len(shares), 1)}
