"""Plain reference of the ``granite_h_micro`` configuration: forward,
loss, and through ``jax.grad`` its gradients.

granite-4.0-h-micro (``config.json`` of ibm-granite/granite-4.0-h-micro,
``model_type`` ``granitemoehybrid``; the state-space layers are Mamba-2:
Dao and Gu 2024, "Transformers are SSMs", arXiv:2405.21060, in the order
of the ``granitemoehybrid`` Mamba layer of transformers, which follows
``mamba_ssm``'s Mamba-2 block; layer equations as in ISSUE 38) in
straightforward ``jax.numpy`` and float32, matrix products at precision
``highest``.  No kernels and no chunks: the state-space layer is its
recurrence token by token, the attention a full score matrix a head with
the keys and values repeated over their groups.

``x`` a layer's input, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w`` with
``eps`` 1e-5, no bias in any projection:

- the stack: ``h_0 = embedding_multiplier * E[tokens]`` (12); per layer
  ``u = h + r * mixer(RMSNorm_a(h))``, ``h' = u + r * ffn(RMSNorm_f(u))``
  (pre-norm; ``residual_multiplier`` ``r`` = 0.22 on both branches);
  ``ffn(x) = W_down (silu(W_gate x) * (W_up x))``, inner width 8192;
  ``logits = E RMSNorm(h_L) / logits_scaling`` (8) with the **same**
  ``E`` (``tie_word_embeddings``).  A layer's kind (``layer_types``:
  1-based layers 6, 16, 26, 36 ``attention``, the others ``mamba``) is
  read off its parameters.
- ``mamba`` (``d_inner`` = 2 x 2048 = 4096 = ``H`` = 64 heads of ``P`` =
  64, state ``N`` = 128, one group): ``[z, xBC, dt] = W_in x`` (4096,
  4096 + 2 x 128 and 64 channels, in that order); ``xBC = silu(conv4(xBC)
  + b_conv)``, causal and depth-wise, one weight per channel and tap;
  ``[x, B, C] = split(xBC)`` (4096, 128, 128); ``dt_t = softplus(dt_t +
  dt_bias)`` (no clamp) and ``a_t = exp(-exp(A_log) * dt_t)``, one number
  a head and token; per head ``S_t = a_t S_{t-1} + dt_t B_t x_t^T``
  (``[N, P]``; ``B`` and ``C`` are the same vectors for all heads), ``y_t
  = S_t^T C_t + D x_t`` (``D`` one number a head); ``out = W_out (w *
  RMSNorm0(y * silu(z)))``: the gate first, then the norm, its mean
  square over all 4096 channels.
- ``attention``: 32 query heads and 8 key/value heads of 64 (query head
  ``i`` reads key/value head ``i // 4``), no positions of any kind,
  causal softmax of ``(q . k) * attention_multiplier`` (0.015625 = 1/64,
  where ``64^-0.5`` would be the default), ``W_o``.
- loss: mean token cross entropy, no other term.

No share: the configuration keeps every head and every width (its cut is
depth and vocabulary rows), so there is no partial sum here.

It takes the parameter tree of ``models/transformer_lm.py`` as it is
(``blocks_<i>/{ln1, ssm | attn, ln2, mlp}``, ``embedding``, ``ln_f``; no
``head``).  The only structure it shares with the program: the gradient
through the recurrence recomputes in blocks of ``RECOMPUTE`` tokens (the
recurrence itself is token by token), the score matrices are taken one
head after the other, and each half of a layer is recomputed in the
backward pass (``jax.checkpoint``: ten layers of float32 activations at
8,192 positions are 20 GB), so that it fits a chip.

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, ``dt``, the decay, the
recurrent state, the softmax and the logits.
``benchmark/tools/compare_reference_granite_h.py`` runs it once in
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


def _gated(h, p):
    return _matmul(
        jax.nn.silu(_matmul(h, p["gate"]["kernel"])) * _matmul(h, p["up"]["kernel"]),
        p["down"]["kernel"],
    )


def _conv(x, w, bias):
    """Causal depthwise convolution with a bias: ``y_t = sum_j w[j]
    x_{t-(K-1)+j} + b``."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, j : j + t] for j in range(taps)], axis=2)
    return jnp.einsum("btkc,kc->btc", windows, w, precision=_HI) + bias


def state_space(x, dt, a, b, c):
    """``y_t = S_t^T C_t`` of ``S_t = a_t S_{t-1} + dt_t B_t x_t^T``, token
    by token.  ``x`` ``[batch, time, heads, P]``, ``dt``, ``a`` ``[batch,
    time, heads]``, ``b``, ``c`` ``[batch, time, N]``."""

    def token(S, at):
        x_t, dt_t, a_t, b_t, c_t = at
        S = a_t[..., None, None] * S + b_t[:, None, :, None] * (dt_t[..., None] * x_t)[:, :, None, :]
        return S, jnp.einsum("bn,bhnp->bhp", c_t, S, precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    t = x.shape[1]
    whole = t - t % RECOMPUTE
    xs = [jnp.moveaxis(y, 1, 0) for y in (x, dt, a, b, c)]
    S = jnp.zeros((x.shape[0], x.shape[2], b.shape[-1], x.shape[3]), x.dtype)
    outs = []
    if whole:
        blocks = [y[:whole].reshape(-1, RECOMPUTE, *y.shape[1:]) for y in xs]
        S, out = jax.lax.scan(block, S, blocks)
        outs.append(out.reshape(whole, *out.shape[2:]))
    if t - whole:
        outs.append(block(S, [y[whole:] for y in xs])[1])
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)


def mamba(h, p, eps):
    """The Mamba-2 mixer on ``h`` ``[batch, time, hidden]``; the head
    count, the head size and the state size are read off the parameters."""
    bsz, t, _ = h.shape
    heads = p["A_log"].shape[0]
    inner = p["norm"]["scale"].shape[0]
    state = (p["conv"].shape[1] - inner) // 2
    zxbcdt = _matmul(h, p["in_proj"]["kernel"])
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * state], axis=-1)
    xbc = jax.nn.silu(_conv(xbc, p["conv"], p["conv_bias"]))
    x, b, c = jnp.split(xbc, [inner, inner + state], axis=-1)
    x = x.reshape(bsz, t, heads, -1)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-jnp.exp(p["A_log"]) * dt)
    y = state_space(x, dt, a, b, c) + p["D"][:, None] * x
    y = y.reshape(bsz, t, inner) * jax.nn.silu(z)
    return _matmul(_rms_norm(y, p["norm"], eps), p["out_proj"]["kernel"])


def attention(h, p, num_heads, num_kv_heads, scale):
    """Causal softmax attention without positions over grouped key/value
    heads: query head ``i`` reads key/value head ``i // (num_heads /
    num_kv_heads)``."""
    b, t, _ = h.shape
    group = num_heads // num_kv_heads
    q = _matmul(h, p["query"]["kernel"]).reshape(b, t, num_heads, -1)
    k = _matmul(h, p["key"]["kernel"]).reshape(b, t, num_kv_heads, -1)
    v = _matmul(h, p["value"]["kernel"]).reshape(b, t, num_kv_heads, -1)
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
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


def forward(params, tokens, *, num_heads: int = 32, num_kv_heads: int = 8,
            eps: float = 1e-5, embedding_multiplier: float = 12.0,
            residual_multiplier: float = 0.22,
            attention_multiplier: float = 0.015625,
            logits_scaling: float = 8.0, dtype=jnp.float32):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    table = params["embedding"]["embedding"]
    x = embedding_multiplier * table[tokens]

    @jax.checkpoint
    def mixer_half(x, p):
        h = _rms_norm(x, p["ln1"], eps)
        if "ssm" in p:
            return mamba(h, p["ssm"], eps)
        return attention(h, p["attn"], num_heads, num_kv_heads, attention_multiplier)

    @jax.checkpoint
    def feed_forward_half(x, p):
        return _gated(_rms_norm(x, p["ln2"], eps), p["mlp"])

    layer = 0
    while f"blocks_{layer}" in params:
        p = params[f"blocks_{layer}"]
        x = x + residual_multiplier * mixer_half(x, p)
        x = x + residual_multiplier * feed_forward_half(x, p)
        layer += 1
    return _matmul(_rms_norm(x, params["ln_f"], eps), table.T) / logits_scaling


def loss(params, tokens, targets, **kwargs):
    """``(total, parts)``: mean next-token cross entropy in nats (there is
    no other term); ``parts`` holds ``nll``.  ``kwargs`` as
    :func:`forward`'s."""
    logits = forward(params, tokens, **kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll, {"nll": nll}
