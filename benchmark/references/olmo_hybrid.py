"""Plain reference of the ``olmo_hybrid`` configuration: forward, loss,
and through ``jax.grad`` its gradients.

Olmo-Hybrid-7B (``config.json`` of allenai/Olmo-Hybrid-7B; the linear
layers are the Gated DeltaNet layer that its ``linear_*`` keys name:
Yang, Kautz, Hatamizadeh 2024, "Gated Delta Networks", arXiv:2412.06464;
the block is OLMo 2's, arXiv:2501.00656; layer equations as in ISSUE 32)
in straightforward ``jax.numpy`` and float32, matrix products at
precision ``highest``.  No kernels and no chunks: the linear attention
is its recurrence token by token, the attention a full score matrix a
head.

``x`` a layer's input, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``,
no bias anywhere:

- the stack: ``h = x + RMSNorm_a(mixer(x))``, ``y = h + RMSNorm_f(ffn(h))``
  (each norm on its sub-layer's output, inside the residual branch);
  ``ffn(h) = W_down (silu(W_gate h) * (W_up h))``; a final RMSNorm and an
  untied head.  A layer's kind (``layer_types``: 1-based layers 4, 8, ...
  32 full, the others linear) is read off its parameters.
- ``linear_attention``, ``H`` heads of ``dk`` key and ``dv`` value
  channels: ``q = l2norm(silu(conv4(W_q x)))`` (``x / sqrt(sum x^2 +
  1e-6)``), ``k`` alike, ``v = silu(conv4(W_v x))``, the convolutions
  causal and depth-wise, one weight per channel and tap; ``g_t =
  -exp(A_log) * softplus(W_a x_t + dt_bias)`` and ``b_t = 2 sigmoid(W_b
  x_t)``, one number each per head and token (``b`` up to 2:
  ``linear_allow_neg_eigval``); per head ``S_t = e^{g_t} (I - b_t k_t
  k_t^T) S_{t-1} + b_t k_t v_t^T``, that is ``S_t = e^{g_t} S_{t-1} + b_t
  k_t (v_t - e^{g_t} S_{t-1}^T k_t)^T``, ``o_t = dk^-0.5 S_t^T q_t``; ``y
  = W_o (RMSNorm_head(o_t) * silu(W_g x_t))``, the norm's weight of ``dv``
  shared by the heads.
- ``full_attention``, ``H`` heads of ``D``: ``q = rope(RMSNorm(W_q x))``,
  ``k = rope(RMSNorm(W_k x))`` (the norm over the whole projection that is
  held, before the head split; the rotation pairs channel ``i`` with ``i +
  D/2``, angle ``t * theta^(-2i/D)``), causal softmax at ``D^-0.5``,
  ``W_o``.
- loss: mean token cross entropy, no other term.

The share.  The configuration holds ``H`` = 15 of a layer's 30 heads (one
chip of 2 that share each layer's heads): this file is given the same
parameters and computes the same partial sums; what the other chip's
heads would add is left out on both sides.  Every piece of the
delta-rule mixer but ``W_o``'s sum is per head, so two halves add up to
the uncut layer exactly.  In full attention one thing is not per head:
the mean square under the query and key norms, which a deployment
reduces across the two chips (one scalar per token and projection).
``qk_mean_squares`` hands :func:`full_attention` that statistic from
outside; given the uncut layer's, two halves add up to the uncut layer,
and without it (the configuration, the program) a half norms over its
own channels.  ``tests/test_olmo_hybrid.py`` holds both.

It takes the parameter tree of ``models/transformer_lm.py`` as it is.
The only structure it shares with the program: the gradient through the
recurrence recomputes in blocks of ``RECOMPUTE`` tokens (the recurrence
itself is token by token) and the score matrices are taken one head
after the other, so that it fits a chip.

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, the decay, the recurrent
state, the rotation, the softmax and the logits.
``benchmark/tools/compare_reference_olmo_hybrid.py`` runs it once in
bfloat16, the nearest precision below what the configuration states.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
RECOMPUTE = 128


def _matmul(x, w):
    return jnp.matmul(x, w, precision=_HI)


def _rms_norm(x, p, eps, mean_square=None):
    if mean_square is None:
        mean_square = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(mean_square + eps) * p["scale"]


def _gated(h, p):
    return _matmul(
        jax.nn.silu(_matmul(h, p["gate"]["kernel"])) * _matmul(h, p["up"]["kernel"]),
        p["down"]["kernel"],
    )


def _conv(x, w):
    """Causal depthwise convolution: ``y_t = sum_j w[j] x_{t-(K-1)+j}``."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    windows = jnp.stack([padded[:, j : j + t] for j in range(taps)], axis=2)
    return jnp.einsum("btkc,kc->btc", windows, w, precision=_HI)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def gated_delta_rule(q, k, v, g, b):
    """``o_t = S_t^T q_t`` of ``S_t = e^{g_t} S_{t-1} + b_t k_t (v_t -
    e^{g_t} S_{t-1}^T k_t)^T``, token by token.  ``q, k`` ``[batch, time,
    heads, dk]``, ``v`` ``[batch, time, heads, dv]``, ``g``, ``b``
    ``[batch, time, heads]``."""

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x
        S = jnp.exp(g_t)[..., None, None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_HI)
        )[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    t = q.shape[1]
    whole = t - t % RECOMPUTE
    xs = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, b)]
    S = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), q.dtype)
    outs = []
    if whole:
        blocks = [x[:whole].reshape(-1, RECOMPUTE, *x.shape[1:]) for x in xs]
        S, out = jax.lax.scan(block, S, blocks)
        outs.append(out.reshape(whole, *out.shape[2:]))
    if t - whole:
        outs.append(block(S, [x[whole:] for x in xs])[1])
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)


def linear_attention(h, p, eps):
    """The delta-rule mixer on ``h`` ``[batch, time, hidden]``; the head
    count and the two widths are read off the parameters."""
    b, t, _ = h.shape
    heads = p["A_log"].shape[0]
    mixed = lambda name: jax.nn.silu(
        _conv(_matmul(h, p[name]["kernel"]), p[f"conv_{name}"])
    ).reshape(b, t, heads, -1)
    q, k, v = _l2norm(mixed("query")), _l2norm(mixed("key")), mixed("value")
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(_matmul(h, p["a"]["kernel"]) + p["dt_bias"])
    beta = 2.0 * jax.nn.sigmoid(_matmul(h, p["beta"]["kernel"]))
    o = gated_delta_rule(q, k, v, g, beta) * q.shape[-1] ** -0.5
    gate = jax.nn.silu(_matmul(h, p["gate"]["kernel"])).reshape(o.shape)
    o = _rms_norm(o, p["o_norm"], eps) * gate
    return _matmul(o.reshape(b, t, -1), p["out"]["kernel"])


def _rope(x, theta):
    """``x`` ``[batch, time, heads, D]``: channel ``i`` paired with ``i +
    D/2``, rotated by ``t * theta^(-2i/D)``."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    # The angles are the positions' own constants: float32 whatever ``x``.
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(ang).astype(x.dtype)[None, :, None, :]
    sin = jnp.sin(ang).astype(x.dtype)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def full_attention(h, p, num_heads, eps, theta, qk_mean_squares=None):
    """The full-attention mixer at the ``num_heads`` held.
    ``qk_mean_squares = (ms_q, ms_k)``, each ``[batch, time, 1]``: the
    mean squares under the query and key norms where they are not the
    held channels' own (module docstring, "The share")."""
    b, t, _ = h.shape
    ms_q, ms_k = qk_mean_squares or (None, None)
    split = lambda y: y.reshape(b, t, num_heads, -1)
    q = split(_rms_norm(_matmul(h, p["query"]["kernel"]), p["q_norm"], eps, ms_q))
    k = split(_rms_norm(_matmul(h, p["key"]["kernel"]), p["k_norm"], eps, ms_k))
    v = split(_matmul(h, p["value"]["kernel"]))
    q, k = _rope(q, theta), _rope(k, theta)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scale = q.shape[-1] ** -0.5

    # One head's [time, time] scores at a time, recomputed in the backward
    # pass: 15 heads of 8192 x 8192 float32 are 4 GB.
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


def forward(params, tokens, *, num_heads: int, eps: float = 1e-6,
            theta: float = 500000.0, dtype=jnp.float32):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embedding"]["embedding"][tokens]
    layer = 0
    while f"blocks_{layer}" in params:
        p = params[f"blocks_{layer}"]
        if "linear_attn" in p:
            mixed = linear_attention(x, p["linear_attn"], eps)
        else:
            mixed = full_attention(x, p["attn"], num_heads, eps, theta)
        x = x + _rms_norm(mixed, p["ln1"], eps)
        x = x + _rms_norm(_gated(x, p["mlp"]), p["ln2"], eps)
        layer += 1
    return _matmul(_rms_norm(x, params["ln_f"], eps), params["head"]["kernel"])


def loss(params, tokens, targets, *, num_heads: int, eps: float = 1e-6,
         theta: float = 500000.0, dtype=jnp.float32):
    """``(total, parts)``: mean next-token cross entropy in nats (there is
    no other term); ``parts`` holds ``nll``."""
    logits = forward(params, tokens, num_heads=num_heads, eps=eps, theta=theta, dtype=dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll, {"nll": nll}
