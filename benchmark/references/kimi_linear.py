"""Plain reference of the ``kimi_linear`` configuration: forward, loss,
and through ``jax.grad`` its gradients.

Kimi-Linear-48B-A3B (Kimi Linear technical report, Moonshot AI 2025,
arXiv:2510.26692; ``config.json`` and the published modelling code of
moonshotai/Kimi-Linear-48B-A3B-Instruct; layer equations as in ISSUE 30)
in straightforward ``jax.numpy`` and float32, matrix products at
precision ``highest``.  No kernels, no chunks, no sort, no grouped
product: the linear attention is its recurrence token by token, the
attention a full score matrix, and every held expert is applied to every
token and masked by the choice.

Pre-norm blocks, ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``: ``x = x
+ mixer(RMSNorm(x))``, ``x = x + ffn(RMSNorm(x))``; no position encoding
anywhere; a final RMSNorm and an untied, bias-free head.  A layer's
kind is read off its parameters.

- KDA mixer, per head of ``D`` channels: ``q, k = l2norm(silu(conv(Wq
  h)))`` (``x / sqrt(sum x^2 + 1e-6)``), ``v = silu(conv(Wv h))``, the
  convolutions causal, depthwise, ``K`` taps, no bias; ``a_t = exp(
  -exp(A_log) * softplus(Wf2 Wf1 h + dt_bias))`` per channel (``A_log``
  per head), ``b_t = sigmoid(Wb h)`` per head; ``S_t = (I - b_t k_t
  k_t^T) diag(a_t) S_{t-1} + b_t k_t v_t^T``, ``o_t = S_t^T q_t /
  sqrt(D)``; ``y = Wo (RMSNorm_head(o_t) * sigmoid(Wg2 Wg1 h))``.
- MLA mixer: ``q = Wq h`` (``nope + rope`` channels a head), ``c, k_r =
  split(Wkva h)``, ``c = RMSNorm(c)``, ``k_n, v = split(Wkvb c)``, a
  head's key ``[k_n, k_r]`` with ``k_r`` shared by the heads, nothing
  rotated, causal softmax at ``(nope + rope)^-0.5``, ``y = Wo a``.
- experts: ``s = sigmoid(Wr h)``; the ``top_k`` largest, ties to the
  lower index; weights ``routed_scale * s_e / sum(chosen s)``; ``y =
  sum over the chosen experts that are held of w_e Wdown_e (silu(Wgate_e
  h) * Wup_e h)``, plus the shared expert ``Wdown (silu(Wgate h) * Wup
  h)`` on every token.  The expert stacks hold ``count`` experts,
  ``held_first`` onwards, of the router's: what the absent ones would
  add is left out, as in the program (one chip's share of the layer).
- the leading dense layer: ``Wdown (silu(Wgate h) * Wup h)``.
- loss: mean token cross entropy.  No auxiliary loss; the selection bias
  of the published router is a buffer outside the gradient, held at zero.

It takes the parameter tree of ``models/transformer_lm.py`` as it is.
The only structure it shares with the program: the gradient through the
recurrence recomputes in blocks of ``RECOMPUTE`` tokens (the recurrence
itself is token by token), the score matrices are taken one head after
the other, and one expert after the other, so that it fits a chip.

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, the decay, the recurrent
state, the router, the softmax and the logits.
``benchmark/tools/compare_reference_kimi_linear.py`` runs it once in
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
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


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


def delta_rule(q, k, v, a, b):
    """``o_t = S_t^T q_t`` of ``S_t = (I - b_t k_t k_t^T) diag(a_t)
    S_{t-1} + b_t k_t v_t^T``, token by token.  ``q, k, a`` ``[batch,
    time, heads, dk]``, ``v`` ``[batch, time, heads, dv]``, ``b``
    ``[batch, time, heads]``."""

    def token(S, x):
        q_t, k_t, v_t, a_t, b_t = x
        S = a_t[..., None] * S
        S = S + (b_t[..., None] * k_t)[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, S, precision=_HI)
        )[..., None, :]
        return S, jnp.einsum("bhk,bhkv->bhv", q_t, S, precision=_HI)

    @jax.checkpoint
    def block(S, xs):
        return jax.lax.scan(token, S, xs)

    t = q.shape[1]
    whole = t - t % RECOMPUTE
    xs = [jnp.moveaxis(x, 1, 0) for x in (q, k, v, a, b)]
    S = jnp.zeros((q.shape[0], q.shape[2], q.shape[3], v.shape[3]), q.dtype)
    outs = []
    if whole:
        blocks = [x[:whole].reshape(-1, RECOMPUTE, *x.shape[1:]) for x in xs]
        S, out = jax.lax.scan(block, S, blocks)
        outs.append(out.reshape(whole, *out.shape[2:]))
    if t - whole:
        outs.append(block(S, [x[whole:] for x in xs])[1])
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)


def _kda(h, p, eps):
    b, t, _ = h.shape
    heads = p["A_log"].shape[0]
    width = p["query"]["kernel"].shape[1]
    head = width // heads
    split = lambda y: y.reshape(b, t, heads, head)
    mixed = lambda name: split(
        jax.nn.silu(_conv(_matmul(h, p[name]["kernel"]), p[f"conv_{name}"]))
    )
    q, k, v = _l2norm(mixed("query")), _l2norm(mixed("key")), mixed("value")
    low_rank = lambda first, second: _matmul(
        _matmul(h, p[first]["kernel"]), p[second]["kernel"]
    )
    decay = jnp.exp(
        -jnp.exp(p["A_log"])[:, None]
        * jax.nn.softplus(split(low_rank("f_a", "f_b") + p["dt_bias"]))
    )
    beta = jax.nn.sigmoid(_matmul(h, p["beta"]["kernel"]))
    o = delta_rule(q, k, v, decay, beta) * head**-0.5
    o = _rms_norm(o, p["o_norm"], eps) * jax.nn.sigmoid(split(low_rank("g_a", "g_b")))
    return _matmul(o.reshape(b, t, width), p["out"]["kernel"])


def _mla(h, p, num_heads, eps):
    b, t, _ = h.shape
    rank = p["kv_a_norm"]["scale"].shape[0]
    rope = p["kv_a"]["kernel"].shape[1] - rank
    qk = p["query"]["kernel"].shape[1] // num_heads
    nope = qk - rope
    q = _matmul(h, p["query"]["kernel"]).reshape(b, t, num_heads, qk)
    kv = _matmul(h, p["kv_a"]["kernel"])
    c, k_r = _rms_norm(kv[..., :rank], p["kv_a_norm"], eps), kv[..., rank:]
    kv = _matmul(c, p["kv_b"]["kernel"]).reshape(b, t, num_heads, -1)
    k_n, v = kv[..., :nope], kv[..., nope:]
    causal = jnp.tril(jnp.ones((t, t), bool))

    # One head's [time, time] scores at a time, recomputed in the backward
    # pass: 32 heads of 8192 x 8192 float32 are 8.6 GB.
    @jax.checkpoint
    def one_head(x):
        q_h, k_h, v_h = x  # [b, t, channels]
        k_h = jnp.concatenate([k_h, k_r], axis=-1)
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision=_HI) * qk**-0.5
        scores = jnp.where(causal, scores, -jnp.inf)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(scores, axis=-1), v_h, precision=_HI)

    heads_first = lambda y: jnp.moveaxis(y, 2, 0)
    out = jax.lax.map(one_head, (heads_first(q), heads_first(k_n), heads_first(v)))
    out = jnp.moveaxis(out, 0, 2).reshape(b, t, -1)
    return _matmul(out, p["out"]["kernel"])


def top_k_mask(scores, top_k):
    """``[n, E]`` bool: the ``top_k`` largest of each row, ties to the
    lower index (a stable sort of the negated scores)."""
    order = jnp.argsort(-scores, axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1)
    return rank < top_k


def _experts(h, p, top_k, routed_scale, held_first):
    """``h`` [n, d] -> the layer's output, the choice ``[n, E]`` and the
    share of the assignments that fell on held experts."""
    count = p["w_gate"].shape[0]
    scores = jax.nn.sigmoid(_matmul(h, p["router"]))
    chosen = top_k_mask(scores, top_k)
    weight = jnp.where(chosen, scores, 0.0)
    weight = routed_scale * weight / jnp.sum(weight, axis=-1, keepdims=True)
    held = weight[:, held_first : held_first + count]

    @jax.checkpoint
    def one(w_gate, w_up, w_down, w):
        y = _matmul(jax.nn.silu(_matmul(h, w_gate)) * _matmul(h, w_up), w_down)
        return y * w[:, None]

    def add(acc, per_expert):
        return acc + one(*per_expert), None

    out, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], held.T)
    )
    if "shared" in p:
        out = out + _gated(h, p["shared"])
    share = jnp.sum(chosen[:, held_first : held_first + count]) / (h.shape[0] * top_k)
    return out, chosen, share


def _forward(params, tokens, num_heads, top_k, routed_scale, held_first, eps, dtype):
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embedding"]["embedding"][tokens]
    b, t, d = x.shape
    chosen, shares = [], []
    layer = 0
    while f"blocks_{layer}" in params:
        p = params[f"blocks_{layer}"]
        h = _rms_norm(x, p["ln1"], eps)
        if "linear_attn" in p:
            x = x + _kda(h, p["linear_attn"], eps)
        else:
            x = x + _mla(h, p["attn"], num_heads, eps)
        h = _rms_norm(x, p["ln2"], eps)
        if "moe" in p:
            y, c, share = _experts(
                h.reshape(b * t, d), p["moe"], top_k, routed_scale, held_first
            )
            x = x + y.reshape(b, t, d)
            chosen.append(c)
            shares.append(share)
        else:
            x = x + _gated(h, p["mlp"])
        layer += 1
    logits = _matmul(_rms_norm(x, params["ln_f"], eps), params["head"]["kernel"])
    return logits, chosen, shares


def forward(params, tokens, *, num_heads: int, top_k: int, routed_scale: float,
            held_first: int = 0, eps: float = 1e-5, dtype=jnp.float32):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``."""
    return _forward(params, tokens, num_heads, top_k, routed_scale, held_first, eps, dtype)[0]


def routing(params, tokens, *, num_heads: int, top_k: int, routed_scale: float,
            held_first: int = 0, eps: float = 1e-5, dtype=jnp.float32):
    """Per expert layer, the ``[batch * time, experts]`` bool of chosen
    experts (over all the router's experts, held or not)."""
    return _forward(params, tokens, num_heads, top_k, routed_scale, held_first, eps, dtype)[1]


def loss(params, tokens, targets, *, num_heads: int, top_k: int, routed_scale: float,
         held_first: int = 0, eps: float = 1e-5, dtype=jnp.float32):
    """``(total, parts)``: mean next-token cross entropy in nats (there is
    no other term); ``parts`` holds ``nll`` and ``held_share``, the mean
    over the expert layers of the share of assignments on held experts."""
    logits, _, shares = _forward(
        params, tokens, num_heads, top_k, routed_scale, held_first, eps, dtype
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll, {"nll": nll, "held_share": sum(shares) / max(len(shares), 1)}
