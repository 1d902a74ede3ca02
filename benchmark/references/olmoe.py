"""Plain reference of the ``olmoe`` configuration: forward, loss, and
through ``jax.grad`` its gradients.

OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060; layer equations
as in ISSUE 25 and the public ``modeling_olmoe.py``) in straightforward
``jax.numpy`` and float32, matrix products at precision ``highest`` (on
a TPU a float32 product otherwise runs in bf16 passes).  No kernels, no
cache, no sort, no grouped product: every expert is applied to every
token, one expert after the other, and masked by the top-k choice.

Per layer, with ``RMSNorm(x) = x / sqrt(mean(x^2) + eps) * w``:

- attention: ``q, k, v = Wq h, Wk h, Wv h`` (no bias) of ``h =
  RMSNorm(x)``; RMSNorm on the whole ``q`` and ``k`` vectors, each with
  its own weight, before the split into heads; RoPE on the whole head
  size, rotate-half pairing ``(i, i + head/2)``; causal softmax at scale
  ``head^-0.5``; ``x = x + Wo a``;
- experts: ``p = softmax(Wr RMSNorm(x))`` over the experts; the ``k``
  largest ``p_e``, used as they are (not renormalised); ``x = x + sum_e
  p_e Wdown_e (silu(Wgate_e h) * Wup_e h)``;
- a final RMSNorm and an untied, bias-free head;
- loss: token cross entropy, plus per layer ``aux_weight * E * sum_e
  f_e P_e`` (``f_e`` the share of the ``k n`` assignments that went to
  expert ``e``, a count and so a constant under differentiation; ``P_e``
  the mean of ``p_e``) and ``z_weight * mean(logsumexp(Wr h)^2)``.

It takes the parameter tree of ``models/transformer_lm.py`` as it is.
Departures from the published description, shared with the program so
that the two can be compared on the same weights:

- ties in the top-k choice go to the lower expert index (the published
  code leaves them to ``torch.topk``);
- the statistics ``f_e`` and ``P_e`` are taken over the tokens of the
  call (the published trainer takes them per device and micro-batch);
- the reference knows no dropout (OLMoE trains without).

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, the router, both softmaxes
and the logits.  ``benchmark/tools/compare_reference.py`` runs it once
in bfloat16, the nearest precision below what the configuration states
(bf16 products under float32 norms, router, softmax and logits), to show
that its tolerances tell the two apart.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _rms_norm(x, p, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * p["scale"]


def _matmul(x, w):
    return jnp.matmul(x, w, precision=_HI)


def _rope(x, theta):
    """``x`` [b, t, heads, head]: rotate the pairs ``(i, i + head/2)``."""
    t, dh = x.shape[1], x.shape[3]
    inv_freq = theta ** (-jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(angle)[None, :, None, :].astype(x.dtype)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2 :]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(h, p, num_heads, eps, theta):
    b, t, d = h.shape
    dh = d // num_heads
    q = _rms_norm(_matmul(h, p["query"]["kernel"]), p["q_norm"], eps)
    k = _rms_norm(_matmul(h, p["key"]["kernel"]), p["k_norm"], eps)
    v = _matmul(h, p["value"]["kernel"])
    split = lambda y: y.reshape(b, t, num_heads, dh)
    q, k, v = _rope(split(q), theta), _rope(split(k), theta), split(v)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=_HI) * dh**-0.5
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v, precision=_HI)
    return _matmul(out.reshape(b, t, d), p["out"]["kernel"])


def top_k_mask(probs, top_k):
    """``[n, E]`` bool: the ``top_k`` largest of each row, ties to the
    lower index, by counting the entries that come before each one."""
    index = jnp.arange(probs.shape[-1])
    mine, other = probs[:, :, None], probs[:, None, :]
    before = (other > mine) | ((other == mine) & (index[None, None, :] < index[None, :, None]))
    return jnp.sum(before, axis=-1) < top_k


def _experts(h, p, top_k):
    """``h`` [n, d] -> the layer's output and its two router losses."""
    num_experts = p["router"].shape[-1]
    logits = _matmul(h, p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = top_k_mask(probs, top_k)
    weight = jnp.where(chosen, probs, 0.0)

    # One expert after the other over all tokens; the body is recomputed
    # in the backward pass so that no [experts, tokens, width] tensor is
    # kept.
    @jax.checkpoint
    def one(w_gate, w_up, w_down, w):
        y = _matmul(jax.nn.silu(_matmul(h, w_gate)) * _matmul(h, w_up), w_down)
        return y * w[:, None]

    def add(acc, per_expert):
        return acc + one(*per_expert), None

    out, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], weight.T)
    )
    share = jnp.mean(chosen.astype(jnp.float32), axis=0) / top_k
    aux = num_experts * jnp.sum(share * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out, aux, z, chosen


def _forward(params, tokens, num_heads, top_k, eps, theta, dtype):
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embedding"]["embedding"][tokens]
    b, t, d = x.shape
    aux, z, chosen = [], [], []
    layer = 0
    while f"blocks_{layer}" in params:
        p = params[f"blocks_{layer}"]
        x = x + _attention(_rms_norm(x, p["ln1"], eps), p["attn"], num_heads, eps, theta)
        y, a, zz, c = _experts(_rms_norm(x, p["ln2"], eps).reshape(b * t, d), p["moe"], top_k)
        x = x + y.reshape(b, t, d)
        aux.append(a)
        z.append(zz)
        chosen.append(c)
        layer += 1
    logits = _matmul(_rms_norm(x, params["ln_f"], eps), params["head"]["kernel"])
    return logits, aux, z, chosen


def forward(params, tokens, *, num_heads: int, top_k: int,
            eps: float = 1e-5, theta: float = 10000.0, dtype=jnp.float32):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``."""
    return _forward(params, tokens, num_heads, top_k, eps, theta, dtype)[0]


def routing(params, tokens, *, num_heads: int, top_k: int,
            eps: float = 1e-5, theta: float = 10000.0, dtype=jnp.float32):
    """Per layer, the ``[batch * time, experts]`` bool of chosen experts."""
    return _forward(params, tokens, num_heads, top_k, eps, theta, dtype)[3]


def loss(params, tokens, targets, *, num_heads: int, top_k: int,
         eps: float = 1e-5, theta: float = 10000.0,
         aux_weight: float = 0.01, z_weight: float = 0.001,
         dtype=jnp.float32):
    """``(total, parts)``: mean next-token cross entropy in nats plus the
    weighted router losses summed over layers; ``parts`` holds ``nll``
    and the unweighted ``aux_loss`` and ``z_loss`` as means over layers."""
    logits, aux, z, _ = _forward(params, tokens, num_heads, top_k, eps, theta, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    total = nll + aux_weight * sum(aux) + z_weight * sum(z)
    parts = {"nll": nll, "aux_loss": sum(aux) / len(aux), "z_loss": sum(z) / len(z)}
    return total, parts
