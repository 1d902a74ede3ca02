"""Plain reference of the ``gpt2m`` configuration's forward pass.

GPT-2's block (Radford et al. 2019) in straightforward ``jax.numpy`` and
float32: learned absolute positions, pre-LayerNorm with bias, multi-head
causal attention with biases everywhere, a tanh-GELU MLP, a final
LayerNorm and an output head.  No kernels, no cache, no batching
tricks, and matrix products at precision ``highest`` (on a TPU a
float32 product otherwise runs in bf16 passes).

It takes the parameter tree of ``models/transformer_lm.py`` as it is
(names below) and follows the program's two departures from the
published model, so that the two can be compared on the same weights:
the head is a separate matrix with a bias (GPT-2 ties it to the token
embedding), and LayerNorm's epsilon is 1e-6 (GPT-2: 1e-5).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

LN_EPS = 1e-6  # flax's default, which the program uses
_HI = jax.lax.Precision.HIGHEST


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _dense(x, p):
    return jnp.matmul(x, p["kernel"], precision=_HI) + p["bias"]


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def _attention(x, p, num_heads):
    b, t, d = x.shape
    dh = d // num_heads
    split = lambda y: y.reshape(b, t, num_heads, dh).transpose(0, 2, 1, 3)
    q, k, v = (split(_dense(x, p[name])) for name in ("query", "key", "value"))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=_HI) / jnp.sqrt(dh)
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision=_HI)
    return _dense(out.transpose(0, 2, 1, 3).reshape(b, t, d), p["out"])


def forward(params, tokens, *, num_heads: int):
    """Logits ``[batch, time, vocab]`` (float32) for ``tokens``
    ``[batch, time]``, no dropout."""
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    t = tokens.shape[1]
    x = params["embedding"]["embedding"][tokens] + params["pos_embedding"][:t]
    layer = 0
    while f"blocks_{layer}" in params:
        p = params[f"blocks_{layer}"]
        x = x + _attention(_layer_norm(x, p["ln1"]), p["attn"], num_heads)
        h = _layer_norm(x, p["ln2"])
        x = x + _dense(_gelu_tanh(_dense(h, p["mlp"]["up"])), p["mlp"]["down"])
        layer += 1
    return _dense(_layer_norm(x, params["ln_f"]), params["head"])


def loss(params, tokens, targets, *, num_heads: int):
    """Mean next-token cross entropy in nats."""
    logp = jax.nn.log_softmax(forward(params, tokens, num_heads=num_heads), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
