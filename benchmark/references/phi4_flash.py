"""Plain reference of the ``phi4_mini_flash`` configuration: forward,
loss, and through ``jax.grad`` its gradients.

Phi-4-mini-flash-reasoning (``config.json`` of
microsoft/Phi-4-mini-flash-reasoning, ``model_type`` ``phi4flash``; the
design is SambaY: Ren et al. 2025, "Decoder-Hybrid-Decoder Architecture
for Efficient Reasoning with Long Generation", arXiv:2507.06607; the
self-decoder is Samba, arXiv:2406.07522, over Mamba, Gu and Dao 2023,
arXiv:2312.00752; the attention is differential attention, Ye et al. 2024,
arXiv:2410.05258; layer equations as in ISSUE 44) in straightforward
``jax.numpy`` and float32, matrix products at precision ``highest``.  No
kernels and no chunks: the Mamba layer is its recurrence token by token,
the attention two full masked score matrices a pair of heads, subtracted
as written.

``x`` a layer's input, ``LN`` LayerNorm with weight and bias, ``eps``
1e-5:

- the stack: ``h_0 = E[tokens]`` (no positions of any kind); per layer
  ``u = h + mixer(LN_a(h))``, ``h' = u + ffn(LN_f(u))`` (pre-norm);
  ``ffn(x) = W_down (silu(W_gate x) * (W_up x))`` without bias, inner
  width 10240; ``logits = E LN(h_L)`` with the **same** ``E``
  (``tie_word_embeddings``).  A layer's kind is given (``layers``): a
  window cannot be read off parameters.
- ``mamba`` (``d_inner`` 5120, state ``N`` 16, ``dt_rank`` 160): ``[x, z]
  = W_in u``; ``x = silu(conv4(x) + b_conv)``, causal and depth-wise;
  ``[r, B, C] = W_x x`` (160, 16, 16); ``dt = softplus(W_dt r + b_dt)``;
  ``A = -exp(A_log)`` ``[5120, 16]``; ``h_t = exp(dt_t A) * h_{t-1} +
  (dt_t x_t) B_t^T``; ``y_t = h_t C_t + D * x_t``; ``out = W_out (y *
  silu(z))``.  ``y`` is the **memory** a later ``gmu`` layer reads.
- ``window`` and ``full``: differential attention over 40 query and 20
  key/value heads of 64, biases on the four projections.  Query pair ``j``
  (of 20) is ``q1, q2`` = query heads ``2 j, 2 j + 1``; it reads key/value
  pair ``m = j // 2`` (of 10): ``k1, k2`` = key heads ``2 m, 2 m + 1``,
  ``v`` = value heads ``2 m`` and ``2 m + 1`` side by side (128).  ``a =
  softmax(q1 k1^T / 8) v - lambda * softmax(q2 k2^T / 8) v`` under the
  causal mask, in a ``window`` layer ``0 <= t - s < 512`` besides;
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init
  = 0.8 - 0.6 exp(-0.3 l)`` at the layer's published index ``l``; ``out =
  W_o ((1 - lambda_init) * rmsnorm_128(a))``, the norm with a weight per
  pair.  A ``full`` layer's keys and values are what a later ``cross``
  layer reads.
- ``cross``: the same with ``q = W_q u + b`` alone and ``k``, ``v`` those
  of the nearest earlier ``full`` layer; full causal.
- ``gmu``: ``out = W_out (m * silu(W_in u))``, ``m`` the nearest earlier
  ``mamba`` layer's memory.
- loss: mean token cross entropy, no other term.

Departures from the published description: none in the mathematics above;
the feed-forward holds ``W_in`` as two matrices (gate, up) where the
published layer holds one of twice the width (the same products), ``W_dt``
and its bias are two leaves (``dt_proj``, ``dt_bias``), and the six layers
and the vocabulary slice are the configuration's cut
(``benchmark/configs/phi4_mini_flash.json``).

No share: the configuration keeps every head and every width, so there is
no partial sum here.

It takes the parameter tree of ``models/transformer_lm.py`` as it is
(``blocks_<i>/{ln1, ssm | attn, ln2, mlp}``, ``embedding``, ``ln_f``; no
``head``).  The only structure it shares with the program: the gradient
through the recurrence recomputes in blocks of ``RECOMPUTE`` tokens (the
recurrence itself is token by token), the score matrices are taken one
pair of heads after the other, and each half of a layer is recomputed in
the backward pass (``jax.checkpoint``), so that it fits a chip.

``dtype`` (float32 unless given) is the precision of everything: the
weights as used, every activation, the norms, ``dt``, the decay, the
recurrent state, the softmaxes and the logits.
``benchmark/tools/compare_reference_phi4_flash.py`` runs it once in
bfloat16, the nearest precision below what the configuration states.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST
RECOMPUTE = 128


def _matmul(x, w):
    return jnp.matmul(x, w, precision=_HI)


def _affine(x, p):
    return _matmul(x, p["kernel"]) + p["bias"]


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


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


def selective_scan(x, dt, A, b, c):
    """``y_t = h_t C_t`` of ``h_t = exp(dt_t A) * h_{t-1} + (dt_t x_t)
    B_t^T``, token by token.  ``x``, ``dt`` ``[batch, time, D]``, ``A``
    ``[D, N]``, ``b``, ``c`` ``[batch, time, N]``."""

    def token(h, at):
        x_t, dt_t, b_t, c_t = at
        h = jnp.exp(dt_t[..., None] * A) * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        return h, jnp.sum(h * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def block(h, xs):
        return jax.lax.scan(token, h, xs)

    t = x.shape[1]
    whole = t - t % RECOMPUTE
    xs = [jnp.moveaxis(y, 1, 0) for y in (x, dt, b, c)]
    h = jnp.zeros((x.shape[0],) + A.shape, x.dtype)
    outs = []
    if whole:
        blocks = [y[:whole].reshape(-1, RECOMPUTE, *y.shape[1:]) for y in xs]
        h, out = jax.lax.scan(block, h, blocks)
        outs.append(out.reshape(whole, *out.shape[2:]))
    if t - whole:
        outs.append(block(h, [y[whole:] for y in xs])[1])
    return jnp.moveaxis(jnp.concatenate(outs, axis=0), 0, 1)


def mamba(u, p):
    """``(out, memory)`` of the Mamba-1 mixer on ``u`` ``[batch, time,
    hidden]``; every size is read off the parameters."""
    inner, state = p["A_log"].shape
    x, z = jnp.split(_matmul(u, p["in_proj"]["kernel"]), 2, axis=-1)
    x = jax.nn.silu(_conv(x, p["conv"], p["conv_bias"]))
    rank = p["dt_proj"]["kernel"].shape[0]
    r, b, c = jnp.split(_matmul(x, p["x_proj"]["kernel"]), [rank, rank + state], axis=-1)
    dt = jax.nn.softplus(_matmul(r, p["dt_proj"]["kernel"]) + p["dt_bias"])
    y = selective_scan(x, dt, -jnp.exp(p["A_log"]), b, c) + p["D"] * x
    return _matmul(y * jax.nn.silu(z), p["out_proj"]["kernel"]), y


def gated_memory_unit(u, p, memory):
    return _matmul(
        memory * jax.nn.silu(_matmul(u, p["in_proj"]["kernel"])), p["out_proj"]["kernel"]
    )


def lambda_init(layer_id: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer_id)


def differential_attention(q, k, v, p, layer_id, window, eps):
    """``W_o`` of the differential attention of ``q`` ``[b, t, 40, 64]``
    over ``k``, ``v`` ``[b, t, 20, 64]`` (module docstring); ``window``
    None: full causal."""
    b, t, heads, dim = q.shape
    pairs, kv_pairs = heads // 2, k.shape[2] // 2
    at = jnp.arange(t)
    seen = at[:, None] >= at[None, :]
    if window is not None:
        seen = seen & (at[:, None] - at[None, :] < window)
    lam0 = lambda_init(layer_id)
    lam = (
        jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
        + lam0
    )
    # [b, t, pairs, 2, dim]: the two heads of a pair.
    q2 = q.reshape(b, t, pairs, 2, dim)
    k2 = jnp.repeat(k.reshape(b, t, kv_pairs, 2, dim), pairs // kv_pairs, axis=2)
    v2 = jnp.repeat(v.reshape(b, t, kv_pairs, 2 * dim), pairs // kv_pairs, axis=2)

    def softmax_of(q_h, k_h):
        scores = jnp.einsum("bqd,bkd->bqk", q_h, k_h, precision=_HI) / math.sqrt(dim)
        return jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)

    # One pair's two [time, time] score matrices at a time, recomputed in
    # the backward pass.
    @jax.checkpoint
    def one_pair(x):
        q_p, k_p, v_p = x  # [b, t, 2, dim], [b, t, 2, dim], [b, t, 2 dim]
        read = lambda s: jnp.einsum(
            "bqk,bkd->bqd", softmax_of(q_p[:, :, s], k_p[:, :, s]), v_p, precision=_HI
        )
        return read(0) - lam * read(1)

    pairs_first = lambda y: jnp.moveaxis(y, 2, 0)
    a = jax.lax.map(one_pair, (pairs_first(q2), pairs_first(k2), pairs_first(v2)))
    a = jnp.moveaxis(a, 0, 2)  # [b, t, pairs, 2 dim]
    a = a * jax.lax.rsqrt(jnp.mean(jnp.square(a), axis=-1, keepdims=True) + eps)
    a = (1.0 - lam0) * a * p["subln"]["scale"]
    return _affine(a.reshape(b, t, -1), p["out"])


def _heads(y, count):
    return y.reshape(*y.shape[:2], count, -1)


def forward(params, tokens, *, layers, layer_ids, num_heads: int = 40,
            num_kv_heads: int = 20, window: int = 512, eps: float = 1e-5,
            dtype=jnp.float32):
    """Logits ``[batch, time, vocab]`` for ``tokens`` ``[batch, time]``.
    ``layers``: each layer's kind, ``"mamba" | "window" | "full" | "gmu" |
    "cross"``; ``layer_ids``: its published index."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    table = params["embedding"]["embedding"]
    x = table[tokens]
    memory = keys_values = None

    @jax.checkpoint
    def feed_forward_half(x, p):
        return _gated(_layer_norm(x, p["ln2"], eps), p["mlp"])

    for i, (kind, layer_id) in enumerate(zip(layers, layer_ids)):
        p = params[f"blocks_{i}"]

        @jax.checkpoint
        def mixer_half(x, p, memory, keys_values, kind=kind, layer_id=layer_id):
            """``(out, memory, keys_values)``, the last two as handed on."""
            u = _layer_norm(x, p["ln1"], eps)
            if kind == "mamba":
                out, memory = mamba(u, p["ssm"])
            elif kind == "gmu":
                out = gated_memory_unit(u, p["ssm"], memory)
            else:
                a = p["attn"]
                q = _heads(_affine(u, a["query"]), num_heads)
                if kind == "cross":
                    k, v = keys_values
                else:
                    k = _heads(_affine(u, a["key"]), num_kv_heads)
                    v = _heads(_affine(u, a["value"]), num_kv_heads)
                if kind == "full":
                    keys_values = (k, v)
                out = differential_attention(
                    q, k, v, a, layer_id, window if kind == "window" else None, eps
                )
            return out, memory, keys_values

        out, memory, keys_values = mixer_half(x, p, memory, keys_values)
        x = x + out
        x = x + feed_forward_half(x, p)
    return _matmul(_layer_norm(x, params["ln_f"], eps), table.T)


def loss(params, tokens, targets, **kwargs):
    """``(total, parts)``: mean next-token cross entropy in nats (there is
    no other term); ``parts`` holds ``nll``.  ``kwargs`` as
    :func:`forward`'s."""
    logits = forward(params, tokens, **kwargs)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
    return nll, {"nll": nll}
