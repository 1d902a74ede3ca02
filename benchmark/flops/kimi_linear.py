"""Model FLOPs of one Kimi Linear training token as the ``kimi_linear``
configuration cuts it (one chip's share of 32 that share each layer's
experts), and the two cores' own operations and bytes per step, counted
from shapes.

Per token the forward pass multiplies by

- a KDA mixer: the query, key, value and output projections (4 x hidden
  x heads x head), the two low-rank gates (2 x (hidden x head + head x
  heads x head)), the ``b`` projection (hidden x heads), and the delta
  rule itself as the recurrence states it, per head three passes over
  the ``head x head`` state (``k^T S``, the rank-one update, ``S^T q``);
- an MLA mixer: the query projection (hidden x heads x (nope + rope)),
  the compression (hidden x (rank + rope)), the expansion (rank x heads
  x (nope + value)), the output projection, and the core over the whole
  sequence (``seq_len x (nope + rope + value)`` per head: the whole
  length for every position, as ``flops/gpt2.py`` counts it);
- the leading dense feed-forward (3 x hidden x dense_width); in every
  other layer the router (hidden x n_router), the shared experts and
  the routed experts **this chip holds**: of a token's ``top_k``
  assignments ``held / n_router`` fall here when the routing is even
  (8 x 8 / 256 = a quarter of an expert a token; the step's real share
  is the metric ``moe_held_share.tokens``);
- the output head (hidden x vocab_size: the slice).

A MAC is 2 FLOPs and forward + backward is 3x forward; recomputation is
not counted.  Embeddings, the norms, the short convolutions, the
activations, the dispatch and the optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def forward_macs_per_token(kda_layers: int, mla_layers: int, dense_layers: int,
                           hidden: int, heads: int, kda_head: int, nope: int,
                           rope: int, value: int, kv_rank: int, dense_width: int,
                           expert_width: int, n_router: int, held: int, top_k: int,
                           shared: int, vocab_size: int, seq_len: int) -> float:
    width = heads * kda_head
    kda = (
        4 * hidden * width
        + 2 * (hidden * kda_head + kda_head * width)
        + hidden * heads
        + 3 * heads * kda_head * kda_head
    )
    mla = (
        hidden * heads * (nope + rope)
        + hidden * (kv_rank + rope)
        + kv_rank * heads * (nope + value)
        + heads * value * hidden
        + heads * seq_len * (nope + rope + value)
    )
    expert = 3 * hidden * expert_width
    experts = hidden * n_router + shared * expert + top_k * held / n_router * expert
    expert_layers = kda_layers + mla_layers - dense_layers
    return (
        kda_layers * kda
        + mla_layers * mla
        + dense_layers * 3 * hidden * dense_width
        + expert_layers * experts
        + hidden * vocab_size
    )


def flops_per_item(**kwargs) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(**kwargs)


def kda_core_per_step(tokens: int, kda_layers: int, heads: int, head: int,
                      chunk: int, sub: int) -> dict:
    """What the chunk-wise delta rule (scope ``kda_core``,
    ``ops/linear_attention.py``) needs per training step of ``tokens``
    tokens, forward and backward (twice the forward): ``flops`` of its
    matrix products and of the pair-by-pair products inside ``sub``
    blocks, per chunk and head

    - the decayed key-key and query-key products: blocks below the
      diagonal as matrix products (``sub x sub x head`` each, ``n (n - 1)
      / 2`` of them with ``n = chunk / sub``) and the ``n`` diagonal
      blocks pair by pair, both twice;
    - ``T [rhs]``: ``chunk x chunk x 2 head``; ``W S``, ``K^T U`` and
      ``Q S``: ``chunk x head x head`` each; ``QK U``: ``chunk x chunk x
      head``;

    and ``bytes``: ``q``, ``k``, ``v`` and the output in bf16, the log
    decay in float32 and ``b`` read or written once in the forward pass,
    and in the backward pass read again with the output's cotangent and
    written as five cotangents.  Nothing between them has to touch HBM,
    so this is the least; the plain ``jax.numpy`` form moves much more."""
    n = chunk // sub
    per_chunk = (
        2 * (n * (n - 1) // 2 + n) * sub * sub * head
        + chunk * chunk * 2 * head
        + 3 * chunk * head * head
        + chunk * chunk * head
    )
    chunks = tokens / chunk * heads * kda_layers
    per_token_head = 3 * head * BF16_BYTES + head * F32_BYTES + F32_BYTES + head * BF16_BYTES
    return {
        "flops": float(3 * 2 * per_chunk * chunks),
        "bytes": float(3 * per_token_head * tokens * heads * kda_layers),
    }


def mla_core_per_step(tokens: int, mla_layers: int, heads: int, nope: int,
                      rope: int, value: int, seq_len: int) -> dict:
    """What the latent attention's core (scope ``attention_core``: the
    fused kernels of ``ops/attention.py`` at 192 query/key and 128 value
    channels) needs per training step, forward and backward: ``flops`` of
    the score and value products over the causal half of the sequence
    (``seq_len / 2`` keys a query on average), the backward twice the
    forward, the recomputed scores and the zero channels the kernels pad
    to (256 for 192) not counted; ``bytes`` of q, k, v, the output and
    their cotangents in bf16, once each a pass."""
    per_token_head = seq_len / 2 * (nope + rope + value)
    channels = 2 * (nope + rope) + 2 * value
    return {
        "flops": float(3 * 2 * per_token_head * tokens * heads * mla_layers),
        "bytes": float(3 * channels * BF16_BYTES * tokens * heads * mla_layers),
    }
