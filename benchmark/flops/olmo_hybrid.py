"""Model FLOPs of one Olmo-Hybrid training token as the ``olmo_hybrid``
configuration cuts it (one chip of 2 that share each layer's heads), and
the gated delta rule's own operations and bytes per step, counted from
shapes.

Per token the forward pass multiplies by

- a delta-rule mixer at the ``heads`` held here: the query and key
  projections (2 x hidden x heads x key), the value, gate and output
  projections (3 x hidden x heads x value), the two per-head projections
  of the decay and ``b`` (2 x hidden x heads), and the rule itself as
  the recurrence states it, per head three passes over the ``key x
  value`` state (``k^T S``, the rank-one update, ``S^T q``);
- a full-attention layer at the same ``heads`` of ``attention_head``
  channels: four projections (4 x hidden x heads x attention_head) and
  the core over the whole sequence (``2 x seq_len x attention_head`` per
  head: the whole length for every position, as ``flops/gpt2.py`` counts
  it);
- the gated feed-forward, whole (3 x hidden x dense_width), in every
  layer;
- the output head (hidden x vocab_size: the slice).

A MAC is 2 FLOPs and forward + backward is 3x forward; recomputation is
not counted.  Embeddings, the norms, the short convolutions, the
activations, the rotation and the optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def forward_macs_per_token(gdn_layers: int, attention_layers: int, hidden: int,
                           heads: int, key: int, value: int, attention_head: int,
                           dense_width: int, vocab_size: int, seq_len: int) -> int:
    gdn = (
        hidden * heads * (2 * key + 3 * value)
        + 2 * hidden * heads
        + 3 * heads * key * value
    )
    attention = (
        4 * hidden * heads * attention_head
        + 2 * seq_len * heads * attention_head
    )
    return (
        gdn_layers * gdn
        + attention_layers * attention
        + (gdn_layers + attention_layers) * 3 * hidden * dense_width
        + hidden * vocab_size
    )


def flops_per_item(**kwargs) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(**kwargs)


def gdn_core_per_step(tokens: int, gdn_layers: int, heads: int, key: int,
                      value: int, chunk: int, sub: int) -> dict:
    """What the chunk-wise gated delta rule (scope ``gdn_core``,
    ``ops/linear_attention.py::chunked_gdn``) needs per training step of
    ``tokens`` tokens, forward and backward (twice the forward), counted
    as ``flops/kimi_linear.py::kda_core_per_step`` counts the
    per-channel rule: ``flops`` per chunk and head of

    - the masked key-key and query-key products, the ``n (n + 1) / 2``
      blocks of ``sub x sub x key`` on and below the diagonal (``n =
      chunk / sub``), both;
    - ``T [rhs]``: ``chunk x chunk x (value + key)``; ``W S``, ``K^T U``
      and ``Q S``: ``chunk x key x value`` each; ``QK U``: ``chunk x
      chunk x value``;

    and ``bytes``: ``q``, ``k``, ``v`` and the output in bf16, the log
    decay and ``b`` in float32 (one number each a head and token) read or
    written once in the forward pass, and in the backward pass read again
    with the output's cotangent and written as five cotangents.  Nothing
    between them has to touch HBM, so this is the least; the plain
    ``jax.numpy`` form moves much more.  The time under the scope holds
    the forward pass twice where the blocks are recomputed and the need
    counts it once, so the share cannot pass 100."""
    n = chunk // sub
    per_chunk = (
        2 * (n * (n + 1) // 2) * sub * sub * key
        + chunk * chunk * (value + key)
        + 3 * chunk * key * value
        + chunk * chunk * value
    )
    chunks = tokens / chunk * heads * gdn_layers
    per_token_head = (2 * key + 2 * value) * BF16_BYTES + 2 * F32_BYTES
    return {
        "flops": float(3 * 2 * per_chunk * chunks),
        "bytes": float(3 * per_token_head * tokens * heads * gdn_layers),
    }
