"""Model FLOPs of one Nemotron 3 Nano training token as the
``nemotron3_nano`` configuration cuts it (nine layers, one chip's share of
16 that share each layer's experts, an eighth of the vocabulary, every
width whole), and the state-space scan's own operations and bytes per
step, counted from shapes.

Each layer is one sub-layer.  Per token the forward pass multiplies by

- a Mamba-2 mixer: the input projection (hidden x (2 x inner + 2 x
  groups x state + heads), ``inner = ssm_heads x ssm_head``), the output
  projection (inner x hidden), and the recurrence as it is stated, per
  head two passes over the ``state x head`` state (the write ``B x^T``
  and the read ``S^T C``; the decay is no product);
- an attention layer: the query and output projections (2 x hidden x
  heads x attention_head: 4096 channels against a width of 2688), the
  key and value projections at the grouped heads (2 x hidden x kv_heads
  x attention_head), and the core over the whole sequence (``2 x seq_len
  x attention_head`` per query head: the whole length for every
  position, as ``flops/gpt2.py`` counts it);
- an expert layer: the router (hidden x n_router), the shared expert
  (**two** matrices, 2 x hidden x shared_width) and the routed experts
  **this chip holds**: of a token's ``top_k`` assignments ``held /
  n_router`` fall here when the routing is even (6 x 8 / 128 = three
  eighths of an expert a token, each 2 x hidden x expert_width; the
  step's real share is the metric ``moe_held_share.tokens``);
- the output head (hidden x vocab_size: the slice).

A MAC is 2 FLOPs and forward + backward is 3x forward; recomputation is
not counted.  The embedding's gather, the norms, the short convolution,
the activations, the gate, the dispatch and the optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def forward_macs_per_token(ssm_layers: int, attention_layers: int,
                           expert_layers: int, hidden: int, ssm_heads: int,
                           ssm_head: int, ssm_state: int, ssm_groups: int,
                           heads: int, kv_heads: int, attention_head: int,
                           expert_width: int, shared_width: int, n_router: int,
                           held: int, top_k: int, vocab_size: int,
                           seq_len: int) -> float:
    inner = ssm_heads * ssm_head
    ssm = (
        hidden * (2 * inner + 2 * ssm_groups * ssm_state + ssm_heads)
        + inner * hidden
        + 2 * ssm_heads * ssm_state * ssm_head
    )
    attention = (
        2 * hidden * heads * attention_head
        + 2 * hidden * kv_heads * attention_head
        + 2 * seq_len * heads * attention_head
    )
    experts = (
        hidden * n_router
        + 2 * hidden * shared_width
        + top_k * held / n_router * 2 * hidden * expert_width
    )
    return (
        ssm_layers * ssm
        + attention_layers * attention
        + expert_layers * experts
        + hidden * vocab_size
    )


def flops_per_item(**kwargs) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(**kwargs)


def ssd_core_per_step(tokens: int, ssm_layers: int, heads: int, head: int,
                      state: int, chunk: int, groups: int = 1) -> dict:
    """What the chunk-wise state-space scan (scope ``ssd_core``,
    ``ops/ssm.py::chunked_ssd``) needs per training step of ``tokens``
    tokens, forward and backward (twice the forward), with ``B`` and ``C``
    one vector a group of heads (``groups`` 1 gives
    ``flops/granite_h.py::ssd_core_per_step``'s numbers): ``flops`` per
    chunk of

    - ``C B^T``, ``chunk x chunk x state``, **once a chunk and group**,
      not once a head;
    - per head the masked scores times the values, ``chunk x chunk x
      head``, the read of the carried state ``C S`` and the chunk's write
      ``B^T V``, ``chunk x state x head`` each;

    and ``bytes``: ``x`` and the output in bf16 (``heads x head`` each a
    token), ``B`` and ``C`` in bf16 (``groups x state`` each a token),
    ``dt`` in float32 (one number a head and token) read or written once
    in the forward pass, and in the backward pass read again with the
    output's cotangent and written as four cotangents.  Nothing between
    them has to touch HBM, so this is the least.  The time under the scope
    holds the forward pass twice where the layers are recomputed and the
    need counts it once, so the share cannot pass 100."""
    per_chunk = (
        groups * chunk * chunk * state
        + heads * (chunk * chunk * head + 2 * chunk * state * head)
    )
    chunks = tokens / chunk * ssm_layers
    per_token = (
        (2 * heads * head + 2 * groups * state) * BF16_BYTES + heads * F32_BYTES
    )
    return {
        "flops": float(3 * 2 * per_chunk * chunks),
        "bytes": float(3 * per_token * tokens * ssm_layers),
    }
