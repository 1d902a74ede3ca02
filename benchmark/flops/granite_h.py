"""Model FLOPs of one granite-4.0-h-micro training token as the
``granite_h_micro`` configuration cuts it (ten layers, an eighth of the
vocabulary, every head and width whole), and the state-space scan's own
operations and bytes per step, counted from shapes.

Per token the forward pass multiplies by

- a Mamba-2 mixer: the input projection (hidden x (2 x inner + 2 x state
  + heads), ``inner = ssm_heads x ssm_head``), the output projection
  (inner x hidden), and the recurrence as it is stated, per head two
  passes over the ``state x head`` state (the write ``B x^T`` and the
  read ``S^T C``; the decay is no product);
- the attention layer: the query and output projections (2 x hidden x
  heads x attention_head), the key and value projections at the grouped
  heads (2 x hidden x kv_heads x attention_head), and the core over the
  whole sequence (``2 x seq_len x attention_head`` per query head: the
  whole length for every position, as ``flops/gpt2.py`` counts it);
- the gated feed-forward (3 x hidden x dense_width), in every layer;
- the output head (hidden x vocab_size: the slice; tied, so the one
  matrix is counted where it multiplies).

A MAC is 2 FLOPs and forward + backward is 3x forward; recomputation is
not counted.  The embedding's gather, the norms, the short convolution,
the activations, the gate and the optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def forward_macs_per_token(ssm_layers: int, attention_layers: int, hidden: int,
                           ssm_heads: int, ssm_head: int, ssm_state: int,
                           heads: int, kv_heads: int, attention_head: int,
                           dense_width: int, vocab_size: int, seq_len: int) -> int:
    inner = ssm_heads * ssm_head
    ssm = (
        hidden * (2 * inner + 2 * ssm_state + ssm_heads)
        + inner * hidden
        + 2 * ssm_heads * ssm_state * ssm_head
    )
    attention = (
        2 * hidden * heads * attention_head
        + 2 * hidden * kv_heads * attention_head
        + 2 * seq_len * heads * attention_head
    )
    return (
        ssm_layers * ssm
        + attention_layers * attention
        + (ssm_layers + attention_layers) * 3 * hidden * dense_width
        + hidden * vocab_size
    )


def flops_per_item(**kwargs) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(**kwargs)


def ssd_core_per_step(tokens: int, ssm_layers: int, heads: int, head: int,
                      state: int, chunk: int) -> dict:
    """What the chunk-wise state-space scan (scope ``ssd_core``,
    ``ops/ssm.py::chunked_ssd``) needs per training step of ``tokens``
    tokens, forward and backward (twice the forward), counted as
    ``flops/olmo_hybrid.py::gdn_core_per_step`` counts the gated delta
    rule: ``flops`` per chunk of

    - ``C B^T``, ``chunk x chunk x state``, **once a chunk and not once a
      head** (one group: the heads share ``B`` and ``C``);
    - per head the masked scores times the values, ``chunk x chunk x
      head``, the read of the carried state ``C S`` and the chunk's write
      ``B^T V``, ``chunk x state x head`` each;

    and ``bytes``: ``x`` and the output in bf16 (``heads x head`` each a
    token), ``B`` and ``C`` in bf16 (``state`` each a token, whatever the
    number of heads), ``dt`` in float32 (one number a head and token)
    read or written once in the forward pass, and in the backward pass
    read again with the output's cotangent and written as four
    cotangents.  Nothing between them has to touch HBM, so this is the
    least; the plain ``jax.numpy`` form moves much more.  The time under
    the scope holds the forward pass twice where the blocks are
    recomputed and the need counts it once, so the share cannot pass
    100."""
    per_chunk = (
        chunk * chunk * state
        + heads * (chunk * chunk * head + 2 * chunk * state * head)
    )
    chunks = tokens / chunk * ssm_layers
    per_token = (2 * heads * head + 2 * state) * BF16_BYTES + heads * F32_BYTES
    return {
        "flops": float(3 * 2 * per_chunk * chunks),
        "bytes": float(3 * per_token * tokens * ssm_layers),
    }
