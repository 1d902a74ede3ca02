"""Model FLOPs of one OLMoE training token, and the expert products' own
operations and bytes per step, counted from shapes.

Per layer and token the forward pass multiplies by the attention
projections (4 d^2 weights: q, k, v, out; no grouped queries), attends
over the sequence (QK^T and AV: 2 T d multiply-accumulates together, the
whole T for every position as ``flops/gpt2.py`` counts it), by the router
(d x E) and by the **active** experts only: ``top_k`` experts of three
d x f matrices each (gate, up, down).  The output head multiplies by
d x V; the embedding lookup is no matrix multiplication.  A MAC is 2
FLOPs, and forward + backward is 3x forward.  RMSNorm, RoPE, the
softmaxes, SiLU, the sort and gathers of the dispatch, the losses and the
optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2


def forward_macs_per_token(n_layer: int, hidden: int, expert_width: int,
                           n_experts: int, top_k: int, vocab_size: int,
                           seq_len: int) -> int:
    per_layer = (
        4 * hidden * hidden
        + 2 * seq_len * hidden
        + top_k * 3 * hidden * expert_width
        + hidden * n_experts
    )
    return n_layer * per_layer + hidden * vocab_size


def flops_per_item(n_layer: int, hidden: int, expert_width: int,
                   n_experts: int, top_k: int, vocab_size: int,
                   seq_len: int) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(
        n_layer, hidden, expert_width, n_experts, top_k, vocab_size, seq_len
    )


def expert_products_per_step(tokens: int, n_layer: int, hidden: int,
                             expert_width: int, n_experts: int,
                             top_k: int) -> dict:
    """What the grouped products of the expert layers need per training
    step of ``tokens`` tokens (scope ``moe_experts``), forward and both
    backward products: ``flops``, and ``bytes`` in bf16 with the sorted
    rows, the gate, up and hidden rows, the output rows and the three
    weight stacks moved once each per pass."""
    rows = top_k * tokens
    flops = 3 * 2 * rows * 3 * hidden * expert_width
    row_bytes = rows * (hidden + 3 * expert_width + hidden)
    weight_bytes = n_experts * 3 * hidden * expert_width
    return {
        "flops": float(n_layer * flops),
        "bytes": float(n_layer * 3 * BF16_BYTES * (row_bytes + weight_bytes)),
    }
