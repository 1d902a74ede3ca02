"""Model FLOPs of one GPT-2 training token, counted from shapes.

Per layer and token the forward pass multiplies by the attention
projections (4 d^2 weights: q, k, v, out) and the MLP (2 d d_ff), and
attends over the sequence: QK^T and AV are 2 T d multiply-accumulates
together (the whole T for every position, the usual convention of the
PaLM paper's appendix B; a causal kernel that skips the masked half
does not lower the count).  The output head multiplies by d x V; the
embedding lookups are not matrix multiplications.  A MAC is 2 FLOPs,
and forward + backward is 3x forward.  LayerNorm, GELU, softmax, the
loss and the optimizer are left out.
"""

from __future__ import annotations


def forward_macs_per_token(n_layer: int, n_embd: int, n_inner: int,
                           vocab_size: int, seq_len: int) -> int:
    per_layer = 4 * n_embd * n_embd + 2 * n_embd * n_inner + 2 * seq_len * n_embd
    return n_layer * per_layer + n_embd * vocab_size


def flops_per_item(n_layer: int, n_embd: int, n_inner: int,
                   vocab_size: int, seq_len: int) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(
        n_layer, n_embd, n_inner, vocab_size, seq_len
    )
