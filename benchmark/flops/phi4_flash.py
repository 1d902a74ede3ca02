"""Model FLOPs of one Phi-4-mini-flash-reasoning training token as the
``phi4_mini_flash`` configuration cuts it (six layers: published 0, 1, 16,
17, 18, 19; an eighth of the vocabulary; every head and width whole), and
what the two kernels this configuration brought need per step, counted
from shapes.

Per token the forward pass multiplies by

- a Mamba-1 mixer: the input projection (hidden x 2 inner), ``W_x``
  (inner x (dt_rank + 2 state)), ``W_dt`` (dt_rank x inner), the output
  projection (inner x hidden), and the recurrence as it is stated, two
  passes over the ``inner x state`` state (the write ``(dt x) B^T`` and the
  read ``h C``; the decay is no product);
- a differential attention layer: the query and output projections (2 x
  hidden x heads x head), the key and value projections at the grouped
  heads (2 x hidden x kv_heads x head), and the core: every query head
  scores ``head`` channels and reads ``2 head`` value channels (a pair's
  two value heads side by side) a position, over the whole sequence in the
  full layer (no causal discount, as ``flops/gpt2.py`` counts it) and over
  the window's 512 in a window layer;
- a cross-attention layer: the query and output projections and the same
  core over the whole sequence, no key or value projection;
- a gated memory unit: its two projections (2 x hidden x inner);
- the gated feed-forward (3 x hidden x dense_width), in every layer;
- the output head (hidden x vocab_size: the slice; tied, so the one
  matrix is counted where it multiplies).

A MAC is 2 FLOPs and forward + backward is 3x forward; recomputation is
not counted.  The embedding's gather, the norms, the short convolution,
the activations, the gates, ``lambda`` and the optimizer are left out.
"""

from __future__ import annotations

BF16_BYTES = 2
F32_BYTES = 4


def forward_macs_per_token(mamba_layers: int, window_layers: int, full_layers: int,
                           cross_layers: int, gmu_layers: int, hidden: int,
                           inner: int, state: int, dt_rank: int, heads: int,
                           kv_heads: int, head: int, window: int,
                           dense_width: int, vocab_size: int, seq_len: int) -> int:
    mamba = (
        hidden * 2 * inner + inner * (dt_rank + 2 * state) + dt_rank * inner
        + inner * hidden + 2 * inner * state
    )
    query_out = 2 * hidden * heads * head
    keys_values = 2 * hidden * kv_heads * head
    core = lambda span: heads * (head + 2 * head) * span
    layers = mamba_layers + window_layers + full_layers + cross_layers + gmu_layers
    return (
        mamba_layers * mamba
        + window_layers * (query_out + keys_values + core(window))
        + full_layers * (query_out + keys_values + core(seq_len))
        + cross_layers * (query_out + core(seq_len))
        + gmu_layers * 2 * hidden * inner
        + layers * 3 * hidden * dense_width
        + hidden * vocab_size
    )


def flops_per_item(**kwargs) -> float:
    """Forward + backward FLOPs of one token."""
    return 3.0 * 2.0 * forward_macs_per_token(**kwargs)


def sscan_core_per_step(tokens: int, mamba_layers: int, inner: int, state: int) -> dict:
    """What Mamba-1's selective scan (scope ``sscan_core``,
    ``ops/selective_scan.py::selective_scan``) needs per training step of
    ``tokens`` tokens, forward and backward (twice the forward), whatever
    implements it.

    ``flops``: a token of a channel and state is three multiply-adds (the
    decay's exponent ``dt A``, the update ``a h + u B``, the read ``h C``)
    and an exponential, counted as 6 FLOPs and none.  **None of it is the
    MXU's**: the roofline of ``benchmark/lib/roofline.py`` is the larger of
    these over the bf16 matrix peak and the bytes over the HBM peak, and
    here the **bytes bind** (2.5 ms a step against 0.12 ms): ``x`` and the
    output in bf16, ``dt`` in float32 (``inner`` each a token), ``B`` and
    ``C`` in bf16 (``state`` each), read or written once in the forward
    pass, and in the backward pass read again with the output's cotangent
    and written as four cotangents.  The state never has to touch HBM.
    The scan is bound by neither: it is element-wise work on the vector
    unit, whose peak ``benchmark/peaks.json`` does not hold, so the share
    reads low by design and says how far the scan is from costing only its
    traffic.  The time under the scope holds the forward pass twice where
    the blocks are recomputed and the need counts it once, so the share
    cannot pass 100."""
    per_token_flops = 3 * 2 * inner * state
    per_token_bytes = 2 * inner * BF16_BYTES + inner * F32_BYTES + 2 * state * BF16_BYTES
    return {
        "flops": float(3 * per_token_flops * tokens * mamba_layers),
        "bytes": float(3 * per_token_bytes * tokens * mamba_layers),
    }


def swa_core_per_step(tokens: int, window_layers: int, heads: int, kv_heads: int,
                      head: int, window: int) -> dict:
    """What the window layers' attention core (scope ``swa_core`` inside
    ``attention_core``) needs per training step of one sequence of
    ``tokens`` tokens, forward and backward (twice the forward), whatever
    implements it.

    ``flops``: query ``t`` sees ``min(t + 1, window)`` keys; every query
    head scores ``head`` channels and reads ``2 head`` value channels a
    seen key (differential attention: a pair's value heads side by side).
    **The operations bind** (about 1 ms a step against 0.6).  ``bytes``:
    the queries (``heads x head``), keys and values (``kv_heads x head``
    each) and the output (``heads x 2 head``) in bf16, read or written once
    in the forward pass, and in the backward pass read again with the
    output's cotangent and written as three cotangents.  Masked pairs inside
    a tile, the padded query/key channels and the repeated key/value heads
    are the implementation's, not the need's."""
    seen = window * tokens - window * (window - 1) // 2 if tokens >= window else (
        tokens * (tokens + 1) // 2
    )
    per_token_bytes = (heads * head + 2 * kv_heads * head + heads * 2 * head) * BF16_BYTES
    return {
        "flops": float(3 * 2 * heads * 3 * head * seen * window_layers),
        "bytes": float(3 * per_token_bytes * tokens * window_layers),
    }
