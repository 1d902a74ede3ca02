"""What a recomputed half of a block keeps (``transformer_lm.Block.remat``).

``Block`` recomputes its mixer half and its feed-forward half in the
backward pass, each on its own.  A half keeps its input and **the
outputs of its wide input products**; everything else (norms,
activations, gates, convolutions, every Pallas kernel: the scan, the
attention core, the grouped expert products) runs again.  A product
``[T, d_in] x [d_in, d_out]`` kept saves ``d_in`` FLOP for each byte of
output it holds, so the set is the products with the most to save a
byte, the same for every model (ISSUE 42; PERF.md section 6):

1. the feed-forward's output where a post-norm reads it (``Block``,
   ``norm_placement="post"``: the norm's backward needs it, so without it
   all three products of the half run twice; before a pre-norm block's
   residual nothing needs it and nothing is named);
2. the feed-forwards' wide products, dense and shared alike: ``gate`` and
   ``up`` of ``GatedMLP``, ``up`` of ``MLP``;
3. ``Mamba2Mixer``'s, ``Mamba1Mixer``'s and ``GatedMemoryUnit``'s
   ``in_proj``;
4. what a source layer hands on to the layers that read it (the scan
   output a gated memory unit reads, the keys and values a
   cross-attention reads): later halves hold them as inputs anyway.

Beside the products, one thing that is no product and is kept for what it
costs to make, not for its FLOPs (ISSUE 45):

5. the routing plan of an expert layer that holds a range of its
   router's experts (``parallel/moe.py::RoutingPlan``, through
   :func:`kept_plan`): the router's float32 logits ``[T, E]``, the
   top-k's scores and experts ``[T, k]``, the sorted order of the
   assignments ``[T k]`` and the experts' counts ``[E]``; 4 (E + 3 k) T
   bytes, 18.4 MB a layer at Kimi Linear's 256 experts, top-8 and 16,384
   tokens, 4.8 MB at Nemotron 3 Nano's 128, top-6 and 8,192.  Without it
   the backward pass runs the router's product (float32 at full
   precision: six bf16 passes), the top-k, the sort and the count again to
   rebuild a few MB of indices.

The modules name those outputs with :func:`kept`; :func:`half` is the
``nn.remat`` whose policy saves that name and nothing else.  Outside a
recomputed half :func:`kept` and :func:`kept_plan` return their argument,
so a model that recomputes nothing traces the program it traced before.
What a traced half holds is counted like the ops' routes, at trace time
in the process-global registry (``remat/products_kept``,
``remat/bytes_kept``, ``moe/plan_kept``).
"""

from __future__ import annotations

import threading

import flax.linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name

from distributed_tensorflow_models_tpu.telemetry.registry import (
    MOE_PLAN_KEPT,
    REMAT_BYTES_KEPT,
    REMAT_PRODUCTS_KEPT,
    get_registry,
)

KEPT_NAME = "kept_product"


class _Tracing(threading.local):
    """How many recomputed halves this thread is tracing (the AOT thread
    and the loop's trace the step side by side)."""

    halves = 0


_tracing = _Tracing()


def half(fn):
    """``fn(module, y, *read)`` recomputed in the backward pass but for
    what :func:`kept` names inside it.  ``read`` is what a half reads of
    an earlier layer beside the residual stream (a memory, keys and
    values): an input like ``y``, so kept and not recomputed; what a half
    hands on to later layers it names with :func:`kept`."""

    def traced(mdl, *args):
        _tracing.halves += 1
        try:
            return fn(mdl, *args)
        finally:
            _tracing.halves -= 1

    return nn.remat(
        traced, policy=jax.checkpoint_policies.save_only_these_names(KEPT_NAME)
    )


def _named(x):
    get_registry().counter(REMAT_BYTES_KEPT).inc(x.size * x.dtype.itemsize)
    return checkpoint_name(x, KEPT_NAME)


def kept(x):
    """``x``, a product's output, and inside a recomputed half the array
    that half keeps."""
    if not _tracing.halves:
        return x
    get_registry().counter(REMAT_PRODUCTS_KEPT).inc()
    return _named(x)


def kept_plan(plan):
    """``plan``, an expert layer's routing plan (a tuple of arrays), and
    inside a recomputed half the arrays that half keeps: counted as one
    plan and by their bytes, not as products."""
    if not _tracing.halves:
        return plan
    get_registry().counter(MOE_PLAN_KEPT).inc()
    return jax.tree.map(_named, plan)
