"""What a recomputed half of a block keeps (``transformer_lm.Block.remat``).

``Block`` recomputes its mixer half and its feed-forward half in the
backward pass, each on its own.  A half keeps its input and **the
outputs of its wide input products**; everything else (norms,
activations, gates, convolutions, every Pallas kernel but the two cores
of 6 below: the scans, the grouped expert products, the KDA mixer's fused
passes) runs again.  A product
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

Beside the products, two kinds of thing that are no product and are kept
for what they cost to make, not for their FLOPs (ISSUEs 45 and 47):

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
6. what a core's forward kernel writes (a core: Pallas kernels under a
   ``custom_vjp``; what its forward rule hands its backward rule beside
   the inputs, through :func:`kept_core`).  The half still makes the
   core's inputs again (projections, rotations, repeats, padding); without
   the results it also runs the forward kernel a second time, only to make
   what the core's own backward kernel reads.  Two cores take it:

   - the chunk-wise delta rule's
     (``ops/linear_attention.py::kernel_kda_results``): the output ``[B,
     T, H dv]`` in the model's dtype, the state at the start of each grid
     step's token block ``[B, T / 512, H, dv, dk]`` and every chunk's
     ``T`` ``[B, T / 64, H, 64, 64]``, float32 both; 134 + 67 + 134 = 335
     MB a layer at Kimi Linear's 32 heads of 128 and 16,384 tokens, for 11
     ms (a state a chunk would be 537 MB: the backward kernel walks a
     block's chunks forward from the one state it is handed instead);
   - the fused attention's
     (``ops/attention.py::fused_attention_results``): the output ``[B, T,
     H, D]`` in the model's dtype and the scores' log-sum-exp, float32, 4
     bytes a head and token; 134 + 2 MB at Kimi Linear's latent layer (32
     heads of 128 values, 16,384 tokens), 84 MB a core in Phi-4-mini-flash,
     67 in Nemotron 3 Nano, 34 in Granite 4.0-H, 31 in OLMo Hybrid, for 4
     to 16 ms a layer.

   A core's results are kept where the forward pass they save is dear for
   the bytes they hold.  The rule leaves out: ``ssd_core`` (23.0 ms for
   three passes of nine layers in ``granite_h_train``, 0.7 ms a layer,
   for 130 MB a layer of output and states: 1.2 GB for 6 ms in a cell
   that stands at 15.22 GB) and ``sscan_core`` likewise (24.06 ms for
   three passes, ``[8192, 5120]`` outputs); ``gdn_core``, which is
   ``jax.numpy`` code with no residual of its own to name; the grouped
   expert products, whose outputs are products' (PERF.md section 7).

The modules name those outputs with :func:`kept`; :func:`half` is the
``nn.remat`` whose policy saves that name and nothing else.  Outside a
recomputed half :func:`kept` and :func:`kept_plan` return their argument
and :func:`kept_core` names nothing, so a model that recomputes nothing
traces the program it traced before.  What a traced half holds is counted
like the ops' routes, at trace time in the process-global registry
(``remat/products_kept``, ``remat/bytes_kept``, ``moe/plan_kept``,
``remat/cores_kept``).
"""

from __future__ import annotations

import threading

import flax.linen as nn
import jax
from jax.ad_checkpoint import checkpoint_name

from distributed_tensorflow_models_tpu.telemetry.registry import (
    MOE_PLAN_KEPT,
    REMAT_BYTES_KEPT,
    REMAT_CORES_KEPT,
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


def _count_bytes(x):
    get_registry().counter(REMAT_BYTES_KEPT).inc(x.size * x.dtype.itemsize)


def _named(x):
    _count_bytes(x)
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


def kept_core(results):
    """The name a half keeps, for a core (Pallas kernels under a
    ``custom_vjp``) to give what its forward rule hands its backward rule
    beside the inputs (``results``, their shapes: the core asks while the
    half that calls it is traced, its rule is traced later, outside
    :func:`half`), and None outside a recomputed half: counted as one core
    and by its bytes."""
    if not _tracing.halves:
        return None
    get_registry().counter(REMAT_CORES_KEPT).inc()
    for x in results:
        _count_bytes(x)
    return KEPT_NAME
