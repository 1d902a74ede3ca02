"""Expert parallelism: Switch-style mixture-of-experts over the ``expert``
mesh axis.

The reference has no MoE (SURVEY.md §2.4: "out of scope") — like
``parallel/pipeline.py`` this is the framework's design-headroom layer for
the reserved ``expert`` axis, in the TPU-native form: expert FFN weights
shard one-expert-per-rank over ``expert``; tokens are exchanged with
``lax.all_to_all`` (compiled to ICI all-to-all), each rank runs its expert
on the tokens routed to it, and a second all-to-all returns them.  One
compiled SPMD program, no parameter servers, no host-side routing.

Router: top-1 ("switch") gating with a per-expert capacity.  Tokens over
capacity are *dropped* (their combine weight is zero and the residual path
carries them) — the standard Switch-Transformer trade that keeps every
shape static for XLA (SURVEY.md §7: no dynamic shapes).  The auxiliary
load-balancing loss (fraction-dispatched x mean-gate per expert, scaled by
E) is returned for the caller to add to the task loss.

Everything is differentiable: ``all_to_all`` has a transpose rule, routing
uses one-hot matmuls, and capacity masking is a multiply.

Beside it, for the models that state it (OLMoE and its successors,
ROADMAP R1-R4): :func:`topk_moe_ffn`, softmax-then-top-k routing over
experts with **no capacity**: every one of a token's ``top_k``
assignments is computed.  An expert is three matrices with a SiLU gate
(OLMoE, Kimi Linear) or two around a squared ReLU without a gate (the
Nemotron-H family), by whether ``params`` holds ``w_gate``; both go
through one function (:func:`_expert_rows`).  The assignments are sorted by expert, the rows
gathered, each projection is one grouped matrix product over contiguous
groups of uneven size (the Pallas grouped matmul that ships with jax,
``megablox``: static shapes, the group sizes are data), and the weighted
rows are summed back per token.  No tensor grows with tokens x experts x
capacity.  ``jax.lax.ragged_dot`` was measured against it on a v5e at the
cell's shapes and lost (100 against 135 TFLOP/s over forward and both
backward products, PERF.md section 6, PR 25); XLA's rewrite of it also
drops the instruction's ``op_name``, and with it the scopes below.

The same layer is one chip's share of an expert-parallel deployment when
it is told which experts it holds (``held = (first, count)``; Kimi
Linear's 256 experts over 32 chips are 8 here, Nemotron 3 Nano's 128 over
16 chips too): the router keeps every
output and the top-k runs over all of them, the assignments are sorted
with the held experts first, and the rows of that prefix are gathered,
multiplied and added back slab by slab (:func:`_held_local`; rows of a
slab that are no held expert's are not visited by the grouped product and
come out zero).  What the absent experts would add is left out; no
assignment to a held expert is dropped, whatever the imbalance.  The
scores may be a sigmoid in place of the softmax, renormalised over the
chosen experts and scaled, as the models that state it have them.

Such a layer keeps one assignment in 16 or 32, so what it does with all
of them is made once and what it does again and again follows the kept
rows (ISSUE 45; PERF.md section 6).  What routing decided is a
:class:`RoutingPlan` (the float32 logits, the top-k's scores and experts,
the sorted order, the counts: 18.4 MB a layer at Kimi Linear's shapes,
4.8 MB at Nemotron 3 Nano's); a caller that recomputes the layer in the
backward pass is handed it to keep (``keep``), and then runs no router
product, top-k, sort or count a second time: every later use, the
gradient's too, reads the plan (:func:`_at_choice`).  A slab is the even
share of the held experts times ``_HELD_SLAB_MARGIN``, not a multiple of
it, since every row of a slab is gathered and scatter-added whether kept
or not; the shares are gathered, and their gradients scattered, for a
slab's rows and not for all assignments (``held_slabs`` says how many
slabs a step's routing took: 1 in an ordinary step).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental.pallas.ops.tpu.megablox import ops as megablox
from jax.sharding import Mesh, PartitionSpec as P

from distributed_tensorflow_models_tpu.core.mesh import AxisNames

class MoEOutput(NamedTuple):
    out: jax.Array  # [tokens, d_model] combined expert outputs
    aux_loss: jax.Array  # scalar load-balancing loss
    dropped_fraction: jax.Array  # scalar diagnostics


def init_moe_params(
    rng: jax.Array, num_experts: int, d_model: int, d_ff: int
) -> dict:
    """Per-expert FFN (w_in [E, d, f], w_out [E, f, d]) + router [d, E].
    Shard the expert-stacked leaves over ``expert`` with
    :func:`moe_param_spec`."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = 1.0 / jnp.sqrt(d_model)
    scale_out = 1.0 / jnp.sqrt(d_ff)
    return {
        "router": jax.random.normal(k1, (d_model, num_experts)) * scale_in,
        "w_in": jax.random.normal(k2, (num_experts, d_model, d_ff))
        * scale_in,
        "w_out": jax.random.normal(k3, (num_experts, d_ff, d_model))
        * scale_out,
    }


def moe_param_spec(axis: str = AxisNames.EXPERT) -> dict:
    return {
        "router": P(),
        "w_in": P(axis),
        "w_out": P(axis),
    }


def _route_local(x, router, num_experts: int, capacity: int):
    """Top-1 routing of local tokens [n, d] → dispatch/combine tensors.

    Returns (dispatch [n, E, C] 0/1, combine [n, E, C] gate-weighted,
    aux_loss, dropped_fraction).  Position within an expert's capacity is
    assigned in token order (cumsum), matching the Switch reference.
    """
    n = x.shape[0]
    logits = x @ router  # [n, E] — router always in f32 for stable softmax
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)  # [n]
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    onehot = jax.nn.one_hot(expert_idx, num_experts, dtype=jnp.float32)
    # Position of each token in its expert's queue (0-based).
    position = jnp.cumsum(onehot, axis=0) * onehot - onehot  # [n, E]
    pos = jnp.sum(position, axis=-1).astype(jnp.int32)  # [n]
    # one_hot of an out-of-range pos is an all-zero row, which IS the
    # capacity mask: over-capacity tokens get a zero dispatch slot.
    pos_onehot = jax.nn.one_hot(pos, capacity, dtype=jnp.float32)
    dispatch_nec = onehot[:, :, None] * pos_onehot[:, None, :]  # [n,E,C]
    combine_nec = dispatch_nec * gate[:, None, None]

    # Switch aux loss: E * sum_e fraction_tokens(e) * mean_prob(e).
    fraction = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = num_experts * jnp.sum(fraction * mean_prob)
    dropped = 1.0 - jnp.sum(dispatch_nec) / n
    return dispatch_nec, combine_nec, aux, dropped


def moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    mesh: Mesh,
    capacity_factor: float = 1.25,
    axis: str = AxisNames.EXPERT,
    activation=jax.nn.relu,
) -> MoEOutput:
    """Expert-parallel Switch FFN over tokens ``x`` [tokens, d_model].

    Tokens shard over ``axis`` (each expert rank also holds a token shard
    — the standard EP layout where the same devices carry both roles);
    expert weights shard one-per-rank.  Two ``all_to_all`` collectives move
    each token to its expert and back.
    """
    num_experts = params["w_in"].shape[0]
    e_size = mesh.shape[axis]
    if num_experts % e_size:
        raise ValueError(
            f"num_experts {num_experts} not divisible by expert axis {e_size}"
        )
    tokens = x.shape[0]
    if tokens % e_size:
        raise ValueError(
            f"tokens {tokens} not divisible by expert axis {e_size}"
        )
    local_tokens = tokens // e_size
    capacity = max(
        1, int(capacity_factor * local_tokens / num_experts)
    )

    def per_device(params, x_local):
        experts_local = num_experts // e_size
        dispatch, combine, aux, dropped = _route_local(
            x_local.astype(jnp.float32),
            params["router"],
            num_experts,
            capacity,
        )
        # Gather expert inputs: [E, C, d] on the source rank...
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, x_local)
        # ...reshape to [e_size, experts_local, C, d] and exchange so rank r
        # receives every source's slots for its local experts.
        expert_in = expert_in.reshape(
            e_size, experts_local, capacity, -1
        )
        recv = lax.all_to_all(
            expert_in, axis, split_axis=0, concat_axis=0, tiled=False
        )  # [e_size(source), experts_local, C, d]

        w_in = params["w_in"]  # [experts_local, d, f] (sharded slice)
        w_out = params["w_out"]
        h = activation(jnp.einsum("slcd,ldf->slcf", recv, w_in))
        expert_out = jnp.einsum("slcf,lfd->slcd", h, w_out)

        # Send results back to their source ranks.
        back = lax.all_to_all(
            expert_out, axis, split_axis=0, concat_axis=0, tiled=False
        )  # [e_size(expert-group), experts_local, C, d]
        back = back.reshape(num_experts, capacity, -1)
        out = jnp.einsum("nec,ecd->nd", combine, back)
        aux = lax.pmean(aux, axis)
        dropped = lax.pmean(dropped, axis)
        return out.astype(x_local.dtype), aux, dropped

    fn = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(moe_param_spec(axis), P(axis)),
        out_specs=(P(axis), P(), P()),
    )
    out, aux, dropped = fn(params, x)
    return MoEOutput(out=out, aux_loss=aux, dropped_fraction=dropped)


def moe_ffn_reference(
    params: dict,
    x: jax.Array,
    *,
    num_ranks: int,
    capacity_factor: float = 1.25,
    activation=jax.nn.relu,
) -> MoEOutput:
    """Single-device oracle with identical routing/capacity semantics
    (including the per-source-rank capacity accounting EP implies):
    processes the token shards rank-by-rank exactly as the EP layout
    would."""
    num_experts = params["w_in"].shape[0]
    tokens = x.shape[0]
    if tokens % num_ranks:
        raise ValueError(
            f"tokens {tokens} not divisible by num_ranks {num_ranks}"
        )
    local_tokens = tokens // num_ranks
    capacity = max(1, int(capacity_factor * local_tokens / num_experts))

    outs, auxes, drops = [], [], []
    for r in range(num_ranks):
        xl = x[r * local_tokens : (r + 1) * local_tokens].astype(
            jnp.float32
        )
        dispatch, combine, aux, dropped = _route_local(
            xl, params["router"], num_experts, capacity
        )
        expert_in = jnp.einsum("nec,nd->ecd", dispatch, xl)
        h = activation(
            jnp.einsum("ecd,edf->ecf", expert_in, params["w_in"])
        )
        expert_out = jnp.einsum("ecf,efd->ecd", h, params["w_out"])
        outs.append(
            jnp.einsum("nec,ecd->nd", combine, expert_out).astype(x.dtype)
        )
        auxes.append(aux)
        drops.append(dropped)
    return MoEOutput(
        out=jnp.concatenate(outs, axis=0),
        aux_loss=jnp.mean(jnp.stack(auxes)),
        dropped_fraction=jnp.mean(jnp.stack(drops)),
    )


# --- Exact top-k routing over experts (no capacity) ----------------------

# ``jax.named_scope`` names of the expert layer, path elements of every
# instruction's ``op_name`` in the compiled step (PERF.md section 3): the
# whole layer; routing, sort, gather and the weighted sum back; the
# grouped products and the activation.
MOE_SCOPE = "moe"
MOE_DISPATCH_SCOPE = "moe_dispatch"
MOE_EXPERTS_SCOPE = "moe_experts"


class TopKMoEOutput(NamedTuple):
    out: jax.Array  # [tokens, d_model]
    aux_loss: jax.Array  # E * sum_e f_e * P_e (unweighted)
    z_loss: jax.Array  # mean(logsumexp(router logits)^2) (unweighted)
    load_max_over_mean: jax.Array  # fullest expert's assignments / mean
    held_share: jax.Array  # share of the assignments that fell on held experts
    held_slabs: jax.Array  # slabs of sorted rows the held experts' took (1: an ordinary step)


@jax.custom_vjp
def _permute_rows(rows, perm, inverse):
    """``rows[perm]`` for a permutation ``perm`` whose inverse is given:
    the transpose of a gather by a permutation is the gather by its
    inverse, which is what the backward pass runs in place of the
    scatter-add XLA would derive."""
    del inverse
    return rows[perm]


def _permute_rows_fwd(rows, perm, inverse):
    return rows[perm], inverse


def _permute_rows_bwd(inverse, g):
    return g[inverse], None, None


_permute_rows.defvjp(_permute_rows_fwd, _permute_rows_bwd)


def _tiling(dtype) -> tuple[int, int, int]:
    """The grouped product's row, k and n tiles: for 2-byte operands the
    best of four tilings tried on a v5e at [131072, 2048] x [64, 2048,
    1024] (PERF.md section 6); 4-byte operands (the float32 comparison
    with the reference) take tiles a quarter the size to stay inside the
    kernel's fast memory."""
    return (512, 1024, 1024) if jnp.dtype(dtype).itemsize <= 2 else (256, 512, 512)


def grouped_matmul(
    rows: jax.Array, weights: jax.Array, group_sizes, leading: bool = False
):
    """``rows`` [m, k], sorted by group, times each group's own matrix of
    ``weights`` [groups, k, n]: row ``i`` of group ``g`` gives ``rows[i]
    @ weights[g]``.  ``m`` has to be a multiple of the row tile
    (:func:`_pad_rows`).  With ``leading``, ``weights`` holds the first
    ``len(weights)`` of the ``len(group_sizes)`` groups only (megablox's
    ``group_offset`` 0): their rows are visited, the others come out
    zero.  Differentiable in ``rows`` and ``weights``
    (megablox's own backward products).  Off the TPU the kernel runs in
    Pallas' interpret mode."""
    return megablox.gmm(
        rows,
        weights,
        group_sizes,
        rows.dtype,
        _tiling(rows.dtype),
        jnp.zeros((), jnp.int32) if leading else None,
        None,
        False,
        jax.default_backend() != "tpu",
    )


def _pad_rows(rows: jax.Array, group_sizes: jax.Array):
    """Zero rows up to a multiple of the row tile, counted into the last
    group: they cost a tile at most and change no result."""
    pad = -rows.shape[0] % _tiling(rows.dtype)[0]
    if pad:
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    return rows, group_sizes


class Routing(NamedTuple):
    """How a model states its router: the scores (``"softmax"`` over the
    experts, or a ``"sigmoid"`` of each logit), whether the chosen
    experts' scores are renormalised to sum to 1, and the factor on the
    result."""

    scoring: str = "softmax"
    renormalize: bool = False
    scale: float = 1.0


def _router_logits(router: jax.Array, x: jax.Array):
    """The router's product on tokens ``x`` [n, d], in float32 at full
    precision: a bf16 product here moves near-ties across the top-k
    boundary."""
    return jnp.dot(
        x.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )


def _scores(logits: jax.Array, routing: Routing):
    if routing.scoring == "softmax":
        return jax.nn.softmax(logits, axis=-1)
    if routing.scoring == "sigmoid":
        return jax.nn.sigmoid(logits)
    raise ValueError(
        f"unknown router scoring {routing.scoring!r} "
        "(want 'softmax' or 'sigmoid')"
    )


def _chosen_weight(weight: jax.Array, routing: Routing):
    """The top-k's scores [n, top_k], renormalised and scaled as
    ``routing`` says."""
    if routing.renormalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if routing.scale != 1.0:
        weight = weight * routing.scale
    return weight


def route_topk(
    router: jax.Array, x: jax.Array, top_k: int, routing: Routing = Routing()
):
    """``(logits, probs, weight, expert)`` of tokens ``x`` [n, d]: the
    router's product, its scores (``routing.scoring``) and the choice, in
    float32 at full precision.  ``weight`` and ``expert`` are [n, top_k],
    largest first, ties to the lower expert index; ``weight`` is the
    chosen scores, renormalised and scaled as ``routing`` says."""
    logits = _router_logits(router, x)
    probs = _scores(logits, routing)
    weight, expert = lax.top_k(probs, top_k)
    return logits, probs, _chosen_weight(weight, routing), expert


def squared_relu(x):
    """``relu(x)^2``: the activation of the Nemotron-H family's
    feed-forwards, dense, shared and routed alike."""
    return jnp.square(jax.nn.relu(x))


def _expert_stacks(params: dict) -> tuple:
    """The expert matrices in the order :func:`_expert_rows` takes them:
    ``(w_gate, w_up, w_down)`` of gated SiLU experts, or ``(w_up,
    w_down)`` of experts without a gate (squared ReLU)."""
    names = ("w_gate", "w_up", "w_down") if "w_gate" in params else ("w_up", "w_down")
    return tuple(params[name] for name in names)


def _expert_rows(rows, stacks, grouped, dtype):
    """Sorted ``rows`` through their experts, ``grouped`` the product
    with each group's own matrix: ``W_down (silu(W_gate h) * W_up h)`` for
    three stacks, ``W_down relu(W_up h)^2`` for two; the activation in
    float32."""
    *inner, w_down = stacks
    into = lambda w: grouped(rows, w.astype(dtype))
    if len(inner) == 2:
        gate, up = into(inner[0]), into(inner[1])
        hidden = jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
    else:
        hidden = squared_relu(into(inner[0]).astype(jnp.float32))
    return grouped(hidden.astype(dtype), w_down.astype(dtype))


def _routing_statistics(counts, probs, logits, n: int, top_k: int):
    """``(aux, z, load)`` of one rank's step: the load-balancing loss ``E
    sum_e f_e P_e``, the router z-loss and the fullest expert's
    assignments over the mean."""
    num_experts = counts.shape[0]
    fraction = counts.astype(jnp.float32) / (n * top_k)
    aux = num_experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    load = jnp.max(fraction) * num_experts
    return aux, z, load


def _topk_local(
    params: dict, x: jax.Array, top_k: int, dtype,
    routing: Routing, held: Optional[tuple[int, int]], keep,
):
    """The layer on one rank's tokens ``x`` [n, d].  ``held`` None: every
    expert is here; ``(first, count)``: the expert stacks hold those
    ``count`` of the router's experts (:func:`_held_local`, which alone
    hands its :class:`RoutingPlan` to ``keep``)."""
    if held is not None:
        return _held_local(params, x, top_k, dtype, routing, held, keep)
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        x = x.astype(dtype)
        logits, probs, weight, expert = route_topk(
            params["router"], x, top_k, routing
        )
        flat = expert.reshape(n * top_k)
        order = jnp.argsort(flat, stable=True)  # assignment ids by expert
        inverse = jnp.argsort(order)
        counts = jnp.sum(
            jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0
        )
        # Assignment j belongs to token j // k: repeat, then permute.
        rows = _permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
    with jax.named_scope(MOE_EXPERTS_SCOPE):
        rows, sizes = _pad_rows(rows, counts)
        grouped = functools.partial(grouped_matmul, group_sizes=sizes)
        down = _expert_rows(rows, _expert_stacks(params), grouped, dtype)[: n * top_k]
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        back = _permute_rows(down, inverse, order).reshape(n, top_k, d)
        out = jnp.sum(
            back.astype(jnp.float32) * weight[..., None], axis=1
        ).astype(dtype)
        aux, z, load = _routing_statistics(counts, probs, logits, n, top_k)
    one = jnp.ones((), jnp.float32)
    return out, aux, z, load, one, one


# Of the sorted assignments, the held experts' come first.  The layer
# works through them in slabs of this many times the rows an even routing
# would give the held experts, in whole row tiles: one slab in an ordinary
# step, as many as the step's routing needs otherwise (``held_slabs`` says
# how many it was).  Every row of a slab is gathered, multiplied by its
# share and scatter-added whether a held expert's or not, so the margin is
# what an ordinary step pays for rows it does not keep, and a second slab
# costs a whole one.  Timed on the chip at 1, 1.5 and 2 in both cells that
# hold a share (PERF.md section 6, PR 45: ``kimi_linear_train`` 27,558 |
# 27,631 | 27,527 tokens/s, ``nemotron_h_train`` 35,703 | 36,031 | 35,725,
# one seed), where the held share, a mean over four layers, read 0.4 to
# 1.5 times the even one (1.2-4.7% against 3.125%, 2.1-7.9% against 6.25%)
# and a single layer more: at 1 a step walked 1.18 and 1.41 slabs a layer,
# at 1.5 1.00-1.38 and 1.06-1.21, at 2 1.00 and 1.13.
_HELD_SLAB_MARGIN = 1.5


def _slab_rows(assignments: int, count: int, num_experts: int, tile: int) -> int:
    """Rows of a slab: the held experts' even share of the assignments
    times the margin, in whole row tiles (one at least)."""
    even = -(-assignments * count // num_experts)
    rows = min(assignments, math.ceil(_HELD_SLAB_MARGIN * even))
    return max(tile, -(-rows // tile) * tile)


class RoutingPlan(NamedTuple):
    """What a layer's routing decided, and what its backward pass reads of
    it: the router's ``logits`` [n, E] (float32), the top-k's chosen
    scores ``weight`` (as the top-k gives them: not yet renormalised or
    scaled) and ``expert`` [n, k], the assignment ids in their sorted
    ``order`` [n k] (the held experts first) and the assignments each
    expert got, ``counts`` [E].  Everything else of the dispatch (the
    scores, the shares, each row's token, the held experts' offsets) is
    element-wise in these or a few elements long.  Inside a recomputed
    half of a block the half keeps the plan (``topk_moe_ffn``'s ``keep``),
    so that the backward pass does not route again."""

    logits: jax.Array
    weight: jax.Array
    expert: jax.Array
    order: jax.Array
    counts: jax.Array


@jax.custom_vjp
def _at_choice(probs, expert, weight):
    """``weight`` [n, k], the scores ``probs`` [n, E] at the chosen
    ``expert`` [n, k] as the top-k gave them, as a function of ``probs``:
    the backward pass puts each weight's cotangent at its expert (a
    token's experts differ, so by comparison, no scatter) and reads
    ``expert`` alone, where the top-k's own would read the top-k's."""
    del probs, expert
    return weight


def _at_choice_fwd(probs, expert, weight):
    # An empty array carries the experts' number and the scores' type.
    return weight, (expert, jnp.zeros((0, probs.shape[-1]), probs.dtype))


def _at_choice_bwd(residuals, g):
    expert, like = residuals
    here = expert[:, None, :] == jnp.arange(like.shape[-1])[None, :, None]
    dprobs = jnp.sum(jnp.where(here, g[:, None, :], 0), axis=-1).astype(like.dtype)
    return dprobs, None, jnp.zeros_like(g)


_at_choice.defvjp(_at_choice_fwd, _at_choice_bwd)


def _slab(x, stacks, share, token, sizes, dtype):
    """One slab of sorted assignments: ``token`` [R] names each row's
    token, ``share`` [R] its routing weight, ``sizes`` [count + 1] the
    rows of each held expert and, last, the rows that are no held
    expert's (they come out of the grouped product as zeros).  Returns
    the weighted rows ``[R, d]`` in float32."""
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        rows = x[token]
    with jax.named_scope(MOE_EXPERTS_SCOPE):
        grouped = functools.partial(
            grouped_matmul, group_sizes=sizes, leading=True
        )
        down = _expert_rows(rows, stacks, grouped, dtype)
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        return down.astype(jnp.float32) * share[:, None]


def _slab_inputs(i, rows: int, weight, order, offsets):
    """Slab ``i`` of the sorted order: its assignment ids ``[rows]``, the
    share (``weight`` [n, k] at the id) and the token of each, and its
    group sizes from the held experts' ``offsets`` [count + 1] into the
    sorted order."""
    start = i * rows
    lo = jnp.clip(offsets[:-1], start, start + rows)
    hi = jnp.clip(offsets[1:], start, start + rows)
    held = hi - lo
    sizes = jnp.concatenate([held, (rows - jnp.sum(held))[None]])
    ids = lax.dynamic_slice_in_dim(order, start, rows)
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        share = weight.reshape(-1)[ids]
    return ids, share, ids // weight.shape[-1], sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_experts(x, stacks, weight, order, offsets, rows: int, dtype):
    """``out[t] = sum over t's held assignments of weight * expert(x[t])``
    for assignments sorted with the held experts first: ``weight`` [n,
    k] by assignment id, ``order`` [slabs * rows] the ids in sorted order
    (padded with ids past the last assignment: rows of no expert, which
    the gathers clamp and the scatters drop), ``offsets`` [count + 1] where each
    held expert's rows start (the last: where they end).  Slab after slab
    while there are held rows left (a ``while_loop``: its length is the
    step's routing), each slab's rows and shares gathered, multiplied and
    added into the tokens; nothing the size of all assignments is moved.
    The backward pass walks the same slabs, recomputing each."""
    return _held_experts_fwd(x, stacks, weight, order, offsets, rows, dtype)[0]


def _held_experts_fwd(x, stacks, weight, order, offsets, rows, dtype):
    def body(state):
        i, out = state
        _, share, token, sizes = _slab_inputs(i, rows, weight, order, offsets)
        weighted = _slab(x, stacks, share, token, sizes, dtype)
        with jax.named_scope(MOE_DISPATCH_SCOPE):
            return i + 1, out.at[token].add(weighted)

    _, out = lax.while_loop(
        lambda state: state[0] * rows < offsets[-1],
        body,
        (jnp.zeros((), jnp.int32), jnp.zeros(x.shape, jnp.float32)),
    )
    return out.astype(dtype), (x, stacks, weight, order, offsets)


def _held_experts_bwd(rows, dtype, residuals, g):
    x, stacks, weight, order, offsets = residuals
    g = g.astype(jnp.float32)

    def body(state):
        i, dx, dstacks, dflat = state
        ids, share, token, sizes = _slab_inputs(i, rows, weight, order, offsets)
        _, pull = jax.vjp(
            lambda x_, stacks_, share_: _slab(x_, stacks_, share_, token, sizes, dtype),
            x, stacks, share,
        )
        with jax.named_scope(MOE_DISPATCH_SCOPE):
            dx_i, dstacks_i, dshare = pull(g[token])
            dflat = dflat.at[ids].add(dshare)
            dx = dx + dx_i.astype(jnp.float32)
        return i + 1, dx, jax.tree.map(jnp.add, dstacks, dstacks_i), dflat

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    _, dx, dstacks, dflat = lax.while_loop(
        lambda state: state[0] * rows < offsets[-1],
        body,
        (jnp.zeros((), jnp.int32), zeros(x), jax.tree.map(zeros, stacks), zeros(weight.reshape(-1))),
    )
    cast = lambda d, a: d.astype(a.dtype)
    return (
        cast(dx, x), jax.tree.map(cast, dstacks, stacks),
        cast(dflat, weight).reshape(weight.shape), None, None,
    )


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _held_local(params, x, top_k, dtype, routing, held, keep):
    """One chip's share of the layer on tokens ``x`` [n, d]: the router
    and the top-k run over every expert, the grouped products over the
    ``count`` held ones, ``first`` onwards.  The assignments are sorted
    with the held experts first, so that their rows are a prefix of the
    sorted order, and :func:`_held_experts` works through that prefix and
    no further: no assignment to a held expert is left out, whatever the
    imbalance, and an ordinary step touches one slab of rows.  Everything
    after the :class:`RoutingPlan` reads the plan as ``keep`` hands it
    back."""
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    first, count = held
    assignments = n * top_k
    rows = _slab_rows(assignments, count, num_experts, _tiling(dtype)[0])
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        x = x.astype(dtype)
        logits = _router_logits(params["router"], x)
        # The choice itself is not differentiated: the scores' gradient
        # goes through the plan (:func:`_at_choice`).
        weight, expert = lax.top_k(lax.stop_gradient(_scores(logits, routing)), top_k)
        flat = expert.reshape(assignments)
        plan = keep(RoutingPlan(
            logits, weight, expert,
            # Assignment ids by expert, the held experts first.
            order=jnp.argsort((flat - first) % num_experts, stable=True),
            counts=jnp.sum(jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0),
        ))
        probs = _scores(plan.logits, routing)
        weight = _chosen_weight(_at_choice(probs, plan.expert, plan.weight), routing)
        offsets = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(lax.dynamic_slice_in_dim(plan.counts, first, count)),
        ])
        pad = -assignments % rows
        order = jnp.concatenate([plan.order, assignments + jnp.arange(pad, dtype=jnp.int32)])
    out = _held_experts(
        x, _expert_stacks(params), weight, order, offsets, rows, dtype
    )
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        aux, z, load = _routing_statistics(plan.counts, probs, plan.logits, n, top_k)
        held_rows = offsets[-1]
        held_share = held_rows.astype(jnp.float32) / assignments
        held_slabs = jnp.maximum(1, -(-held_rows // rows)).astype(jnp.float32)
    return out, aux, z, load, held_share, held_slabs


@jax.named_scope(MOE_SCOPE)
def topk_moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
    mesh: Optional[Mesh] = None,
    dtype=jnp.bfloat16,
    routing: Routing = Routing(),
    held: Optional[tuple[int, int]] = None,
    keep=lambda plan: plan,
) -> TopKMoEOutput:
    """Top-k routing over experts, exactly: ``y = sum_{e in top_k} p_e *
    W_down_e (silu(W_gate_e h) * W_up_e h)`` with the ``p_e`` the
    softmax's as they are (not renormalised), or what ``routing`` states.
    ``x`` is ``[batch, time, d_model]``; ``params`` holds ``router`` [d,
    E] and the expert stacks ``w_gate``, ``w_up`` [E, d, f] and ``w_down``
    [E, f, d]; without ``w_gate`` an expert is ``W_down_e relu(W_up_e
    h)^2`` (two matrices, no gate).  With ``held = (first, count)``
    the stacks hold ``count`` experts, ``first`` onwards, of the router's
    ``E``, and the sum runs over the chosen experts that are held (module
    docstring); ``held_share`` says how many of the assignments that was
    and ``held_slabs`` in how many slabs of rows they were worked through.
    ``keep`` is handed such a layer's :class:`RoutingPlan` and returns
    it: a caller that recomputes the layer in the backward pass names the
    plan's arrays there for keeping (``models/remat.py::kept_plan``), and
    the recomputed pass then routes nothing again.

    On a mesh every rank routes its own tokens (``x`` sharded
    ``[data, seq, ...]``, the experts replicated) and the statistics
    are means over ranks.  An ``expert`` axis larger than 1 needs an
    exchange of uneven size and is not built.
    """
    d = x.shape[-1]
    num_experts = params["router"].shape[-1]
    if held is not None and not (
        0 <= held[0] and held[1] == params["w_down"].shape[0]
        and held[0] + held[1] <= num_experts
    ):
        raise ValueError(
            f"held {held} against {params['w_down'].shape[0]} expert "
            f"matrices and {num_experts} router outputs"
        )
    local = lambda p, xl: _topk_local(
        p, xl.reshape(-1, d), top_k, dtype, routing, held, keep
    )
    if mesh is None:
        out, *stats = local(params, x)
        return TopKMoEOutput(out.reshape(x.shape), *stats)
    if mesh.shape[AxisNames.EXPERT] > 1:
        raise NotImplementedError(
            "exact top-k routing over an expert axis larger than 1 needs "
            "an all-to-all of uneven size: that is the cell "
            "olmoe_train_ep4's PR (PERF.md section 7); here every expert "
            "lives on every rank"
        )
    token_axes = (AxisNames.DATA, AxisNames.SEQ)

    def per_device(p, xl):
        out, *stats = local(p, xl)
        stats = lax.pmean(jnp.stack(stats), token_axes)
        return out.reshape(xl.shape), stats

    # pallas_call outputs carry no varying-mesh-axes type, which the vma
    # checker rejects (as in parallel/ring.py); a Mosaic kernel also wants
    # every axis manual.  Unchecked, the transpose sums the experts'
    # gradient over every axis and divides the output's by the axes it is
    # replicated over, which is right for ranks that hold other tokens
    # and for ranks that repeat the same ones (tests/test_olmoe_block.py).
    out, stats = jax.shard_map(
        per_device,
        mesh=mesh,
        in_specs=(P(), P(*token_axes)),
        out_specs=(P(*token_axes), P()),
        check_vma=False,
    )(params, x)
    return TopKMoEOutput(out, *stats)
