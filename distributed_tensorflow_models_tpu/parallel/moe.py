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
"""

from __future__ import annotations

import functools
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


def route_topk(
    router: jax.Array, x: jax.Array, top_k: int, routing: Routing = Routing()
):
    """``(logits, probs, weight, expert)`` of tokens ``x`` [n, d]: the
    router's product, its scores (``routing.scoring``) and the choice, in
    float32 at full precision: a bf16 product here moves near-ties across
    the top-k boundary.  ``weight`` and ``expert`` are [n, top_k], largest
    first, ties to the lower expert index; ``weight`` is the chosen
    scores, renormalised and scaled as ``routing`` says."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if routing.scoring == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
    elif routing.scoring == "sigmoid":
        probs = jax.nn.sigmoid(logits)
    else:
        raise ValueError(
            f"unknown router scoring {routing.scoring!r} "
            "(want 'softmax' or 'sigmoid')"
        )
    weight, expert = lax.top_k(probs, top_k)
    if routing.renormalize:
        weight = weight / jnp.sum(weight, axis=-1, keepdims=True)
    if routing.scale != 1.0:
        weight = weight * routing.scale
    return logits, probs, weight, expert


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
    routing: Routing = Routing(), held: Optional[tuple[int, int]] = None,
):
    """The layer on one rank's tokens ``x`` [n, d].  ``held`` None: every
    expert is here; ``(first, count)``: the expert stacks hold those
    ``count`` of the router's experts (:func:`_held_local`)."""
    if held is not None:
        return _held_local(params, x, top_k, dtype, routing, held)
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
    return out, aux, z, load, jnp.ones((), jnp.float32)


# Of the sorted assignments, the held experts' come first.  The layer
# works through them in slabs of this many times the rows an even routing
# would give the held experts: one slab in an ordinary step (a Zipf
# stream's routing is uneven: a layer's held share reads up to twice the
# even one), as many as the step's routing needs otherwise.
_HELD_SLAB_MARGIN = 4


def _slab_rows(assignments: int, count: int, num_experts: int, tile: int) -> int:
    rows = min(assignments, _HELD_SLAB_MARGIN * assignments * count // num_experts)
    return max(tile, -(-rows // tile) * tile)


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


def _slab_inputs(i, rows: int, share, token, offsets):
    """Slab ``i``'s slices of the sorted ``share`` and ``token``, and its
    group sizes from the held experts' ``offsets`` [count + 1] into the
    sorted order."""
    start = i * rows
    lo = jnp.clip(offsets[:-1], start, start + rows)
    hi = jnp.clip(offsets[1:], start, start + rows)
    held = hi - lo
    sizes = jnp.concatenate([held, (rows - jnp.sum(held))[None]])
    cut = lambda a: lax.dynamic_slice_in_dim(a, start, rows)
    return cut(share), cut(token), sizes


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _held_experts(x, stacks, share, token, offsets, rows: int, dtype):
    """``out[t] = sum over t's held assignments of share * expert(x[t])``
    for assignments sorted with the held experts first: ``share`` and
    ``token`` [slabs * rows] in that order, ``offsets`` [count + 1] where
    each held expert's rows start (the last: where they end).  Slab after
    slab while there are held rows left (a ``while_loop``: its length is
    the step's routing), each gathered, multiplied and added into the
    tokens; nothing the size of all assignments exists.  The backward
    pass walks the same slabs, recomputing each."""
    return _held_experts_fwd(x, stacks, share, token, offsets, rows, dtype)[0]


def _held_experts_fwd(x, stacks, share, token, offsets, rows, dtype):
    def body(state):
        i, out = state
        share_i, token_i, sizes = _slab_inputs(i, rows, share, token, offsets)
        weighted = _slab(x, stacks, share_i, token_i, sizes, dtype)
        with jax.named_scope(MOE_DISPATCH_SCOPE):
            return i + 1, out.at[token_i].add(weighted)

    _, out = lax.while_loop(
        lambda state: state[0] * rows < offsets[-1],
        body,
        (jnp.zeros((), jnp.int32), jnp.zeros(x.shape, jnp.float32)),
    )
    return out.astype(dtype), (x, stacks, share, token, offsets)


def _held_experts_bwd(rows, dtype, residuals, g):
    x, stacks, share, token, offsets = residuals
    g = g.astype(jnp.float32)

    def body(state):
        i, dx, dstacks, dshare = state
        share_i, token_i, sizes = _slab_inputs(i, rows, share, token, offsets)
        _, pull = jax.vjp(
            lambda x_, stacks_, share_: _slab(x_, stacks_, share_, token_i, sizes, dtype),
            x, stacks, share_i,
        )
        with jax.named_scope(MOE_DISPATCH_SCOPE):
            dx_i, dstacks_i, dshare_i = pull(g[token_i])
            dshare = lax.dynamic_update_slice_in_dim(dshare, dshare_i, i * rows, 0)
            dx = dx + dx_i.astype(jnp.float32)
        return i + 1, dx, jax.tree.map(jnp.add, dstacks, dstacks_i), dshare

    zeros = lambda a: jnp.zeros(a.shape, jnp.float32)
    _, dx, dstacks, dshare = lax.while_loop(
        lambda state: state[0] * rows < offsets[-1],
        body,
        (jnp.zeros((), jnp.int32), zeros(x), jax.tree.map(zeros, stacks), zeros(share)),
    )
    cast = lambda d, a: d.astype(a.dtype)
    return cast(dx, x), jax.tree.map(cast, dstacks, stacks), dshare, None, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _held_local(params, x, top_k, dtype, routing, held):
    """One chip's share of the layer on tokens ``x`` [n, d]: the router
    and the top-k run over every expert, the grouped products over the
    ``count`` held ones, ``first`` onwards.  The assignments are sorted
    with the held experts first, so that their rows are a prefix of the
    sorted order, and :func:`_held_experts` works through that prefix and
    no further: no assignment to a held expert is left out, whatever the
    imbalance, and an ordinary step touches one slab of rows."""
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    first, count = held
    assignments = n * top_k
    rows = _slab_rows(assignments, count, num_experts, _tiling(dtype)[0])
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        x = x.astype(dtype)
        logits, probs, weight, expert = route_topk(
            params["router"], x, top_k, routing
        )
        flat = expert.reshape(assignments)
        # Assignment ids by expert, the held experts first.
        order = jnp.argsort((flat - first) % num_experts, stable=True)
        counts = jnp.sum(
            jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0
        )
        offsets = jnp.concatenate([
            jnp.zeros((1,), jnp.int32),
            jnp.cumsum(lax.dynamic_slice_in_dim(counts, first, count)),
        ])
        pad = (0, -assignments % rows)
        share = jnp.pad(weight.reshape(assignments)[order], pad)
        token = jnp.pad(order // top_k, pad)
    out = _held_experts(
        x, _expert_stacks(params), share, token, offsets, rows, dtype
    )
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        aux, z, load = _routing_statistics(counts, probs, logits, n, top_k)
        held_share = offsets[-1].astype(jnp.float32) / assignments
    return out, aux, z, load, held_share


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
    docstring); ``held_share`` says how many of the assignments that was.

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
        p, xl.reshape(-1, d), top_k, dtype, routing, held
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
