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
gated experts with **no capacity**: every one of a token's ``top_k``
assignments is computed.  The assignments are sorted by expert, the rows
gathered, each projection is one grouped matrix product over contiguous
groups of uneven size (the Pallas grouped matmul that ships with jax,
``megablox``: static shapes, the group sizes are data), and the weighted
rows are summed back per token.  No tensor grows with tokens x experts x
capacity.  ``jax.lax.ragged_dot`` was measured against it on a v5e at the
cell's shapes and lost (100 against 135 TFLOP/s over forward and both
backward products, PERF.md section 6, PR 25); XLA's rewrite of it also
drops the instruction's ``op_name``, and with it the scopes below.
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


# --- Exact top-k routing over gated experts (no capacity) ----------------

# ``jax.named_scope`` names of the expert layer, path elements of every
# instruction's ``op_name`` in the compiled step (PERF.md section 3): the
# whole layer; routing, sort, gather and the weighted sum back; the
# grouped products and the gate.
MOE_SCOPE = "moe"
MOE_DISPATCH_SCOPE = "moe_dispatch"
MOE_EXPERTS_SCOPE = "moe_experts"


class TopKMoEOutput(NamedTuple):
    out: jax.Array  # [tokens, d_model]
    aux_loss: jax.Array  # E * sum_e f_e * P_e (unweighted)
    z_loss: jax.Array  # mean(logsumexp(router logits)^2) (unweighted)
    load_max_over_mean: jax.Array  # fullest expert's assignments / mean


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


def grouped_matmul(rows: jax.Array, weights: jax.Array, group_sizes):
    """``rows`` [m, k], sorted by group, times each group's own matrix of
    ``weights`` [groups, k, n]: row ``i`` of group ``g`` gives ``rows[i]
    @ weights[g]``.  ``m`` has to be a multiple of the row tile
    (:func:`_pad_rows`).  Differentiable in ``rows`` and ``weights``
    (megablox's own backward products).  Off the TPU the kernel runs in
    Pallas' interpret mode."""
    return megablox.gmm(
        rows,
        weights,
        group_sizes,
        rows.dtype,
        _tiling(rows.dtype),
        None,
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


def route_topk(router: jax.Array, x: jax.Array, top_k: int):
    """``(logits, probs, weight, expert)`` of tokens ``x`` [n, d]: the
    router's product, its softmax and the choice, in float32 at full
    precision: a bf16 product here moves near-ties across the top-k
    boundary.  ``weight`` and ``expert`` are [n, top_k], largest first,
    ties to the lower expert index."""
    logits = jnp.dot(
        x.astype(jnp.float32),
        router.astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weight, expert = lax.top_k(probs, top_k)
    return logits, probs, weight, expert


def _topk_local(params: dict, x: jax.Array, top_k: int, dtype):
    """The layer on one rank's tokens ``x`` [n, d]; every expert is here."""
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        x = x.astype(dtype)
        logits, probs, weight, expert = route_topk(params["router"], x, top_k)
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
        gate = grouped(rows, params["w_gate"].astype(dtype))
        up = grouped(rows, params["w_up"].astype(dtype))
        hidden = (
            jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)
        ).astype(dtype)
        down = grouped(hidden, params["w_down"].astype(dtype))[: n * top_k]
    with jax.named_scope(MOE_DISPATCH_SCOPE):
        back = _permute_rows(down, inverse, order).reshape(n, top_k, d)
        out = jnp.sum(
            back.astype(jnp.float32) * weight[..., None], axis=1
        ).astype(dtype)
        fraction = counts.astype(jnp.float32) / (n * top_k)
        aux = num_experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
        z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        load = jnp.max(fraction) * num_experts
    return out, aux, z, load


@jax.named_scope(MOE_SCOPE)
def topk_moe_ffn(
    params: dict,
    x: jax.Array,
    *,
    top_k: int,
    mesh: Optional[Mesh] = None,
    dtype=jnp.bfloat16,
) -> TopKMoEOutput:
    """Softmax-then-top-k routing over gated (SiLU) experts, exactly:
    ``y = sum_{e in top_k} p_e * W_down_e (silu(W_gate_e h) * W_up_e h)``
    with the ``p_e`` as they are (not renormalised).  ``x`` is
    ``[batch, time, d_model]``; ``params`` holds ``router`` [d, E] and the expert
    stacks ``w_gate``, ``w_up`` [E, d, f] and ``w_down`` [E, f, d].

    On a mesh every rank routes its own tokens (``x`` sharded
    ``[data, seq, ...]``, the experts replicated) and the three statistics
    are means over ranks.  An ``expert`` axis larger than 1 needs an
    exchange of uneven size and is not built.
    """
    d = x.shape[-1]
    local = lambda p, xl: _topk_local(p, xl.reshape(-1, d), top_k, dtype)
    if mesh is None:
        out, aux, z, load = local(params, x)
        return TopKMoEOutput(out.reshape(x.shape), aux, z, load)
    if mesh.shape[AxisNames.EXPERT] > 1:
        raise NotImplementedError(
            "exact top-k routing over an expert axis larger than 1 needs "
            "an all-to-all of uneven size: that is the cell "
            "olmoe_train_ep4's PR (PERF.md section 7); here every expert "
            "lives on every rank"
        )
    token_axes = (AxisNames.DATA, AxisNames.SEQ)

    def per_device(p, xl):
        out, aux, z, load = local(p, xl)
        stats = lax.pmean(jnp.stack([aux, z, load]), token_axes)
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
    return TopKMoEOutput(out, stats[0], stats[1], stats[2])
