"""What a recomputed half of a block keeps (``models/remat.py``), at a
small size on the CPU in float32, for a two-layer ``TransformerLM`` of
each kind that reaches a named product: the gated feed-forward behind a
pre-norm and behind a post-norm, the squared-ReLU one in a layer of its
own, the state-space mixer with one group and with two, an expert layer
with a shared expert, the GELU MLP.

For each: (a) the loss and every gradient are those of the bare
``nn.remat`` this replaced, bit for bit, and those of the model that
recomputes nothing to float32 rounding (XLA fuses a recomputed half
otherwise than the first pass: 5e-7 of the largest entry, with the bare
``nn.remat`` too); (b) the differentiated program makes each named
product once where the bare ``nn.remat`` makes it twice, while the
attention projections, the scan and the grouped expert products are
still made twice; (c) ``remat/products_kept`` and ``remat/bytes_kept``
read what the shapes say, and 0 where nothing is recomputed.

Two kinds of thing a half keeps that are no product have sections of their
own below: the routing plan of an expert layer that holds a range of its
router's experts (ISSUE 45), and what a core's forward kernel writes
(ISSUE 47: the chunk-wise delta rule's in a KDA mixer half and the fused
attention's in a plain, a latent and a differential attention half, every
kernel interpreted; the core's forward kernel once in the differentiated
program).
"""

import collections
import functools

import flax.linen as nn
import jax
import jax.numpy as jnp
import pytest
from jax._src import core as jax_core

from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.models import remat as rematlib
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import linear_attention as linattn
from distributed_tensorflow_models_tpu.telemetry import registry as reglib

B, T, D = 2, 32, 64
# Four query heads and two key/value heads of 8: the projections' kernels
# are [64, 32], [64, 16] twice and [32, 64], none the shape of a
# feed-forward's.
BASE = dict(
    vocab_size=97, num_layers=2, num_heads=4, num_kv_heads=2, head_dim=8, d_model=D, d_ff=96, max_len=T,
    norm="rmsnorm", use_bias=False, pos_encoding="none", mlp="gated_silu", dtype=jnp.float32, attn_impl="reference",
)
QUERY, KEY_VALUE, OUT = (D, 32), (D, 16), (32, D)
SSM = dict(layer_mixers=("ssm", "attention"), ssm_num_heads=4, ssm_head_dim=8, ssm_state_dim=16, ssm_chunk=16)

# kind -> (model kwargs; kernel shape -> products of that shape a forward
# pass makes, for the products a half keeps and for some it does not;
# name of a jitted function -> calls a forward pass makes that are
# recomputed).  ``in_proj`` is ``2 d_inner + 2 G N + H`` wide.
KINDS = {
    "gated_silu_pre_norm": (BASE, {(D, 96): 4}, {QUERY: 2, KEY_VALUE: 4}, {}),
    # The post-norm reads each sub-layer's output: ``down`` is kept, the
    # attention's ``out`` is made again.
    "gated_silu_post_norm": (
        {**BASE, "norm_placement": "post"}, {(D, 96): 4, (96, D): 2}, {QUERY: 2, KEY_VALUE: 4, OUT: 2}, {},
    ),
    "relu2_ffn_only": (
        {**BASE, "mlp": "relu2", "layer_mixers": ("attention_only", "ffn_only")},
        {(D, 96): 1}, {QUERY: 1, KEY_VALUE: 2}, {},
    ),
    "ssm_one_group": (
        {**BASE, **SSM}, {(D, 100): 1, (D, 96): 4}, {QUERY: 1, KEY_VALUE: 2}, {"plain_ssd": 1},
    ),
    "ssm_two_groups": (
        {**BASE, **SSM, "ssm_num_groups": 2}, {(D, 132): 1, (D, 96): 4}, {QUERY: 1, KEY_VALUE: 2}, {"plain_ssd": 1},
    ),
    # Two expert layers of four experts of 24, top-2, one shared expert of 40:
    # three grouped products a layer.
    "shared_expert": (
        {**BASE, "d_ff": 24, "moe_router": "topk", "moe_layers": "all", "num_experts": 4, "moe_top_k": 2,
         "moe_shared_experts": 1, "moe_shared_d_ff": 40},
        {(D, 40): 4}, {QUERY: 2, KEY_VALUE: 4}, {"gmm": 6},
    ),
    "gelu_mlp": (
        {**BASE, "mlp": "gelu", "norm": "layernorm", "use_bias": True, "pos_encoding": "learned"},
        {(D, 96): 2}, {QUERY: 2, KEY_VALUE: 4}, {},
    ),
}
kinds = pytest.mark.parametrize("kind", sorted(KINDS))


def _tokens():
    return jnp.arange(B * T).reshape(B, T) % 97


def _loss_of(kind, remat):
    model = get_model("transformer_lm", **KINDS[kind][0], remat=remat)

    def loss(params):
        (logits, _), _ = model.apply(params, _tokens(), mutable=["losses", "moe_stats"])
        return jnp.mean(jnp.square(logits))

    return model, loss


@functools.lru_cache(maxsize=None)
def _params(kind):
    return jax.jit(_loss_of(kind, False)[0].init)(jax.random.key(0), _tokens())


def _value_and_grad(kind, remat):
    return jax.jit(jax.value_and_grad(_loss_of(kind, remat)[1]))(_params(kind))


def _walk(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in jax_core.jaxprs_in_params(eqn.params):
            yield from _walk(inner)


def _made(kind, remat):
    """``(products by kernel shape, jitted calls by name, names)`` of the
    loss's gradient as traced: the products are the ``x @ kernel`` of a
    ``Dense`` (the backward pass's contract other dimensions)."""
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of(kind, remat)[1]))(_params(kind)).jaxpr
    products, calls, names = collections.Counter(), collections.Counter(), 0
    for eqn in _walk(jaxpr):
        if eqn.primitive.name == "dot_general" and eqn.params["dimension_numbers"] == (((2,), (0,)), ((), ())):
            products[tuple(eqn.invars[1].aval.shape)] += 1
        elif eqn.primitive.name in ("jit", "pjit"):
            calls[eqn.params["name"]] += 1
        names += eqn.primitive.name == "name"
    return products, calls, names


def _counted(fn):
    registry = reglib.get_registry()
    read = lambda: tuple(
        registry.counter(name).value for name in (reglib.REMAT_PRODUCTS_KEPT, reglib.REMAT_BYTES_KEPT)
    )
    before = read()
    fn()
    return tuple(after - was for after, was in zip(read(), before))


@kinds
def test_a_recomputed_half_that_keeps_its_products_computes_what_it_computed(kind, monkeypatch):
    loss, grads = _value_and_grad(kind, True)
    plain_loss, plain_grads = _value_and_grad(kind, False)
    monkeypatch.setattr(rematlib, "half", nn.remat)  # what ``Block`` wrapped its halves in before
    bare_loss, bare_grads = _value_and_grad(kind, True)
    assert loss == bare_loss
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(bare_grads)):
        assert jnp.array_equal(got, want), jax.tree_util.keystr(path)
    assert jnp.allclose(loss, plain_loss, rtol=1e-6)
    # Against the tree's largest entry: a key bias's gradient is rounding alone.
    room = 1e-5 * max(float(jnp.max(jnp.abs(want))) for want in jax.tree.leaves(plain_grads))
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(plain_grads)):
        assert float(jnp.max(jnp.abs(got - want))) <= room, jax.tree_util.keystr(path)


@kinds
def test_the_differentiated_program_makes_a_kept_product_once_and_the_others_twice(kind, monkeypatch):
    _, kept, twice, recomputed_calls = KINDS[kind]
    once, plain_calls, plain_names = _made(kind, False)
    assert plain_names == 0  # a model that recomputes nothing traces no name
    assert {shape: once[shape] for shape in (*kept, *twice)} == {**kept, **twice}
    products, calls, names = _made(kind, True)
    assert names == sum(kept.values())
    assert {shape: products[shape] for shape in kept} == kept
    assert {shape: products[shape] for shape in twice} == {shape: 2 * n for shape, n in twice.items()}
    for name, n in recomputed_calls.items():
        assert calls[name] == plain_calls[name] + n, name
    # The bare ``nn.remat`` made every one of them twice.
    monkeypatch.setattr(rematlib, "half", nn.remat)
    bare, _, _ = _made(kind, True)
    assert {shape: bare[shape] for shape in kept} == {shape: 2 * n for shape, n in kept.items()}


@kinds
def test_the_counters_read_what_the_shapes_say(kind):
    _, kept, _, _ = KINDS[kind]
    trace = lambda remat: lambda: jax.eval_shape(jax.grad(_loss_of(kind, remat)[1]), _params(kind))
    assert _counted(trace(False)) == (0, 0)
    want = sum(kept.values()), sum(n * B * T * shape[1] * 4 for shape, n in kept.items())
    assert _counted(trace(True)) == want
    # ``model.init`` runs the halves under ``nn.remat`` too, as the routes count.
    model = _loss_of(kind, True)[0]
    assert _counted(lambda: jax.eval_shape(model.init, jax.random.key(0), _tokens())) == want


def test_kept_is_the_identity_outside_a_recomputed_half():
    x = jnp.ones((4, 8))
    assert _counted(lambda: rematlib.kept(x)) == (0, 0) and rematlib.kept(x) is x
    assert "name" not in str(jax.make_jaxpr(rematlib.kept)(x))


# --- the routing plan of a held expert layer (ISSUE 45) ------------------

# Two expert layers that hold experts 2-5 of their router's eight, top-2 by
# a renormalised sigmoid, one shared expert of 40 beside them.
EXPERTS, TOP_K = 8, 2
KINDS["held_experts"] = (
    {**BASE, "d_ff": 24, "moe_router": "topk", "moe_layers": "all", "num_experts": EXPERTS, "moe_top_k": TOP_K,
     "moe_held": (2, 4), "moe_scoring": "sigmoid", "moe_renormalize": True, "moe_routed_scale": 2.446,
     "moe_shared_experts": 1, "moe_shared_d_ff": 40},
    {(D, 40): 4}, {QUERY: 2, KEY_VALUE: 4}, {},
)
# float32 logits [n, E], the top-k's scores and experts [n, k], the sorted
# order [n k] and the counts [E], four bytes each.
PLAN_BYTES = 4 * (B * T * (EXPERTS + 3 * TOP_K) + EXPERTS)
_ROUTER_PRODUCT = (((1,), (0,)), ((), ()))  # [n, d] x [d, E], the tokens flat


def _routing_made(remat):
    """How often the gradient of ``held_experts``'s loss, as traced, makes
    the router's forward product, a top-k and a sort."""
    jaxpr = jax.make_jaxpr(jax.grad(_loss_of("held_experts", remat)[1]))(_params("held_experts")).jaxpr
    made = collections.Counter()
    for eqn in _walk(jaxpr):
        name = eqn.primitive.name
        if name in ("top_k", "sort"):
            made[name] += 1
        elif name == "dot_general" and eqn.params["dimension_numbers"] == _ROUTER_PRODUCT:
            made["router"] += tuple(eqn.invars[1].aval.shape) == (D, EXPERTS)
    return dict(made)


def test_a_recomputed_held_expert_layer_routes_once(monkeypatch):
    once = {"router": 2, "top_k": 2, "sort": 2}  # one of each a layer
    assert _routing_made(False) == once
    assert _routing_made(True) == once
    monkeypatch.setattr(rematlib, "half", nn.remat)  # the parent's halves: everything runs again
    assert _routing_made(True) == {what: 2 * n for what, n in once.items()}


def test_the_kept_routing_plan_changes_no_value(monkeypatch):
    loss, grads = _value_and_grad("held_experts", True)
    monkeypatch.setattr(rematlib, "half", nn.remat)
    bare_loss, bare_grads = _value_and_grad("held_experts", True)
    assert loss == bare_loss
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(bare_grads)):
        assert jnp.array_equal(got, want), jax.tree_util.keystr(path)
    assert float(jnp.max(jnp.abs(grads["params"]["blocks_1"]["moe"]["router"]))) > 0


def test_a_kept_plan_counts_as_a_plan_and_by_its_bytes_not_as_a_product():
    registry = reglib.get_registry()
    plans = registry.counter(reglib.MOE_PLAN_KEPT)
    trace = lambda remat: lambda: jax.eval_shape(jax.grad(_loss_of("held_experts", remat)[1]), _params("held_experts"))
    before = plans.value
    assert _counted(trace(False)) == (0, 0) and plans.value == before
    # The shared experts' ``gate`` and ``up`` are the products; the plans are two more names and no product.
    products = 4 * B * T * 40 * 4
    assert _counted(trace(True)) == (4, products + 2 * PLAN_BYTES)
    assert plans.value == before + 2
    # Where every expert is held nothing is named but the products.
    before = plans.value
    assert _counted(lambda: jax.eval_shape(jax.grad(_loss_of("shared_expert", True)[1]), _params("shared_expert")))[0] == 4
    assert plans.value == before


# --- what a core's forward kernel writes (ISSUE 47) -----------------------

# One layer of each kind of core whose results a half keeps, at whole lane
# blocks and with every kernel interpreted, as the chip runs them: the KDA
# mixer on its fused route (two heads of 128, 1,024 tokens: two grid steps
# of eight chunks), and the fused attention of a plain layer (four heads of
# 64 over two key/value heads: a head pair a lane block, the keys and
# values repeated outside the kernels), of a latent layer (two heads of 128
# + 64 query/key channels, padded to 256, over 128 value channels) and of a
# differential layer under a window (two pairs of heads of 64 over one
# key/value pair, values 128 wide), 256 tokens each.
KDA_T, KDA_H, KDA_D = 1024, 2, 128
ATTN_T = 256
_ATTN = {**BASE, "num_layers": 1, "max_len": ATTN_T, "attn_impl": "auto", "head_dim": 64}
_KDA_KERNELS, _ATTN_KERNELS = ("_kda_fwd_kernel", "_kda_bwd_kernel"), ("_fused_fwd_kernel", "_fused_bwd_kernel")
# kind -> (model kwargs, tokens, the core's (forward, backward) kernels,
# bytes of the results in float32, the mixer's leaf).
CORES = {
    # The output [1, T, H D], the two block states [1, 2, H, D, D] and the
    # sixteen chunks' T [1, 16, H, 64, 64].
    "kda": (
        {**BASE, "num_layers": 1, "layer_mixers": ("kda",), "kda_num_heads": KDA_H, "kda_head_dim": KDA_D,
         "max_len": KDA_T},
        KDA_T, _KDA_KERNELS, 4 * (KDA_T * KDA_H * KDA_D + 2 * KDA_H * KDA_D * KDA_D + 16 * KDA_H * 64 * 64),
        "linear_attn",
    ),
    # The output [1, T, 4, 64] and a log-sum-exp a head and token.
    "attention": (_ATTN, ATTN_T, _ATTN_KERNELS, 4 * (ATTN_T * 4 * 64 + 4 * ATTN_T), "attn"),
    "latent_attention": (
        {**_ATTN, "layer_mixers": ("mla",), "num_heads": 2, "num_kv_heads": 0, "head_dim": 0,
         "mla_kv_lora_rank": 32, "mla_nope_dim": 128, "mla_rope_dim": 64, "mla_v_dim": 128},
        ATTN_T, _ATTN_KERNELS, 4 * (ATTN_T * 2 * 128 + 2 * ATTN_T), "attn",
    ),
    # The core's heads are the four query heads, each over its pair's 128 value channels.
    "differential_window": (
        {**_ATTN, "attn_differential": True, "attn_window": 128},
        ATTN_T, _ATTN_KERNELS, 4 * (ATTN_T * 4 * 128 + 4 * ATTN_T), "attn",
    ),
}
cores = pytest.mark.parametrize("kind", sorted(CORES))


@pytest.fixture
def cores_on_the_kernel_routes(monkeypatch):
    """What the chip runs, interpreted: the KDA mixer's fused route and the
    fused attention for every ``attention(impl="auto")``."""
    monkeypatch.setattr(linattn, "kda_mixer_route", lambda *a, **k: "fused")
    for name in ("kda_prologue", "kda_epilogue", "chunked_kda_flat"):
        monkeypatch.setattr(linattn, name, functools.partial(getattr(linattn, name), interpret=True))
    monkeypatch.setattr(attnlib, "auto_route", lambda *a, **k: "fused")
    fused = attnlib.fused_attention
    monkeypatch.setattr(
        attnlib, "fused_attention",
        lambda q, k, v, causal, scale, **kw: fused(q, k, v, causal, scale, None, None, True, **kw),
    )


def _core_loss(kind, remat, **kwargs):
    model = get_model("transformer_lm", **{**CORES[kind][0], **kwargs}, remat=remat)
    n = CORES[kind][1]
    tokens = jnp.arange(n).reshape(1, n) % 97
    loss = lambda params: jnp.mean(jnp.square(model.apply(params, tokens)[0]))
    return model, tokens, loss


@functools.lru_cache(maxsize=None)
def _core_params(kind):
    model, tokens, _ = _core_loss(kind, False, attn_impl="reference")
    return jax.jit(model.init)(jax.random.key(0), tokens)


def _core_made(loss, params):
    """``(Pallas kernels by the name of the kernel's function, ``Dense``
    products by kernel shape)`` in the gradient of ``loss`` as traced."""
    kernels, products = collections.Counter(), collections.Counter()
    for eqn in _walk(jax.make_jaxpr(jax.grad(loss))(params).jaxpr):
        if eqn.primitive.name == "pallas_call":
            kernels[eqn.params["jaxpr"].debug_info.func_name] += 1
        elif eqn.primitive.name == "dot_general" and eqn.params["dimension_numbers"] == (((2,), (0,)), ((), ())):
            products[tuple(eqn.invars[1].aval.shape)] += 1
    return kernels, products


@cores
def test_a_recomputed_half_holds_its_cores_forward_kernel_once(kind, cores_on_the_kernel_routes, monkeypatch):
    params, (forward, backward) = _core_params(kind), CORES[kind][2]
    plain, plain_products = _core_made(_core_loss(kind, False)[2], params)
    assert (plain[forward], plain[backward]) == (1, 1)
    made, products = _core_made(_core_loss(kind, True)[2], params)
    assert (made[forward], made[backward]) == (1, 1)
    if kind == "kda":
        # The fused passes around the core are not kept and run again.
        assert plain["_conv_fwd_kernel"] == 3
        assert (made["_conv_fwd_kernel"], made["_decay_fwd_kernel"], made["_gate_fwd_kernel"]) == (6, 2, 2)
    else:
        # So are the projections that make the core's inputs: the half keeps the results alone.
        query = (D, CORES[kind][0]["num_heads"] * (CORES[kind][0]["head_dim"] or 192))
        assert (plain_products[query], products[query]) == (1, 2)
    monkeypatch.setattr(rematlib, "half", nn.remat)  # the parent's halves: the core's forward kernel twice
    bare, _ = _core_made(_core_loss(kind, True)[2], params)
    assert (bare[forward], bare[backward]) == (2, 1)


@cores
def test_a_kept_core_changes_no_value(kind, cores_on_the_kernel_routes, monkeypatch):
    """The loss and every gradient with the core's results kept are those
    with the core run again (the bare ``nn.remat``) bit for bit, and those
    of the model that recomputes nothing to float32 rounding, as for the
    products above (XLA:CPU fuses a recomputed half otherwise: 2e-7 of the
    largest entry here, with the bare ``nn.remat`` too)."""
    params = _core_params(kind)
    value_and_grad = lambda remat: jax.jit(jax.value_and_grad(_core_loss(kind, remat)[2]))(params)
    (loss, grads), (plain_loss, plain_grads) = value_and_grad(True), value_and_grad(False)
    monkeypatch.setattr(rematlib, "half", nn.remat)
    bare_loss, bare_grads = value_and_grad(True)
    assert loss == bare_loss == plain_loss
    room = 1e-5 * max(float(jnp.max(jnp.abs(want))) for want in jax.tree.leaves(plain_grads))
    for (path, got), want, plain in zip(
        jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(bare_grads), jax.tree.leaves(plain_grads)
    ):
        assert jnp.array_equal(got, want), jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(got - plain))) <= room, jax.tree_util.keystr(path)
    for leaf in jax.tree.leaves(grads["params"]["blocks_0"][CORES[kind][4]]):
        assert float(jnp.max(jnp.abs(leaf))) > 0


@cores
def test_a_kept_core_counts_as_a_core_and_by_its_bytes(kind, cores_on_the_kernel_routes):
    counter = reglib.get_registry().counter(reglib.REMAT_CORES_KEPT)
    params, core_bytes = _core_params(kind), CORES[kind][3]
    trace = lambda remat, **kw: lambda: jax.eval_shape(jax.grad(_core_loss(kind, remat, **kw)[2]), params)
    before = counter.value
    assert _counted(trace(False)) == (0, 0) and counter.value == before
    # The feed-forward's ``gate`` and ``up`` are the products; the core is no product.
    products = 2 * CORES[kind][1] * 96 * 4
    assert _counted(trace(True)) == (2, products + core_bytes)
    assert counter.value == before + 1
    model, tokens, _ = _core_loss(kind, True)
    assert _counted(lambda: jax.eval_shape(model.init, jax.random.key(0), tokens)) == (2, products + core_bytes)
    assert counter.value == before + 2
    if kind != "kda":
        # The blockwise route has no rule whose residual could be named: the products alone.
        assert _counted(trace(True, attn_impl="blockwise")) == (2, products)
        assert counter.value == before + 2


def _grad_jaxpr(core, *x):
    return jax.make_jaxpr(jax.grad(lambda *x: jnp.sum(core(*x)), argnums=tuple(range(len(x)))))(*x)


@pytest.mark.parametrize("kind", ["kda", "attention"])
def test_kept_core_names_nothing_outside_a_recomputed_half(kind, cores_on_the_kernel_routes):
    counter = reglib.get_registry().counter(reglib.REMAT_CORES_KEPT)
    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    if kind == "kda":
        flat, beta = spec(1, 128, 2 * 128), spec(1, 128, 2)
        results, x = linattn.kernel_kda_results(flat, flat, beta), (flat, flat, flat, flat, beta)
        core = lambda **kw: lambda *x: linattn.chunked_kda_flat(*x, **kw)
    else:
        heads = spec(1, 128, 2, 64)
        results, x = attnlib.fused_attention_results(heads, heads), (heads, heads, heads)
        core = lambda **kw: lambda *x: attnlib.attention(*x, causal=True, **kw)
    before = counter.value
    assert _counted(lambda: rematlib.kept_core(results)) == (0, 0)
    assert rematlib.kept_core(results) is None and counter.value == before
    # The core as the model calls it and as any other caller does: one program, nothing named.
    kept, bare = _grad_jaxpr(core(keep=rematlib.kept_core), *x), _grad_jaxpr(core(), *x)
    assert not any(eqn.primitive.name == "name" for eqn in _walk(kept.jaxpr))
    assert str(kept) == str(bare)
