"""``parallel/moe.py``.  Expert parallelism: the all_to_all EP layout must
match the single-device oracle exactly (same routing, capacity, drops),
train, and balance load via the aux loss.  And the exact top-k layer that
holds a share of the experts (Kimi Linear's and Nemotron 3 Nano's cells
run it): a share against the dense masked form, the shares adding up, the
held rows in one slab or many, everything held against the parent's layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.parallel import moe

E_AXIS = 4
NUM_EXPERTS = 8
D_MODEL = 16
D_FF = 32
TOKENS = 64


@pytest.fixture(scope="module")
def expert_mesh():
    return meshlib.create_mesh(meshlib.MeshSpec(data=2, expert=E_AXIS))


@pytest.fixture(scope="module")
def setup():
    params = moe.init_moe_params(
        jax.random.key(0), NUM_EXPERTS, D_MODEL, D_FF
    )
    x = jax.random.normal(jax.random.key(1), (TOKENS, D_MODEL))
    return params, x


def test_ep_matches_single_device_oracle(expert_mesh, setup):
    params, x = setup
    got = jax.jit(
        lambda p, x: moe.moe_ffn(p, x, mesh=expert_mesh)
    )(params, x)
    ref = moe.moe_ffn_reference(params, x, num_ranks=E_AXIS)
    np.testing.assert_allclose(
        np.asarray(got.out), np.asarray(ref.out), atol=1e-5, rtol=1e-5
    )
    np.testing.assert_allclose(
        float(got.aux_loss), float(ref.aux_loss), rtol=1e-5
    )
    np.testing.assert_allclose(
        float(got.dropped_fraction), float(ref.dropped_fraction), atol=1e-6
    )


def test_ep_gradients_match_oracle(expert_mesh, setup):
    params, x = setup

    def loss_ep(p):
        r = moe.moe_ffn(p, x, mesh=expert_mesh)
        return jnp.mean(r.out**2) + 0.01 * r.aux_loss

    def loss_ref(p):
        r = moe.moe_ffn_reference(p, x, num_ranks=E_AXIS)
        return jnp.mean(r.out**2) + 0.01 * r.aux_loss

    g_ep = jax.jit(jax.grad(loss_ep))(params)
    g_ref = jax.jit(jax.grad(loss_ref))(params)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4
        ),
        g_ep,
        g_ref,
    )


def test_capacity_drops_tokens(expert_mesh, setup):
    params, x = setup
    tight = jax.jit(
        lambda p, x: moe.moe_ffn(p, x, mesh=expert_mesh, capacity_factor=0.5)
    )(params, x)
    # With top-1 routing and capacity_factor < 1 some tokens must drop
    # (unless routing is perfectly uniform, which random init never is).
    assert float(tight.dropped_fraction) > 0.0
    loose = jax.jit(
        lambda p, x: moe.moe_ffn(p, x, mesh=expert_mesh, capacity_factor=8.0)
    )(params, x)
    assert float(loose.dropped_fraction) == 0.0


def test_moe_trains_and_aux_balances(expert_mesh):
    params = moe.init_moe_params(jax.random.key(2), NUM_EXPERTS, D_MODEL, D_FF)
    x = jax.random.normal(jax.random.key(3), (TOKENS, D_MODEL))
    target = jnp.roll(x, 1, axis=-1) * 0.5

    def loss(p):
        r = moe.moe_ffn(p, x, mesh=expert_mesh, capacity_factor=2.0)
        return jnp.mean((r.out - target) ** 2) + 0.01 * r.aux_loss

    vg = jax.jit(jax.value_and_grad(loss))
    l0 = float(vg(params)[0])
    for _ in range(30):
        l, g = vg(params)
        params = jax.tree.map(lambda p, d: p - 0.5 * d, params, g)
    assert float(vg(params)[0]) < l0 * 0.8


def test_validation_errors(expert_mesh):
    params = moe.init_moe_params(jax.random.key(0), 6, D_MODEL, D_FF)
    x = jnp.zeros((TOKENS, D_MODEL))
    with pytest.raises(ValueError):  # 6 experts % 4 ranks
        moe.moe_ffn(params, x, mesh=expert_mesh)
    params8 = moe.init_moe_params(jax.random.key(0), 8, D_MODEL, D_FF)
    with pytest.raises(ValueError):  # 62 tokens % 4 ranks
        moe.moe_ffn(params8, jnp.zeros((62, D_MODEL)), mesh=expert_mesh)


# --- an expert layer that holds a share of the experts -------------------
# Every call is one jitted program, value and gradients together; a program
# is compiled once a held range and kind of expert, whatever the router's skew.

E, K, D, F, N = 16, 4, 64, 32, 96
ROUTING = moe.Routing("sigmoid", True, 2.446)


def _layer_params(seed=0, kind="gated_silu"):
    """The expert stacks of either kind: ``"gated_silu"``, three matrices
    an expert (Kimi Linear's, OLMoE's), or ``"relu2"``, two and no gate
    (Nemotron 3 Nano's)."""
    keys = jax.random.split(jax.random.key(seed), 4)
    params = {
        "router": jax.random.normal(keys[0], (D, E)) * D**-0.5,
        "w_gate": jax.random.normal(keys[1], (E, D, F)) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (E, D, F)) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (E, F, D)) * F**-0.5,
    }
    if kind == "relu2":
        del params["w_gate"]
    return params


@pytest.fixture(scope="module")
def layer_params():
    """``{kind: stacks}``, drawn once; a test that moves a leaf copies the dict."""
    return {kind: _layer_params(kind=kind) for kind in ("gated_silu", "relu2")}


def _stacks(params):
    return [k for k in ("w_gate", "w_up", "w_down") if k in params]


def _share(params, first, count):
    take = lambda w: w[first : first + count]
    return {"router": params["router"], **{k: take(params[k]) for k in _stacks(params)}}


def _dense_masked(params, x, held=(0, E)):
    """Every expert of the held range on every token, masked by the top-k
    over all the router's experts: what a share has to equal.  The stacks
    are all the experts' or the held range's alone; the plain form of
    either kind of expert, by whether there is a gate matrix."""
    lo, hi = held[0], held[0] + held[1]
    mine = (lambda w: w[lo:hi]) if params["w_up"].shape[0] == params["router"].shape[-1] else (lambda w: w)
    with jax.default_matmul_precision("highest"):
        h = x.reshape(-1, D)
        s = jax.nn.sigmoid(h @ params["router"])
        kth = jnp.sort(s, axis=-1)[:, -K][:, None]
        chosen = s >= kth  # no ties on random inputs
        w = jnp.where(chosen, s, 0.0)
        w = 2.446 * w / w.sum(-1, keepdims=True)
        up = jnp.einsum("nd,edf->enf", h, mine(params["w_up"]))
        if "w_gate" in params:
            hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", h, mine(params["w_gate"]))) * up
        else:
            hidden = jnp.square(jnp.maximum(up, 0.0))
        ys = jnp.einsum("enf,efd->end", hidden, mine(params["w_down"]))
        return jnp.einsum("ne,end->nd", w[:, lo:hi], ys).reshape(x.shape), chosen[:, lo:hi]


def _held_layer(share, x, held):
    with jax.default_matmul_precision("highest"):
        out = moe.topk_moe_ffn(share, x, top_k=K, dtype=jnp.float32, routing=ROUTING, held=held)
    return out.out, (out.held_share, out.held_slabs)


@functools.partial(jax.jit, static_argnames=("f", "held"))
def _probed(f, p, y, probe, held):
    """``f(p, y, held)``'s two results and the gradients of its probed first
    by ``p`` and ``y`` (for ``_held_layer`` the hand-written backward of the
    held range, for ``_dense_masked`` autodiff), as one program."""
    loss = lambda p, y: (lambda out, aux: (jnp.sum(out * probe), (out, aux)))(*f(p, y, held))
    (_, results), grads = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)(p, y)
    return results, grads


_SKEWS = ["random", "everything_on_one_share", "nothing_on_this_share"]
SHARE_CASES = [
    *((held, skew, "gated_silu") for skew in _SKEWS for held in [(0, 4), (4, 4), (12, 4), (2, 8)]),
    # Experts of two matrices around a squared ReLU: the same dispatch, the
    # slab's backward (a vjp of the slab) with the other activation.
    ((2, 8), "random", "relu2"), ((4, 4), "everything_on_one_share", "relu2"),
]


@pytest.mark.parametrize(
    "held,skew,kind", SHARE_CASES, ids=[f"held{h[0]}_{h[1]}-{s}-{k}" for h, s, k in SHARE_CASES]
)
def test_a_share_computes_its_own_experts_part_and_drops_nothing(layer_params, held, skew, kind):
    """Forward, and the hand-written backward of the held range (a
    ``custom_vjp`` that walks the slabs again) against autodiff of the
    plain dense masked form, for both kinds of expert."""
    params = dict(layer_params[kind])
    x = jnp.abs(jax.random.normal(jax.random.key(7), (2, N // 2, D))) + 0.1
    if skew != "random":
        # Positive inputs: a router column of one sign decides an expert.
        sign = 1.0 if skew == "everything_on_one_share" else -1.0
        cols = jnp.arange(held[0], held[0] + held[1])
        params["router"] = params["router"].at[:, cols].set(
            sign * (0.1 + 0.01 * jnp.arange(held[1]))  # apart, and short of saturation: no ties
        )
    probe, share = jax.random.normal(jax.random.key(3), x.shape), _share(params, *held)
    (want, chosen), want_grads = _probed(_dense_masked, share, x, probe, held)
    (got, (held_share, _)), got_grads = _probed(_held_layer, share, x, probe, held)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-5)
    on_share = float(chosen.sum()) / (K * N)
    assert float(held_share) == pytest.approx(on_share, abs=1e-6)
    if skew == "everything_on_one_share":
        assert on_share == 1.0  # every assignment of every token, all computed
    if skew == "nothing_on_this_share":
        assert on_share == 0.0 and float(jnp.abs(got).max()) == 0.0
    for g, w in zip(jax.tree.leaves(got_grads), jax.tree.leaves(want_grads), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-4, rtol=1e-3)


@pytest.mark.parametrize("kind", ["gated_silu", "relu2"])
def test_the_four_shares_and_the_shared_expert_once_add_up_to_the_whole_layer(kind):
    """The model-configs guide's test of the cut: 16 experts as 4 shares
    of 4.  The routed parts the four shares give, plus the shared expert
    (which every chip computes alike) counted once, are the uncut layer;
    with squared-ReLU experts and a shared expert of another width
    (Nemotron 3 Nano's layer) the uncut layer is also what the plain
    reference gives with every expert held."""
    from distributed_tensorflow_models_tpu.models import transformer_lm as tlm

    x = jax.random.normal(jax.random.key(5), (2, N // 2, D))
    sizes = dict(dtype=jnp.float32, routing=ROUTING, shared_experts=1, aux_loss_weight=0.0)
    if kind == "relu2":
        sizes.update(expert="relu2", shared_d_ff=F + 8)
        alone = tlm.MLP(D, F + 8, dtype=jnp.float32, use_bias=False, activation="relu2")
    else:
        alone = tlm.GatedMLP(D, F, jnp.float32)
    whole = tlm.TopKExpertsFFN(E, K, D, F, **sizes)
    params = jax.jit(whole.init)(jax.random.key(0), x)["params"]
    assert ("w_gate" in params) == (kind == "gated_silu")

    @jax.jit
    def every_part(params, x):
        with jax.default_matmul_precision("highest"):
            uncut, _ = whole.apply({"params": params}, x, mutable=["moe_stats"])
            shared = alone.apply({"params": params["shared"]}, x)
            parts, shares = [], []
            for first in range(0, E, 4):
                layer = tlm.TopKExpertsFFN(E, K, D, F, held=(first, 4), **sizes)
                mine = {**params, **{k: params[k][first : first + 4] for k in _stacks(params)}}
                out, stats = layer.apply({"params": mine}, x, mutable=["moe_stats"])
                parts.append(out - shared)  # this chip's routed part
                shares.append(stats["moe_stats"]["held_share"])
            # The uncut layer against the dense masked formulation too.
            routed, _ = _dense_masked({k: params[k] for k in ("router", *_stacks(params))}, x)
        return uncut, shared, parts, shares, routed

    uncut, shared, parts, shares, routed = every_part(params, x)
    np.testing.assert_allclose(np.asarray(sum(parts) + shared), np.asarray(uncut), atol=3e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(uncut - shared), np.asarray(routed), atol=3e-5, rtol=1e-5)
    assert sum(float(jnp.squeeze(s)) for s in shares) == pytest.approx(1.0, abs=1e-6)
    if kind == "relu2":
        import os
        import sys

        sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
        from benchmark.lib import cells

        ref = cells.load_module("references", "nemotron_h")
        want, _, share = jax.jit(ref.experts, static_argnums=(2, 3, 4))(x.reshape(-1, D), params, K, 2.446, 0)
        np.testing.assert_allclose(np.asarray(uncut), np.asarray(want).reshape(x.shape), atol=3e-5, rtol=1e-5)
        assert float(share) == 1.0


E64, SLAB_HELD = 64, (8, 4)


def _skewed(skew):
    """Random inputs; the held experts' router columns raised by ``skew``."""
    def build(params, keys, n):
        x = jnp.abs(jax.random.normal(keys[4], (1, n, D))) + 0.1
        router = params["router"].at[:, 8:12].add(skew * (1.0 + 0.1 * jnp.arange(4)))
        return router, x
    return build


def _decided(tokens_on_share):
    """The first feature decides: +1 in that many tokens, whose four
    choices are then the four held experts, and -1 in the others, which
    choose none of them; the other features are too small to matter."""
    def build(params, keys, n):
        x = 0.05 * (jnp.abs(jax.random.normal(keys[4], (1, n, D))) + 0.1)
        x = x.at[0, :, 0].set(jnp.where(jnp.arange(n) < tokens_on_share, 1.0, -1.0))
        router = params["router"].at[0, 8:12].set(3.0 + 0.1 * jnp.arange(4))
        return router, x
    return build


# name -> (router and inputs, held rows: None where the routing is random, slabs)
SLAB_CASES = {
    "one_slab": (_skewed(0.01), None, 1),
    "several_slabs": (_skewed(0.03), None, 4),
    "every_assignment": (_skewed(0.2), 2048, 8),
    "no_held_row": (_decided(0), 0, 1),
    "rows_end_at_a_slab_boundary": (_decided(128), 512, 2),
}


@pytest.mark.parametrize("case", sorted(SLAB_CASES))
def test_held_rows_in_one_slab_or_in_many(case):
    """64 experts of which 4 are held, 2,048 assignments: the layer works
    through the held experts' sorted rows in slabs of 256 (one and a half
    times an even routing's 128, in whole row tiles): one slab, several,
    all eight, none at all (one slab is walked all the same), and two
    that the held rows fill to the last.  Each against the dense masked
    formulation, forward and gradient, and ``held_slabs`` says how many
    slabs it was."""
    from distributed_tensorflow_models_tpu.parallel.moe import _slab_rows

    build, rows_held, slabs = SLAB_CASES[case]
    n = 512
    keys = jax.random.split(jax.random.key(11), 5)
    params = {
        "router": jax.random.normal(keys[0], (D, E64)) * D**-0.5,
        "w_gate": jax.random.normal(keys[1], (4, D, F)) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (4, D, F)) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (4, F, D)) * F**-0.5,
    }
    params["router"], x = build(params, keys, n)
    probe = jax.random.normal(jax.random.key(3), x.shape)

    (want, chosen), w = _probed(_dense_masked, params, x, probe, SLAB_HELD)
    on_share = int(chosen.sum())
    prefix = _slab_rows(n * K, SLAB_HELD[1], E64, 256)
    assert prefix == 256
    if rows_held is not None:
        assert on_share == rows_held
    want_slabs = max(1, -(-on_share // prefix))
    assert want_slabs == slabs, on_share
    (got, (held_share, held_slabs)), g = _probed(_held_layer, params, x, probe, SLAB_HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-5)
    assert float(held_share) == pytest.approx(on_share / (n * K))
    assert float(held_slabs) == want_slabs
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(w)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)


def test_held_has_to_match_the_expert_stacks(layer_params):
    params, x = layer_params["gated_silu"], jnp.ones((1, 8, D))
    for held in ((0, 4), (14, 16), (-1, 16)):
        with pytest.raises(ValueError, match="held"):
            moe.topk_moe_ffn(params, x, top_k=K, dtype=jnp.float32, held=held)


def _parent_topk_local(params, x, top_k, dtype):
    """``parallel/moe.py::_topk_local`` of the parent commit (e15f5a8),
    verbatim but for the scopes: what ``olmoe``'s path has to stay."""
    n, d = x.shape
    num_experts = params["router"].shape[-1]
    x = x.astype(dtype)
    logits = jnp.dot(
        x.astype(jnp.float32), params["router"].astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )
    probs = jax.nn.softmax(logits, axis=-1)
    weight, expert = jax.lax.top_k(probs, top_k)
    flat = expert.reshape(n * top_k)
    order = jnp.argsort(flat, stable=True)
    inverse = jnp.argsort(order)
    counts = jnp.sum(jax.nn.one_hot(flat, num_experts, dtype=jnp.int32), axis=0)
    rows = moe._permute_rows(jnp.repeat(x, top_k, axis=0), order, inverse)
    rows, sizes = moe._pad_rows(rows, counts)
    grouped = functools.partial(moe.grouped_matmul, group_sizes=sizes)
    gate = grouped(rows, params["w_gate"].astype(dtype))
    up = grouped(rows, params["w_up"].astype(dtype))
    hidden = (jax.nn.silu(gate.astype(jnp.float32)) * up.astype(jnp.float32)).astype(dtype)
    down = grouped(hidden, params["w_down"].astype(dtype))[: n * top_k]
    back = moe._permute_rows(down, inverse, order).reshape(n, top_k, d)
    out = jnp.sum(back.astype(jnp.float32) * weight[..., None], axis=1).astype(dtype)
    fraction = counts.astype(jnp.float32) / (n * top_k)
    aux = num_experts * jnp.sum(fraction * jnp.mean(probs, axis=0))
    z = jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
    return out, aux, z, jnp.max(fraction) * num_experts


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_with_everything_held_the_layer_is_bit_for_bit_the_parent_s(dtype):
    params = _layer_params(1)
    x = jax.random.normal(jax.random.key(2), (2, 40, D))
    probe = jax.random.normal(jax.random.key(3), x.shape)

    def ours(p, y):
        res = moe.topk_moe_ffn(p, y, top_k=K, dtype=dtype)
        return jnp.sum(res.out.astype(jnp.float32) * probe) + res.aux_loss + res.z_loss, res

    def parents(p, y):
        out, aux, z, load = _parent_topk_local(p, y.reshape(-1, D), K, dtype)
        return jnp.sum(out.reshape(y.shape).astype(jnp.float32) * probe) + aux + z, (out, aux, z, load)

    both = lambda f: jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(params, x)
    (_, res), got = both(ours)
    (_, (out, aux, z, load)), want = both(parents)
    np.testing.assert_array_equal(np.asarray(res.out.reshape(-1, D), np.float32), np.asarray(out, np.float32))
    for a, b in ((res.aux_loss, aux), (res.z_loss, z), (res.load_max_over_mean, load)):
        assert float(a) == float(b)
    assert float(res.held_share) == 1.0 and float(res.held_slabs) == 1.0
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
