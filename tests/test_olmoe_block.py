"""The OLMoE block of ``models/transformer_lm.py`` and the exact top-k
expert layer of ``parallel/moe.py`` at a small size on the CPU, float32.

The plain reference's side of it (logits, losses, every gradient,
prefill then decode) is ``tests/benchmark/test_bench_reference_olmoe.py``;
``fit`` through the program config on the data mesh is a case of
``tests/test_lm_fit_smoke.py``.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.harness import train as trainlib
from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.parallel import moe as moelib

E, K, D, F, N = 8, 2, 64, 32, 48
SMALL = {
    **get_config("olmoe").model_kwargs,
    "vocab_size": 97, "num_layers": 2, "num_heads": 4, "d_model": D,
    "d_ff": F, "max_len": 32, "num_experts": E, "moe_top_k": K,
}


def _layer_params(seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    return {
        "router": jax.random.normal(keys[0], (D, E)) * D**-0.5,
        "w_gate": jax.random.normal(keys[1], (E, D, F)) * D**-0.5,
        "w_up": jax.random.normal(keys[2], (E, D, F)) * D**-0.5,
        "w_down": jax.random.normal(keys[3], (E, F, D)) * F**-0.5,
    }


def _routing_case(case, kind="gated_silu"):
    """``(params, x)`` whose routing is uneven in the way ``case`` says.
    The inputs are positive, so a router column of one sign decides an
    expert's fate for every token.  ``kind`` ``"relu2"``: experts of two
    matrices around a squared ReLU (no ``w_gate``)."""
    params = _layer_params()
    if kind == "relu2":
        del params["w_gate"]
    x = jnp.abs(jax.random.normal(jax.random.key(7), (1, N, D))) + 0.1
    router = params["router"]
    if case in ("one_expert_gets_nothing", "nothing_and_everything"):
        router = router.at[:, 2].set(-0.5)
    if case in ("one_expert_gets_everything", "nothing_and_everything"):
        router = router.at[:, 5].set(0.5)
    if case == "zipf":
        router = router + jnp.linspace(2.0, -2.0, E)[None, :] / D
    return {**params, "router": router}, x


@functools.partial(jax.jit, static_argnames="top_k")
def _dense_masked(params, x, top_k):
    """Every expert on every token, masked by the top-k choice: the
    formulation the sorted, grouped layer has to equal.  (Jitted, like
    ``_grouped``: one compile a kind of expert, where op by op is dozens.)"""
    with jax.default_matmul_precision("highest"):
        h = x.reshape(-1, x.shape[-1])
        probs = jax.nn.softmax(h @ params["router"], axis=-1)
        kth = jnp.sort(probs, axis=-1)[:, -top_k][:, None]
        chosen = probs >= kth  # no ties on random inputs
        weight = jnp.where(chosen, probs, 0.0)
        up = jnp.einsum("nd,edf->enf", h, params["w_up"])
        if "w_gate" in params:
            hidden = jax.nn.silu(jnp.einsum("nd,edf->enf", h, params["w_gate"])) * up
        else:
            hidden = jnp.square(jnp.maximum(up, 0.0))
        ys = jnp.einsum("enf,efd->end", hidden, params["w_down"])
        out = jnp.einsum("ne,end->nd", weight, ys)
        counts = jnp.sum(chosen, axis=0)
    return out.reshape(x.shape), counts


@functools.partial(jax.jit, static_argnames="top_k")
def _grouped(params, x, top_k=K):
    with jax.default_matmul_precision("highest"):
        return moelib.topk_moe_ffn(params, x, top_k=top_k, dtype=jnp.float32)


CASES = [
    *((case, "gated_silu") for case in (
        "random", "zipf", "one_expert_gets_nothing", "one_expert_gets_everything",
        "nothing_and_everything",
    )),
    # Two grouped products a pass in place of three, the other activation.
    ("zipf", "relu2"), ("nothing_and_everything", "relu2"),
]
_CASE_IDS = [f"{case}-{kind}" for case, kind in CASES]


@pytest.mark.parametrize("case,kind", CASES, ids=_CASE_IDS)
def test_grouped_layer_equals_the_dense_masked_formulation(case, kind):
    params, x = _routing_case(case, kind)
    want, counts = _dense_masked(params, x, K)
    got = _grouped(params, x)
    if "nothing" in case:
        assert int(counts[2]) == 0
    if "everything" in case:
        assert int(counts[5]) == N
    # Every assignment is computed: k per token, none dropped.
    assert int(counts.sum()) == K * N
    # float32 on the CPU: the two differ by reduction order only.
    np.testing.assert_allclose(np.asarray(got.out), np.asarray(want), atol=2e-5, rtol=1e-5)
    assert float(got.load_max_over_mean) == pytest.approx(float(counts.max()) * E / (K * N))


@pytest.mark.parametrize("case,kind", CASES, ids=_CASE_IDS)
def test_grouped_layer_gradients_equal_the_dense_masked_ones(case, kind):
    params, x = _routing_case(case, kind)
    probe = jax.random.normal(jax.random.key(3), x.shape)
    want = jax.jit(jax.grad(lambda p, y: jnp.sum(_dense_masked(p, y, K)[0] * probe), argnums=(0, 1)))(params, x)
    got = jax.jit(jax.grad(lambda p, y: jnp.sum(_grouped(p, y).out * probe), argnums=(0, 1)))(params, x)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5, rtol=1e-4)


@pytest.mark.parametrize("top_k", [1, 2, 8])
def test_the_result_does_not_depend_on_the_order_of_tokens(top_k):
    params, x = _routing_case("zipf")
    perm = jax.random.permutation(jax.random.key(11), N)
    base = _grouped(params, x, top_k)
    moved = _grouped(params, x[:, perm], top_k)
    np.testing.assert_allclose(
        np.asarray(moved.out), np.asarray(base.out[:, perm]), atol=1e-6, rtol=1e-6
    )
    # The statistics are of the set of tokens, not of their order.
    for name in ("aux_loss", "z_loss", "load_max_over_mean"):
        assert float(getattr(moved, name)) == pytest.approx(float(getattr(base, name)), rel=1e-6)


def test_router_losses_are_the_published_ones():
    params, x = _routing_case("zipf")
    got = _grouped(params, x)
    h = x.reshape(-1, D)
    with jax.default_matmul_precision("highest"):
        logits = h @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    chosen = jax.lax.top_k(probs, K)[1]
    share = np.bincount(np.asarray(chosen).ravel(), minlength=E) / (K * N)
    aux = E * float(np.sum(share * np.asarray(probs.mean(0))))
    z = float(jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2))
    assert float(got.aux_loss) == pytest.approx(aux, rel=1e-5)
    assert float(got.z_loss) == pytest.approx(z, rel=1e-5)


@pytest.mark.parametrize("spec", [{"data": 4}, {"data": 2, "model": 2}], ids=["data4", "data2_model2"])
def test_every_rank_of_a_data_mesh_routes_its_own_tokens(spec):
    """On a data mesh the layer is what each rank computes alone on its
    rows, and the statistics are the ranks' mean; ranks of another axis
    repeat the same tokens and change nothing."""
    mesh = meshlib.create_mesh(meshlib.MeshSpec(**spec), jax.devices()[:4])
    rows = 4 // spec["data"]
    params = _layer_params()
    x = jax.random.normal(jax.random.key(5), (4, 12, D))
    on_mesh = jax.jit(
        lambda p, y: moelib.topk_moe_ffn(p, y, top_k=K, mesh=mesh, dtype=jnp.float32)
    )
    with jax.default_matmul_precision("highest"):
        got = on_mesh(params, x)
    alone = [_grouped(params, x[i : i + rows]) for i in range(0, 4, rows)]
    np.testing.assert_allclose(
        np.asarray(got.out), np.concatenate([np.asarray(a.out) for a in alone]), atol=1e-5
    )
    for name in ("aux_loss", "z_loss", "load_max_over_mean"):
        mean = np.mean([float(getattr(a, name)) for a in alone])
        assert float(getattr(got, name)) == pytest.approx(mean, rel=1e-5)
    # The replicated experts' gradient is the sum over the ranks' tokens.
    probe = jax.random.normal(jax.random.key(6), x.shape)
    with jax.default_matmul_precision("highest"):
        g_mesh = jax.jit(jax.grad(lambda p: jnp.sum(on_mesh(p, x).out * probe)))(params)
        g_one = jax.jit(jax.grad(
            lambda p: sum(
                jnp.sum(_grouped(p, x[i : i + rows]).out * probe[i : i + rows])
                for i in range(0, 4, rows)
            )
        ))(params)
    for g, w in zip(jax.tree.leaves(g_mesh), jax.tree.leaves(g_one)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=5e-5, rtol=1e-4)


# --- the block -----------------------------------------------------------


def _tree(params):
    return {
        "/".join(str(k.key) for k in path): leaf.shape
        for path, leaf in jax.tree_util.tree_leaves_with_path(params)
    }


def _init_shapes(**kwargs):
    model = get_model("transformer_lm", **kwargs)
    tokens = jnp.zeros((2, 16), jnp.int32)
    return _tree(jax.eval_shape(lambda: model.init(jax.random.key(0), tokens))["params"])


def test_olmoe_block_parameter_tree():
    got = _init_shapes(**SMALL)
    block = {
        "ln1/scale": (D,), "ln2/scale": (D,),
        "attn/query/kernel": (D, D), "attn/key/kernel": (D, D),
        "attn/value/kernel": (D, D), "attn/out/kernel": (D, D),
        "attn/q_norm/scale": (D,), "attn/k_norm/scale": (D,),
        "moe/router": (D, E), "moe/w_gate": (E, D, F), "moe/w_up": (E, D, F),
        "moe/w_down": (E, F, D),
    }
    want = {f"blocks_{i}/{k}": v for i in range(2) for k, v in block.items()}
    # No bias anywhere, no position table, an untied head, experts in every layer.
    want.update({"embedding/embedding": (97, D), "ln_f/scale": (D,), "head/kernel": (D, 97)})
    assert got == want


# Parameter trees of the configurations that were there, as the parent
# commit (917e6a7) built them: (leaves, parameters) and a few paths.
PARENT_TREES = {
    "transformer_lm": (70, 8_420_624),
    "transformer_lm_moe": (68, 11_565_840),
    "transformer_lm_modern": (69, 7_894_800),
}


@pytest.mark.parametrize("name", sorted(PARENT_TREES))
def test_the_configurations_that_were_there_build_the_trees_they_built(name):
    cfg = get_config(name)
    got = _init_shapes(**cfg.model_kwargs)
    leaves, parameters = PARENT_TREES[name]
    assert (len(got), sum(int(np.prod(s)) for s in got.values())) == (leaves, parameters)
    assert got["blocks_0/ln1/bias"] == got["blocks_0/ln1/scale"] == (256,)
    assert got["blocks_0/attn/out/bias"] == (256,) and got["head/bias"] == (10000,)
    assert not [k for k in got if "norm" in k or "w_gate" in k]
    assert ("pos_embedding" in got) == (name != "transformer_lm_modern")
    assert ("blocks_1/moe/w_in" in got) == (name == "transformer_lm_moe")


def test_gpt2_block_output_is_the_parent_s():
    """``transformer_lm``'s default block on seeded weights: the sums the
    parent commit's model gave on this input (recorded from a checkout of
    917e6a7; float32 on the CPU, so reduction order only)."""
    cfg = get_config("transformer_lm")
    model = get_model("transformer_lm", **cfg.model_kwargs, dtype=jnp.float32)
    tokens = jax.random.randint(jax.random.key(1), (2, 24), 0, 10000)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    logits, _ = jax.jit(lambda p: model.apply({"params": p}, tokens, train=False))(params)
    flat = np.asarray(logits, np.float64)
    assert flat.shape == (2, 24, 10000)
    assert float(flat.sum()) == pytest.approx(PARENT_OUTPUT["sum"], rel=1e-5, abs=1e-2)
    assert float(np.abs(flat).sum()) == pytest.approx(PARENT_OUTPUT["abs_sum"], rel=1e-5)
    assert float(flat[1, 7, :50].sum()) == pytest.approx(PARENT_OUTPUT["slice_sum"], rel=1e-4, abs=1e-4)


PARENT_OUTPUT = {
    "sum": 1406.0330294138985,
    "abs_sum": 384164.5651939892,
    "slice_sum": -0.09973566234111786,
}


@pytest.mark.parametrize(
    "kwargs",
    [
        {"pipelined": True},
        {"pipelined": True, "norm": "layernorm", "qk_norm": False, "use_bias": False,
         "num_experts": 0, "pos_encoding": "learned"},
        {"norm": "groupnorm"},
        {"moe_router": "hash"},
        {"moe_layers": "odd"},
    ],
    ids=["pipelined", "pipelined_bias_free", "unknown_norm", "unknown_router", "unknown_layers"],
)
def test_settings_the_block_does_not_have_are_refused(kwargs):
    model = get_model("transformer_lm", **{**SMALL, **kwargs})
    with pytest.raises(ValueError):
        model.init(jax.random.key(0), jnp.zeros((4, 16), jnp.int32))


def test_a_pipe_axis_is_refused_when_the_state_is_built():
    """``mesh_pipe > 1`` asks for the stacked layout at init, which is
    the GPT-2 block only: refused before anything is traced."""
    cfg = get_config("olmoe", model_kwargs=SMALL, mesh_pipe=2)
    model = get_model(cfg.model, **trainlib._init_model_kwargs(cfg))
    with pytest.raises(ValueError, match="pipelined block stack"):
        model.init(jax.random.key(0), jnp.zeros((4, 16), jnp.int32))


def test_an_expert_axis_is_refused_at_configuration_time():
    cfg = get_config("olmoe", model_kwargs=SMALL, mesh_expert=2)
    with pytest.raises(ValueError, match="olmoe_train_ep4"):
        trainlib._mesh_model_kwargs(cfg, trainlib.mesh_from_config(cfg))


def test_an_expert_axis_is_refused_by_the_layer_itself():
    mesh = meshlib.create_mesh(meshlib.MeshSpec(data=-1, expert=2))
    x = jnp.zeros((4, 8, D))
    with pytest.raises(NotImplementedError, match="olmoe_train_ep4"):
        moelib.topk_moe_ffn(_layer_params(), x, top_k=K, mesh=mesh)


def test_switch_experts_still_do_not_decode():
    model = get_model("transformer_lm", **get_config("transformer_lm_moe").model_kwargs, decode=True)
    with pytest.raises(ValueError, match="Switch"):
        model.init(jax.random.key(0), jnp.zeros((1, 4), jnp.int32))


def test_prefill_then_decode_is_the_full_forward():
    """QK-norm and RoPE through the cache, experts on one token a
    sequence: the same functions as training."""
    kw = {**SMALL, "dtype": jnp.float32}
    model, decoder = get_model("transformer_lm", **kw), get_model("transformer_lm", **kw, decode=True)
    tokens = jax.random.randint(jax.random.key(2), (3, 20), 0, 97)
    params = jax.jit(model.init)(jax.random.key(0), tokens)["params"]
    # Three programs: the full forward, the prefill, one decode step (run eight times).
    full = jax.jit(lambda p, t: model.apply({"params": p}, t, train=False))
    prefill = jax.jit(lambda p, t: decoder.apply({"params": p}, t, mutable=["cache"]))
    decode = jax.jit(lambda p, cache, t: decoder.apply({"params": p, "cache": cache}, t, mutable=["cache"]))
    with jax.default_matmul_precision("highest"):
        want, _ = full(params, tokens)
        got, state = prefill(params, tokens[:, :12])
        steps = [got[0]]
        for t in range(12, 20):
            got, state = decode(params, state["cache"], tokens[:, t : t + 1])
            steps.append(got[0])
    # float32 on the CPU; the cached path attends with the reference
    # softmax, training with the blockwise one: reduction order only.
    np.testing.assert_allclose(
        np.asarray(jnp.concatenate(steps, axis=1)), np.asarray(want), atol=5e-5, rtol=1e-5
    )
