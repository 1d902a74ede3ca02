"""Chip-less compiles for a described TPU v5e (2x2).

The TPU compiler is installed with jax and compiles for a topology that
is described, not attached (``on-chip-measurement`` guide, section 2):
it refuses what the chip's compiler would refuse — a misaligned slice,
too much VMEM, a program that does not fit — at no chip time.  These
cases keep the main path's Pallas kernels, at real shapes, and one
data-parallel step over four devices compiling on every later PR.

Nothing runs and nothing here is a measurement.  Only the fast compiles
are kept; the whole ResNet-50 b256 step (~40 s) and the ``conv2d_mxu``
gradient at 56x56x64 (~18 s) stay in the builder's rehearsal.  Four
whole steps are here all the same, under ``slow`` (345 to 440 s each
from an empty cache beside five other workers, ISSUE 41):
``olmo_hybrid_train``'s, ``granite_h_train``'s, ``nemotron_h_train``'s and
``kimi_linear_train``'s, because those cells' batch, the scan's chunk and
what a recomputed half keeps (``models/remat.py``: every one of the four
at 15.0 GiB or under, ISSUEs 42 and 47) were chosen by what the compiler places;
the chip run of every PR holds the same guard, and the tier-1 run keeps
three of the cells' steps at one period of their layers.  A route
or a kernel count does not depend on the length beyond two chunks, so a
case compiles the smallest shape its kernel admits unless its assertion
is about the cell's shape (it then says so).  The persistent cache is
switched off around the cases: an executable compiled for a described
chip is written to it but cannot be read back without one, and the next
run would warn.
"""

import contextlib
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.ops import attention as attnlib
from distributed_tensorflow_models_tpu.ops import linear_attention as linattn
from distributed_tensorflow_models_tpu.ops import optim
from distributed_tensorflow_models_tpu.ops import ssm as ssmlib
from distributed_tensorflow_models_tpu.ops.conv_mxu import conv2d_mxu


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topo
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()


@pytest.fixture
def one_chip(v5e, monkeypatch):
    """The sharding of one described chip, with the process described as
    that chip's: ``auto`` routes and the expert layer ask the backend and
    the device count, and here the CPU compiles for a chip it has not."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    return SingleDeviceSharding(v5e.devices[0])


def _mosaic_kernels(text):
    """The compiled text's lines of Mosaic kernels (interpret mode leaves none)."""
    return [line for line in text.splitlines() if "tpu_custom_call" in line and "pallas_call" in line]


def _core_kernels(text, scope):
    """``(in the forward pass, in the backward pass)``: the compiled
    text's Mosaic kernels under ``scope``, the backward pass's those under
    a ``transpose(``, a recomputed half's second forward pass among them.
    A core whose results a recomputed half keeps (``models/remat.py``) is
    there once and once a layer (its forward kernel, its backward kernel);
    one the half runs again, once and twice."""
    lines = [line for line in _mosaic_kernels(text) if re.search(rf"[/(]{scope}[/)]", line)]
    backward = sum("transpose(" in line for line in lines)
    return len(lines) - backward, backward


def _cores_kept():
    from distributed_tensorflow_models_tpu.telemetry import registry as reglib

    return reglib.get_registry().counter(reglib.REMAT_CORES_KEPT).value


def _as_the_comparison_runs(dtype):
    """The float32 program runs under ``default_matmul_precision("highest")``."""
    return jax.default_matmul_precision("highest") if dtype == jnp.float32 else contextlib.nullcontext()


def _two_fused_attention_kernels(text):
    """The forward and the one backward kernel under ``attention_core``,
    and no ``while`` left of the blockwise scan."""
    kernels = _mosaic_kernels(text)
    assert len(kernels) == 2
    assert all(re.search(r"[/(]attention_core[/)]", line) for line in kernels)
    assert sum("transpose(" in line for line in kernels) == 1
    assert not re.search(r"\bwhile\(", text)


def _flash_fwd_bwd(q, k, v):
    def loss(q, k, v):
        # The ring's chunk step at offsets 0.  Positional: (q_offset,
        # kv_offset, causal, scale, block_q, block_kv, interpret).
        out = attnlib.flash_attention_chunk(
            q, k, v, 0, 0, True, None, None, None, False
        )[0]
        return jnp.sum(out.astype(jnp.float32))

    return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)


def _conv_fwd(x, kernel):
    return conv2d_mxu(x, kernel, (1, 1), "SAME", interpret=False)


def _kda(q, k, v, g, beta):
    # The decay and ``beta`` are float32 in the mixer.
    return linattn.kernel_kda(
        q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), None, 64, False
    )


def _kda_fwd_bwd(*x):
    loss = lambda *x: jnp.sum(_kda(*x).astype(jnp.float32))
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*x)


def _kda_fwd_bwd_f32(*x):
    # As the comparison with the reference runs the float32 program.
    with jax.default_matmul_precision("highest"):
        return _kda_fwd_bwd(*(a.astype(jnp.float32) for a in x))


# ``kimi_linear_train``'s call of the chunk-wise delta rule.
_KDA_SHAPES = [(2, 8192, 32, 128)] * 4 + [(2, 8192, 32)]


@pytest.mark.parametrize(
    "fn,shapes,n_kernels",
    [
        pytest.param(
            _flash_fwd_bwd, [(16, 512, 8, 64)] * 3, 3,
            id="flash_fwd_bwd_b16_t512",
        ),
        pytest.param(
            _flash_fwd_bwd, [(4, 2048, 8, 64)] * 3, 3,
            id="flash_fwd_bwd_b4_t2048",
        ),
        # Two ResNet-50 3x3 classes (stage 1 and stage 2).
        pytest.param(
            _conv_fwd, [(32, 56, 56, 64), (3, 3, 64, 64)], 1,
            id="conv2d_mxu_56x56x64",
        ),
        pytest.param(
            _conv_fwd, [(32, 28, 28, 128), (3, 3, 128, 128)], 1,
            id="conv2d_mxu_28x28x128",
        ),
        pytest.param(_kda, _KDA_SHAPES, 1, id="kda_fwd_b2_t8192"),
        pytest.param(_kda_fwd_bwd, _KDA_SHAPES, 2, id="kda_fwd_bwd_b2_t8192"),
        pytest.param(
            _kda_fwd_bwd_f32, [(1,) + s[1:] for s in _KDA_SHAPES], 2,
            id="kda_fwd_bwd_f32_highest_b1_t8192",
        ),
    ],
)
def test_pallas_kernel_compiles_for_v5e(one_chip, fn, shapes, n_kernels):
    args = [
        jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
        for s in shapes
    ]
    compiled = jax.jit(fn).lower(*args).compile()
    # Mosaic compiled the kernels (interpret mode leaves no custom call).
    assert compiled.as_text().count("tpu_custom_call") >= n_kernels


@pytest.mark.parametrize(
    "dtype, n_kernels", [(jnp.bfloat16, 9), (jnp.float32, 9)], ids=["bf16", "f32"]
)
def test_topk_expert_layer_compiles_for_v5e(one_chip, dtype, n_kernels):
    """The exact top-k expert layer at OLMoE's widths (64 experts of
    2048 x 1024, 8 per token) on 256 tokens (the cell's 4,096 compile the
    same kernels five times slower), forward and backward: the
    grouped products are Mosaic kernels (three forward, six backward),
    in bf16 as ``olmoe_train`` runs them and in float32 as the comparison
    with the reference does, and every one keeps the ``moe_experts``
    scope in its ``op_name`` (what the per-layer readers find it by)."""
    from distributed_tensorflow_models_tpu.parallel import moe as moelib

    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = {
        "router": spec(2048, 64), "w_gate": spec(64, 2048, 1024),
        "w_up": spec(64, 2048, 1024), "w_down": spec(64, 1024, 2048),
    }

    def loss(p, x):
        out = moelib.topk_moe_ffn(p, x, top_k=8, dtype=dtype)
        return jnp.sum(out.out.astype(jnp.float32)) + out.aux_loss + out.z_loss

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, jax.ShapeDtypeStruct((1, 256, 2048), dtype, sharding=one_chip)
    ).compile()
    kernels = _mosaic_kernels(compiled.as_text())
    assert len(kernels) == n_kernels
    # A whole path element, bare or inside a transform's brackets.
    assert all(re.search(r"[/(]moe_experts[/)]", line) for line in kernels)
    assert all(re.search(r"[/(]moe[/)]", line) for line in kernels)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32"])
@pytest.mark.parametrize(
    "T, heads, kv_heads, qk, v, scale",
    [
        (1024, 16, 16, 64, 64, None),
        (4096, 16, 16, 128, 128, None),
        # ``kimi_linear_train``'s MLA: 192 query/key channels a head, padded
        # to 256 lanes outside the kernels, over 128 value channels.
        (8192, 32, 32, 192, 128, 192**-0.5),
        # ``granite_h_train``'s: 32 query heads over 8 key/value heads (repeated
        # over their groups outside the kernels) and Granite's scale.
        (8192, 32, 8, 64, 64, 0.015625),
    ],
    ids=["gpt2m", "olmoe", "latent", "grouped"],
)
def test_auto_attention_compiles_fused_for_v5e(one_chip, T, heads, kv_heads, qk, v, scale, dtype):
    """``attention(impl="auto")`` at the token cells' shapes, forward and
    backward, for the described chip: two Mosaic kernels (the forward and
    the one backward kernel), both under the ``attention_core`` scope the
    per-layer readers find them by, and no ``while`` left of the blockwise
    scan; in bf16 as the cells run it and in float32 as the comparison with
    the reference does.  The gradients come back at the arguments' heads."""
    spec = lambda h, d: jax.ShapeDtypeStruct((1, T, h, d), dtype, sharding=one_chip)
    args = spec(heads, qk), spec(kv_heads, qk), spec(kv_heads, v)
    assert attnlib.auto_route(*args) == "fused"

    def loss(q, k, v):
        out = attnlib.attention(q, k, v, causal=True, scale=scale)
        return jnp.sum(out.astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    _two_fused_attention_kernels(grad.lower(*args).compile().as_text())
    assert [g.shape for g in jax.eval_shape(grad, *args)] == [a.shape for a in args]


_KDA_MIXER_T = 128  # two chunks of the core, one token block of the passes


def _kda_mixer_text(one_chip, dtype=jnp.bfloat16):
    """The compiled text of a ``KDAMixer`` layer at ``kimi_linear_train``'s
    widths (32 heads of 128 over a width of 2304) on two chunks of tokens,
    ``value_and_grad`` under ``jit`` for the described chip, and its
    Mosaic kernels' lines."""
    from distributed_tensorflow_models_tpu.models.mixers import KDAMixer

    mixer = KDAMixer(
        num_heads=32, head_dim=128, d_model=2304, dtype=dtype, name="linear_attn"
    )
    x = jax.ShapeDtypeStruct((1, _KDA_MIXER_T, 2304), dtype, sharding=one_chip)
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip),
        jax.eval_shape(mixer.init, jax.random.key(0), x),
    )

    def loss(params, x):
        return jnp.sum(mixer.apply(params, x).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss)).lower(params, x).compile().as_text()
    kernels = _mosaic_kernels(text)
    return text, kernels


def test_kda_mixer_takes_the_kernel_route_for_v5e(one_chip):
    """The chunk-wise delta rule of a ``KDAMixer`` layer is exactly two
    Mosaic kernels under ``kda_core`` (the forward that keeps the states,
    and the one backward kernel, under ``transpose(``), both under the
    ``linear_attn`` scope too, as the per-layer readers find them, and
    nothing of the plain route's scan is left."""
    text, kernels = _kda_mixer_text(one_chip)
    core = [line for line in kernels if re.search(r"[/(]kda_core[/)]", line)]
    assert len(core) == 2
    assert all(re.search(r"[/(]linear_attn[/)]", line) for line in core)
    assert sum("transpose(" in line for line in core) == 1
    assert not re.search(r"\bwhile\(", text)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32_highest"])
def test_kda_mixer_runs_its_elementwise_work_as_fused_passes_for_v5e(one_chip, dtype):
    """Everything else of the layer between its matrix products (PR 33):
    five Mosaic kernels forward (a pass each for ``q``, ``k``, ``v`` and
    ``g``, one for the gated output norm) and five backward, under
    ``linear_attn`` and **not** under ``kda_core`` (whose reader and
    yardstick keep meaning the core); and no ``[B, T, 32, 128]`` array
    anywhere in the compiled layer, so no reshape, copy or broadcast
    between that view and the flat one the products write.  As the cell
    runs it (bf16) and as the comparison with the reference runs the
    float32 program, under ``default_matmul_precision("highest")`` (which
    PR 31's kernels first met on the chip)."""
    with _as_the_comparison_runs(dtype):
        text, kernels = _kda_mixer_text(one_chip, dtype)
    passes = [line for line in kernels if not re.search(r"[/(]kda_core[/)]", line)]
    assert len(kernels) == 12 and len(passes) == 10
    assert all(re.search(r"[/(]linear_attn[/)]", line) for line in passes)
    assert sum("transpose(" in line for line in passes) == 5
    assert not re.search(rf"\[1,{_KDA_MIXER_T},32,128\]", text)
    for op in ("reshape", "copy", "broadcast"):
        assert not re.search(rf"f32\[(\d+,)+32,128\]\S* {op}\(", text), op


def test_held_expert_layer_compiles_for_v5e(one_chip):
    """One chip's share of Kimi Linear's expert layer (8 of 256 experts
    of 2304 x 1024 held, sigmoid top-8) on 16,384 tokens, forward and
    backward: the grouped products are Mosaic kernels inside the
    ``while`` loop over slabs of held rows (the backward loop holds the
    three products again and their six gradients; this loss needs no
    value of the forward loop, so the compiler drops it), all under
    ``moe_experts``, and nothing the size of all 131,072 assignments by
    the model width exists (the step would not fit the chip otherwise).
    At the cell's shape: both of those are about the 131,072 assignments."""
    from distributed_tensorflow_models_tpu.parallel import moe as moelib

    spec = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    params = {
        "router": spec(2304, 256), "w_gate": spec(8, 2304, 1024),
        "w_up": spec(8, 2304, 1024), "w_down": spec(8, 1024, 2304),
    }

    def loss(p, x):
        out = moelib.topk_moe_ffn(
            p, x, top_k=8, routing=moelib.Routing("sigmoid", True, 2.446), held=(0, 8)
        )
        return jnp.sum(out.out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, jax.ShapeDtypeStruct((2, 8192, 2304), jnp.bfloat16, sharding=one_chip)
    ).compile()
    text = compiled.as_text()
    kernels = _mosaic_kernels(text)
    assert len(kernels) == 9
    assert all(re.search(r"[/(]moe_experts[/)]", line) for line in kernels)
    assert re.search(r"\bwhile\(", text)
    assert "[131072,2304]" not in text
    # Under a gigabyte of scratch for the whole layer's gradient.
    assert compiled.memory_analysis().temp_size_in_bytes < 2**30


@pytest.mark.parametrize(
    "shape", [(8, 1024, 1024, 50257, True), (4, 4096, 2048, 50304, False)],
    ids=["gpt2m", "olmoe"],
)
def test_fused_head_compiles_chunk_by_chunk_for_v5e(one_chip, shape):
    """The fused LM head at the two token cells' shapes, value and
    gradients, for the described chip: three products of 2 n d V in the
    compiled program (no recomputed forward), and temporaries of about
    one chunk's ``[4096, V]`` f32 logits, not every chunk's: the chunks'
    independent logits products are neither merged nor started together
    (``ops/losses.py::_one_chunk_at_a_time``)."""
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    B, T, d, V, with_bias = shape
    spec = lambda dtype, *dims: jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)
    bias = spec(jnp.float32, V) if with_bias else None
    compiled = jax.jit(
        jax.value_and_grad(
            lambda h, k, b, t: losslib.fused_unembed_mean_xent(h, k, b, t),
            argnums=(0, 1, 2) if with_bias else (0, 1),
        )
    ).lower(
        spec(jnp.bfloat16, B, T, d), spec(jnp.float32, d, V), bias,
        spec(jnp.int32, B, T),
    ).compile()
    flops = compiled.cost_analysis()["flops"]
    assert 2.95 <= flops / (2.0 * B * T * d * V) <= 3.10
    one_block = losslib.UNEMBED_CHUNK_ROWS * V * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5 * one_block


def test_data_parallel_step_compiles_over_four_chips(v5e):
    """A small conv model's donated train step over a 4-device mesh of
    the described chips: the batch is split, the parameters replicated,
    and the gradient all-reduce is in the compiled program."""
    mesh = meshlib.data_parallel_mesh(v5e.devices)
    assert mesh.devices.size == 4
    model = get_model("lenet")
    state = jax.eval_shape(  # nothing runs: the state's shapes are enough
        lambda: TrainState.create(
            model, optim.sgd(0.1), jax.random.key(0),
            jnp.zeros((2, 28, 28, 1), jnp.float32), jit_init=False,
        )
    )
    step = train_loop.make_train_step(
        train_loop.classification_loss_fn(model.apply), donate=True
    )
    replicated = NamedSharding(mesh, P())
    by_batch = NamedSharding(mesh, P(meshlib.AxisNames.DATA))

    def spec(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    abstract_state = jax.tree.map(lambda x: spec(x, replicated), state)
    batch = {
        "image": jax.ShapeDtypeStruct(
            (64, 28, 28, 1), jnp.float32, sharding=by_batch
        ),
        "label": jax.ShapeDtypeStruct((64,), jnp.int32, sharding=by_batch),
    }
    compiled = step.lower(
        abstract_state, batch, spec(jax.eval_shape(lambda: jax.random.key(0)), replicated)
    ).compile()
    assert "all-reduce" in compiled.as_text()
    # Donation reached the compiler: the state's bytes alias the output.
    assert compiled.memory_analysis().alias_size_in_bytes > 0


@pytest.mark.parametrize(
    "dtype, T", [(jnp.bfloat16, 8192), (jnp.float32, 128)], ids=["bf16", "f32_highest"]
)
def test_chunked_gdn_compiles_for_v5e(one_chip, dtype, T):
    """``chunked_gdn`` forward and backward at ``olmo_hybrid_train``'s
    widths (15 heads of 96 key and 192 value channels, ``g`` and ``beta``
    one number a head): as the cell runs it, bf16 on one sequence of 8,192
    tokens (the bound on the temporaries below is about that length), and
    as the comparison with the reference runs the float32 program (under
    ``default_matmul_precision("highest")``, which PR 31's kernels first
    met on the chip) on two chunks, where what is asked is that it lowers:
    whatever route it takes, with the ``gdn_core`` scope on its
    instructions."""
    shapes = [(1, T, 15, 96)] * 2 + [(1, T, 15, 192)] + [(1, T, 15)] * 2
    args = [
        jax.ShapeDtypeStruct(s, dtype if len(s) == 4 else jnp.float32, sharding=one_chip)
        for s in shapes
    ]

    def fwd_bwd(*x):
        loss = lambda *x: jnp.sum(linattn.chunked_gdn(*x).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(*x)

    with _as_the_comparison_runs(dtype):
        compiled = jax.jit(fwd_bwd).lower(*args).compile()
    text = compiled.as_text()
    assert re.search(r"[/(]gdn_core[/)]", text) and "kda_core" not in text
    assert "tpu_custom_call" not in text  # the plain route alone, today
    # Nothing the size of a state per token (9 GB at 8,192 tokens): a state
    # per chunk (0.14 GB in float32) a few times over, 1.8 GiB in all in float32.
    assert compiled.memory_analysis().temp_size_in_bytes < 2.5 * 2**30


def _cell_step_compiled(one_chip, cell_name, period=None):
    """``(compiled step, GiB it holds, abstract state, ssd/route_kernel
    counted while tracing)`` of a cell's configuration (through
    ``benchmark/lib/cells.py``: Adam with the clip, the fused head, the
    cell's recomputation and batch) for one described v5e.  With
    ``period`` the layers are those alone (one of each kind the cell has)
    and the vocabulary 2,048: every route and scope of the whole step at a
    fraction of its compile."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    from benchmark.lib import cells

    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config
    from distributed_tensorflow_models_tpu.telemetry import registry as reglib

    cell = cells.load_cell(cell_name)
    per_chip = cell.traffic["fit"]["per_chip_batch"]
    overrides = dict(cell.config["overrides"])
    if period:
        overrides["vocab_size"] = 2048
        overrides["model_kwargs"] = {
            **overrides["model_kwargs"], "layer_mixers": period, "num_layers": len(period), "vocab_size": 2048,
        }
    cfg = get_config(cell.config["program_config"], **overrides, global_batch_size=per_chip)
    assert (cfg.num_steps, cfg.fused_unembed) == (8192, True) and per_chip in (1, 2)
    model = get_model(cfg.model, **cfg.model_kwargs)
    kernel_route = reglib.get_registry().counter(reglib.SSD_ROUTE_KERNEL)
    before = kernel_route.value
    state = jax.eval_shape(
        lambda: TrainState.create(
            model, cfg.optimizer.make(), jax.random.key(0),
            jnp.zeros((2, 128), jnp.int32), jit_init=False,
        )
    )
    spec = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)
    step = train_loop.make_train_step(trainlib.build_loss(cfg, state), donate=True)
    tokens = jax.ShapeDtypeStruct((per_chip, cfg.num_steps), jnp.int32, sharding=one_chip)
    compiled = step.lower(
        jax.tree.map(spec, state), {"inputs": tokens, "targets": tokens},
        spec(jax.eval_shape(lambda: jax.random.key(0))),
    ).compile()
    m = compiled.memory_analysis()
    held = (
        m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes
        - m.alias_size_in_bytes + m.generated_code_size_in_bytes
    )
    return compiled, held / 2**30, state, kernel_route.value - before


# One period of each cell's layers: what the step has to show there.
_ONE_PERIOD = {
    "olmo_hybrid_train": dict(
        period=("gdn", "attention"), ssd_kernel_calls=0,
        # The attention layer: forward and backward (the half keeps what the forward writes).
        custom_calls=2,
        scopes=("linear_attn", "gdn_core", "attention_core", "unembed_loss", "optimizer"),
    ),
    "granite_h_train": dict(
        # One state-space layer, ``model.init`` and the step.
        period=("ssm", "attention"), ssd_kernel_calls=2,
        # The scan forward, recomputed and backward; the attention layer forward and backward.
        custom_calls=5,
        scopes=("ssm", "ssd_core", "attention_core", "unembed_loss", "optimizer"),
    ),
    "nemotron_h_train": dict(
        period=("ssm_only", "ffn_only", "attention_only"), ssd_kernel_calls=2,
        # The same two, and the experts' grouped products.
        custom_calls=6,
        scopes=("ssm", "ssd_core", "moe", "moe_dispatch", "moe_experts", "moe_shared", "attention_core",
                "unembed_loss", "optimizer"),
    ),
}


def _scopes_are_on(text, cell_name, scopes=None):
    """Every scope the cell's per-layer readers look for, as a whole path
    element of some instruction's ``op_name``; nothing the compiler
    rematerialized of its own."""
    assert not re.search(r"\.remat\d*", text)
    for scope in scopes or _ONE_PERIOD[cell_name]["scopes"]:
        assert re.search(rf"[/(]{scope}[/)]", text), scope


@pytest.mark.parametrize("cell_name", sorted(_ONE_PERIOD))
def test_a_cell_s_step_at_one_period_of_its_layers_takes_its_routes_for_v5e(one_chip, cell_name):
    """The quick sibling of the three whole steps below (which are
    ``slow``): the cell's configuration with one layer of each kind and a
    vocabulary of 2,048, the same sequence of 8,192, compiled for one
    described v5e.  The kernel routes are taken (a recomputed half of these
    cells keeps its wide input products and what its attention core's
    forward kernel writes, so that core is there once forward and once
    backward, and every other kernel forward, recomputed and backward),
    the compiler rematerializes nothing of its own and every scope the
    per-layer readers look for is on the step; what it holds is the whole
    step's and the chip run's to say."""
    want = _ONE_PERIOD[cell_name]
    kept = _cores_kept()
    compiled, _, state, kernel_route = _cell_step_compiled(one_chip, cell_name, want["period"])
    assert _cores_kept() - kept == 2  # the attention layer's, ``model.init`` and the step
    assert sorted(k for k in state.params if k.startswith("blocks_")) == [
        f"blocks_{i}" for i in range(len(want["period"]))
    ]
    assert kernel_route == want["ssd_kernel_calls"]
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= want["custom_calls"]
    assert _core_kernels(text, "attention_core") == (1, 1)
    if want["ssd_kernel_calls"]:
        assert _core_kernels(text, "ssd_core") == (1, 2)
    _scopes_are_on(text, cell_name)


@pytest.mark.slow
def test_olmo_hybrid_step_compiles_for_v5e_under_its_memory(one_chip):
    """The whole ``olmo_hybrid_train`` step (the cell's configuration
    through ``benchmark/lib/cells.py``, Adam with the clip, the fused
    head, each half recomputed but for the three products of its
    feed-forward, which the post-norm makes it keep, one sequence of
    8,192) for one described v5e: it fits the chip's 15.75 GiB with room
    (13.54 GiB: 8.56 of state, 4.80 of temporaries, my chip-less compile,
    PR 47; 13.54 before a half kept what its attention core's forward
    kernel writes, 31 MB, too: PERF.md, PR 42; 13.15 when a half kept its
    input alone), the compiler rematerializes nothing of its own, the
    attention layer runs the fused kernels, forward once and backward once,
    and the three scopes are on the step."""
    kept = _cores_kept()
    compiled, held, _, _ = _cell_step_compiled(one_chip, "olmo_hybrid_train")
    assert _cores_kept() - kept == 2  # ``model.init`` and the step
    assert 12.5 < held < 14.5, held
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2 and _core_kernels(text, "attention_core") == (1, 1)
    _scopes_are_on(text, "olmo_hybrid_train")


# ``granite_h_train``'s call of the state-space scan: one sequence of 8,192
# tokens, 64 heads of 64 over a state of 128, ``B`` and ``C`` one vector
# for all the heads, ``dt`` one number a head.
_SSD_SHAPES = [(1, 8192, 64, 64), (1, 8192, 64), (64,), (1, 8192, 128), (1, 8192, 128), (64,)]


@pytest.mark.parametrize("groups", [0, 8], ids=["one_group", "eight_groups"])
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32_highest"])
def test_chunked_ssd_compiles_for_v5e(one_chip, dtype, groups):
    """``chunked_ssd`` forward and backward at the cells' shape and chunk
    (``granite_h_train``'s one ``B`` and ``C`` for all 64 heads, the call
    without the group axis; ``nemotron_h_train``'s eight groups of eight
    heads), as the cells run it (bf16) and as the comparison with the
    reference runs the float32 program (under
    ``default_matmul_precision("highest")``): all take the Pallas kernels,
    one forward and one backward Mosaic body under the ``ssd_core`` scope,
    with no ``while`` left of the plain route's scan over the chunks, and
    hold a state per chunk and never one per token."""
    wide = (0, 3, 4)  # x, B, C in the model's dtype; dt, A_log, D in float32
    shapes = [(1, 8192, groups, 128) if groups and i in (3, 4) else s for i, s in enumerate(_SSD_SHAPES)]
    args = [
        jax.ShapeDtypeStruct(s, dtype if i in wide else jnp.float32, sharding=one_chip)
        for i, s in enumerate(shapes)
    ]
    assert ssmlib.ssd_route(*args[:5], chunk=256) == "kernel"

    def fwd_bwd(*x):
        loss = lambda *x: jnp.sum(ssmlib.chunked_ssd(*x, chunk=256).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))(*x)

    with _as_the_comparison_runs(dtype):
        compiled = jax.jit(fwd_bwd).lower(*args).compile()
    text = compiled.as_text()
    kernels = _mosaic_kernels(text)
    assert len(kernels) == 2 and "gdn_core" not in text
    assert all(re.search(r"[/(]ssd_core[/)]", line) for line in kernels)
    assert sum("transpose(" in line for line in kernels) == 1
    assert not re.search(r"\bwhile\(", text)
    # A state per token would be 17 GB (8192 x 64 x 128 x 64 float32); a
    # state per chunk of 256 is 67 MB, and no mask or score of any head
    # reaches HBM: 0.16 GiB of temporaries in bf16 (0.63 on the plain route).
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5 * 2**30


@pytest.mark.slow
def test_granite_h_step_compiles_for_v5e_under_its_memory(one_chip):
    """The whole ``granite_h_train`` step (the cell's configuration through
    ``benchmark/lib/cells.py``, Adam with the clip, the fused head fed from
    the tied embedding, each half recomputed but for the feed-forwards'
    ``gate`` and ``up`` and the state-space mixers' ``in_proj``, which it
    keeps, one sequence of 8,192, the scan's chunk of 256) for one
    described v5e: it fits the chip's 15.75 GiB with room (14.21 GiB: 8.63
    of state, 5.53 of temporaries, my chip-less compile, PR 47;
    14.17 before a half kept what its attention core's forward kernel
    writes, 34 MB: PERF.md, PR 42; 11.24 when a half kept
    its input alone, 12.13 when the cell was added; at a chunk of 64 it
    did not fit without 254 rematerialized clones: PR 38), the compiler
    rematerializes nothing of its own, the attention layer runs the fused
    kernels over its grouped heads, forward once and backward once, and
    the scopes are on the step."""
    kept = _cores_kept()
    compiled, held, state, kernel_route = _cell_step_compiled(one_chip, "granite_h_train")
    assert _cores_kept() - kept == 2  # ``model.init`` and the step
    assert "head" not in state.params  # tied
    # Nine state-space layers, ``model.init`` and the step: the generalised
    # scan (groups of heads, PR 40) still takes its kernels at one group.
    assert kernel_route == 18
    assert 13.2 < held < 15.0, held
    text = compiled.as_text()
    # Nine scans forward, recomputed and backward; the attention layer forward and backward.
    assert _core_kernels(text, "ssd_core") == (9, 18) and _core_kernels(text, "attention_core") == (1, 1)
    assert text.count("tpu_custom_call") == 29
    _scopes_are_on(text, "granite_h_train")


@pytest.mark.slow
def test_nemotron_h_step_compiles_for_v5e_under_its_memory(one_chip):
    """The whole ``nemotron_h_train`` step (nine one-sub-layer layers
    ``MEMEM*EME`` at the published widths, 8 of 128 experts held, an eighth
    of the vocabulary, Adam with the clip, the fused head, every layer
    recomputed but for the mixers' ``in_proj`` and the shared experts'
    ``up``, one sequence of 8,192) for one described v5e: it fits the
    chip's 15.75 GiB with room (10.91 GiB: 7.45 of state, 3.30 of
    temporaries, my chip-less compile, PR 47; 11.21 before a half kept
    what its attention core's forward kernel writes, 67 MB: PERF.md, PR 42;
    10.97 when the cell was added), the compiler
    rematerializes nothing of its own, the four state-space layers take
    the grouped scan's kernels (``model.init`` and the step: 8), the
    attention layer the fused kernels over sixteen-fold groups, forward
    once and backward once, and the scopes of every piece are on the step."""
    kept = _cores_kept()
    compiled, held, state, kernel_route = _cell_step_compiled(one_chip, "nemotron_h_train")
    assert _cores_kept() - kept == 2  # ``model.init`` and the step
    assert sorted(state.params["blocks_0"]) == ["ln1", "ssm"] and sorted(state.params["blocks_1"]) == ["ln2", "moe"]
    assert "w_gate" not in state.params["blocks_1"]["moe"] and "head" in state.params
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 666_962_944
    assert kernel_route == 8
    assert 9.9 < held < 11.9, held
    text = compiled.as_text()
    # Four scans forward, recomputed and backward, the attention layer
    # forward and backward; the experts' grouped products.
    assert _core_kernels(text, "ssd_core") == (4, 8) and _core_kernels(text, "attention_core") == (1, 1)
    assert text.count("tpu_custom_call") >= 14
    _scopes_are_on(text, "nemotron_h_train")


@pytest.mark.slow
def test_kimi_linear_step_compiles_for_v5e_under_its_memory(one_chip):
    """The whole ``kimi_linear_train`` step (published layers 1-5 at the
    published widths, 8 of 256 experts held, an eighth of the vocabulary,
    Adam with the clip, the fused head, each half recomputed but for the
    dense layer's and the four shared experts' ``gate`` and ``up``, the
    expert layers' routing plans and what the forward kernels of the four
    delta-rule cores and of the latent attention's core write, two
    sequences of 8,192) for one described v5e: it fits the chip's 15.75 GiB
    with room (13.17 GiB: 6.73 of state, 6.20 of temporaries, my chip-less
    compile, PR 47; 12.52 before a half kept its core's results,
    335 MB a KDA layer and 136 MB the latent one: PERF.md, PR 42; 12.35
    when a half kept its input alone), the compiler rematerializes nothing
    of its own, the four KDA layers take the delta rule's kernels and the
    fused passes (``model.init`` and the step: 8 each), all five mixer
    halves keep their cores' results (10), the step holds each core's
    forward kernel once, and the scopes of every piece are on the step."""
    from distributed_tensorflow_models_tpu.telemetry import registry as reglib

    counters = [
        reglib.get_registry().counter(name)
        for name in (reglib.KDA_ROUTE_KERNEL, reglib.KDA_MIXER_FUSED, reglib.REMAT_CORES_KEPT)
    ]
    before = [c.value for c in counters]
    compiled, held, state, _ = _cell_step_compiled(one_chip, "kimi_linear_train")
    assert [c.value - was for c, was in zip(counters, before)] == [8, 8, 10]
    assert sorted(state.params["blocks_0"]) == ["linear_attn", "ln1", "ln2", "mlp"]
    assert 12.2 < held < 14.2, held
    text = compiled.as_text()
    # Four KDA layers of two core kernels (the forward, whose results the
    # half keeps, and the backward) and fifteen fused passes (each forward,
    # recomputed and backward), the latent attention's two, and four
    # expert layers' three grouped products (those three times, and once
    # more for the weights' gradients).
    assert _core_kernels(text, "kda_core") == (4, 4) and _core_kernels(text, "attention_core") == (1, 1)
    assert text.count("tpu_custom_call") >= 4 * (2 + 15) + 2 + 4 * 3 * 4
    _scopes_are_on(text, "kimi_linear_train", (
        "linear_attn", "kda_core", "kda_pass", "attention_core", "moe", "moe_dispatch", "moe_experts", "moe_shared",
        "unembed_loss", "optimizer",
    ))


# ``phi4_flash_train``'s call of Mamba-1's selective scan: one sequence of
# 8,192 tokens, 5,120 channels over a state of 16, a decay for every
# channel and state.
_SSCAN_SHAPES = [(1, 8192, 5120), (1, 8192, 5120), (5120, 16), (1, 8192, 16), (1, 8192, 16), (5120,)]


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bf16", "f32_highest"])
def test_selective_scan_compiles_for_v5e(one_chip, dtype):
    """``selective_scan`` forward and backward at the cell's shape, as the
    cell runs it (bf16) and as the comparison with the reference runs the
    float32 program: both take the Pallas kernels, one forward and one
    backward Mosaic body under the ``sscan_core`` scope, no ``while`` left
    of the plain route's scan over the chunks, and nothing the size of a
    state per token (``8192 x 5120 x 16`` float32: 2.68 GB) in the
    program: the states at the chunks' starts (21 MB) and the operands'
    float32 tiles."""
    from distributed_tensorflow_models_tpu.ops import selective_scan as sscanlib

    wide = (0, 3, 4)  # x, B, C in the model's dtype; dt, A_log, D in float32
    args = [
        jax.ShapeDtypeStruct(s, dtype if i in wide else jnp.float32, sharding=one_chip)
        for i, s in enumerate(_SSCAN_SHAPES)
    ]
    assert sscanlib.selective_scan_route(args[0], args[2]) == "kernel"

    def fwd_bwd(*x):
        loss = lambda *x: jnp.sum(sscanlib.selective_scan(*x).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4, 5))(*x)

    with _as_the_comparison_runs(dtype):
        compiled = jax.jit(fwd_bwd).lower(*args).compile()
    text = compiled.as_text()
    kernels = _mosaic_kernels(text)
    assert len(kernels) == 2 and "ssd_core" not in text
    assert all(re.search(r"[/(]sscan_core[/)]", line) for line in kernels)
    assert sum("transpose(" in line for line in kernels) == 1
    assert not re.search(r"\bwhile\(", text)
    assert not re.search(r"f32\[(\d+,)*8192,(5120,16|16,5120|16,8,640)\]", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 1.0 * 2**30


def test_window_differential_attention_compiles_for_v5e(one_chip):
    """The window layer's core as ``SelfAttention._differential`` calls it
    (40 query heads of 64 over 128 value channels, 8,192 positions, a
    window of 512) takes the fused route: the forward and the one backward
    kernel under ``swa_core`` inside ``attention_core``, and no ``while``
    left of the blockwise scan."""
    q, v = ((1, 8192, 40, d) for d in (64, 128))
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip) for s in (q, q, v)]
    assert attnlib.auto_route(*args, window=512) == "fused"

    def fwd_bwd(*x):
        loss = lambda *x: jnp.sum(attnlib.attention(*x, causal=True, window=512).astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))(*x)

    text = jax.jit(fwd_bwd).lower(*args).compile().as_text()
    _two_fused_attention_kernels(text)
    assert all(re.search(r"[/(]swa_core[/)]", line) for line in _mosaic_kernels(text))


@pytest.mark.slow
def test_phi4_flash_step_compiles_for_v5e_under_its_memory(one_chip):
    """The whole ``phi4_flash_train`` step (published layers 0, 1, 16, 17,
    18, 19 at the published widths, an eighth of the vocabulary, Adam with
    the clip, the fused head from the tied embedding, each half recomputed
    but for its wide input products and what the two source layers hand
    on, one sequence of 8,192) for one described v5e: it fits the chip's
    15.75 GiB with room (12.56 GiB: 7.79 of state, 4.66 of temporaries, my
    chip-less compile, PR 47; 12.37 before a half kept what its
    attention core's forward kernel writes, 84 MB a core: PERF.md, PR 44),
    the compiler rematerializes nothing of its own, both Mamba-1 layers
    take the scan's kernels and all three attention layers the fused
    kernels (``model.init`` and the step), each forward once and backward
    once, the layers that read handed-on keys and values among them, no
    buffer holds a state per token, and the scopes of every piece are on
    the step."""
    from distributed_tensorflow_models_tpu.telemetry import registry as reglib

    counters = [
        reglib.get_registry().counter(name)
        for name in (reglib.SSCAN_ROUTE_KERNEL, reglib.ATTN_ROUTE_FUSED, reglib.REMAT_CORES_KEPT)
    ]
    before = [c.value for c in counters]
    compiled, held, state, _ = _cell_step_compiled(one_chip, "phi4_flash_train")
    assert [c.value - was for c, was in zip(counters, before)] == [4, 6, 6]
    assert sum(x.size for x in jax.tree.leaves(state.params)) == 697_094_272
    assert 11.6 < held < 13.6, held
    text = compiled.as_text()
    # Two scans forward, recomputed and backward; three attention cores forward and backward.
    assert _core_kernels(text, "sscan_core") == (2, 4) and _core_kernels(text, "attention_core") == (3, 3)
    assert text.count("tpu_custom_call") == 12
    assert not re.search(r"f32\[(\d+,)*8192,(5120,16|16,5120|16,8,640)\]", text)
    _scopes_are_on(text, "phi4_flash_train", (
        "ssm", "sscan_core", "gmu", "attention_core", "swa_core", "unembed_loss", "optimizer",
    ))
