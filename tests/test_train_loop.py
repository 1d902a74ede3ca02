"""End-to-end train-step tests on the 8-fake-device mesh (SURVEY.md §4.3):
the real Mesh/collective code path, no TPU required."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import (
    sharding as shardlib,
    train_loop,
)
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.models import get_model
from distributed_tensorflow_models_tpu.ops import optim


def make_batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    return {
        "image": rng.rand(n, 28, 28, 1).astype(np.float32),
        "label": rng.randint(0, 10, (n,)),
    }


@pytest.fixture(scope="module")
def lenet_setup(mesh8):
    model = get_model("lenet")
    tx = optim.tf_momentum(0.05, 0.9)
    state = TrainState.create(
        model, tx, jax.random.key(0), jnp.zeros((2, 28, 28, 1)),
        ema_decay=0.999,
    )
    state = train_loop.place_state(state, mesh8)
    step = train_loop.make_train_step(
        train_loop.classification_loss_fn(model.apply)
    )
    return model, state, step


def test_loss_decreases(lenet_setup, mesh8):
    model, state, step = lenet_setup
    batch = shardlib.shard_batch(mesh8, make_batch())
    rng = jax.random.key(7)
    losses = []
    for _ in range(20):
        state, metrics = step(state, batch, rng)
        losses.append(float(metrics["loss"]))
    assert losses[-1] < losses[0] * 0.7, losses
    assert int(state.step) == 20


def test_deterministic(lenet_setup, mesh8):
    """SPMD sync training is reproducible — unlike the reference's async PS
    races (SURVEY.md §5.2)."""
    model, state0, step = lenet_setup
    batch = shardlib.shard_batch(mesh8, make_batch(seed=3))
    rng = jax.random.key(11)

    def run():
        s = state0
        out = []
        for _ in range(3):
            s, m = step(s, batch, rng)
            out.append(float(m["loss"]))
        return out

    assert run() == run()


def test_global_batch_semantics(mesh8):
    """Gradients over the sharded global batch must equal single-device
    gradients over the same full batch — the semantics the reference gets
    from SyncReplicasOptimizer's take_grad(N) averaging
    (TF sync_replicas_optimizer.py:281-282)."""
    model = get_model("lenet", dropout_rate=0.0)
    tx = optim.sgd(0.1)
    state = TrainState.create(
        model, tx, jax.random.key(0), jnp.zeros((2, 28, 28, 1))
    )
    loss_fn = train_loop.classification_loss_fn(model.apply)
    step = train_loop.make_train_step(loss_fn)
    batch_np = make_batch(n=16, seed=5)
    rng = jax.random.key(0)

    # Sharded over the 8-device mesh.
    state_mesh = train_loop.place_state(state, mesh8)
    s1, m1 = step(state_mesh, shardlib.shard_batch(mesh8, batch_np), rng)

    # Single device, full batch.
    batch_local = {k: jnp.asarray(v) for k, v in batch_np.items()}
    s2, m2 = step(state, batch_local, rng)

    np.testing.assert_allclose(
        float(m1["loss"]), float(m2["loss"]), rtol=1e-5
    )
    p1 = jax.tree.leaves(s1.params)
    p2 = jax.tree.leaves(s2.params)
    for a, b in zip(p1, p2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def test_eval_step_counts(lenet_setup, mesh8):
    model, state, step = lenet_setup
    batch = shardlib.shard_batch(mesh8, make_batch(n=24))
    eval_step = train_loop.make_eval_step(model.apply, use_ema=False)
    out = eval_step(state, batch)
    assert float(out["count"]) == 24
    assert 0 <= float(out["top1_count"]) <= 24
    assert float(out["top1_count"]) <= float(out["top5_count"])


def test_ema_tracks_params(lenet_setup, mesh8):
    model, state, step = lenet_setup
    batch = shardlib.shard_batch(mesh8, make_batch())
    rng = jax.random.key(1)
    s = state
    for _ in range(3):
        s, _ = step(s, batch, rng)
    # EMA shadows must differ from raw params but not be the init values.
    diffs = [
        float(jnp.max(jnp.abs(a - b)))
        for a, b in zip(
            jax.tree.leaves(s.params), jax.tree.leaves(s.ema_params)
        )
    ]
    assert max(diffs) > 0
    # eval_params prefers EMA
    assert s.eval_params is s.ema_params


# --------------------------------------------------------------------------
# Fused multi-step dispatch (make_multi_step): K-chunked lax.scan must be
# bit-identical to per-step dispatch — rng fold_in by the in-carry step,
# BN stats and the recurrent carry threading through the scan carry.
# --------------------------------------------------------------------------


class _TinyBN(nn.Module):
    """Minimal BN+dropout classifier: exercises batch_stats threading and
    per-step rng derivation without ResNet-sized compile times."""

    @nn.compact
    def __call__(self, x, train=False, **kw):
        x = x.reshape((x.shape[0], -1))
        x = nn.Dense(16)(x)
        x = nn.BatchNorm(use_running_average=not train)(x)
        x = nn.relu(x)
        x = nn.Dropout(0.2, deterministic=not train)(x)
        return nn.Dense(10)(x)


def _stack(batches):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *batches)


def _assert_trees_bitequal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_multi_step_bitexact_bn_model(mesh8):
    """steps_per_loop ∈ {1, K} trajectories agree EXACTLY (not within
    tolerance) for a BN+dropout model: same rng derivation per step, BN
    statistics threaded through the scan carry."""
    model = _TinyBN()
    tx = optim.tf_momentum(0.1, 0.9)
    state0 = TrainState.create(
        model, tx, jax.random.key(0), jnp.zeros((2, 28, 28, 1))
    )
    state0 = train_loop.place_state(state0, mesh8)
    loss_fn = train_loop.classification_loss_fn(model.apply)
    single = train_loop.make_train_step(loss_fn)
    multi = train_loop.make_multi_step(loss_fn)
    batches = [
        shardlib.shard_batch(mesh8, make_batch(seed=i)) for i in range(6)
    ]
    rng = jax.random.key(11)

    s1 = state0
    step_losses = []
    for b in batches:
        s1, m = single(s1, b, rng)
        step_losses.append(float(m["loss"]))

    s2 = state0
    chunk_losses = []
    for lo, hi in ((0, 4), (4, 6)):  # K=4 plus a shrunken tail
        s2, rows = multi(s2, _stack(batches[lo:hi]), rng)
        chunk_losses.extend(float(x) for x in np.asarray(rows["loss"]))

    assert step_losses == chunk_losses
    _assert_trees_bitequal(s1.params, s2.params)
    _assert_trees_bitequal(s1.batch_stats, s2.batch_stats)
    _assert_trees_bitequal(s1.opt_state, s2.opt_state)
    assert int(s2.step) == 6


def test_multi_step_bitexact_lstm_carry(mesh8):
    """The PTB LSTM's truncated-BPTT carry threads through the fused scan
    exactly as through the per-step loop — final carry and params bit-equal."""
    VOCAB, B, T = 50, 16, 8
    model = get_model(
        "ptb_lstm", config="small", vocab_size=VOCAB, dropout_rate=0.1
    )
    import optax

    tx = optax.chain(optim.clip_by_global_norm(5.0), optim.sgd(0.5))
    state0 = TrainState.create(
        model,
        tx,
        jax.random.key(0),
        jnp.zeros((B, T), jnp.int32),
        carry=model.initial_carry(B),
    )
    state0 = train_loop.place_state(state0, mesh8)
    loss_fn = train_loop.lm_loss_fn(model.apply)
    single = train_loop.make_train_step(loss_fn)
    multi = train_loop.make_multi_step(loss_fn)

    def lm_batch(seed):
        r = np.random.RandomState(seed)
        seq = r.randint(0, VOCAB, (B, T + 1))
        return shardlib.shard_batch(
            mesh8, {"inputs": seq[:, :-1], "targets": seq[:, 1:]}
        )

    batches = [lm_batch(i) for i in range(4)]
    rng = jax.random.key(3)

    s1 = state0
    for b in batches:
        s1, _ = single(s1, b, rng)
    s2, rows = multi(state0, _stack(batches), rng)

    _assert_trees_bitequal(s1.params, s2.params)
    _assert_trees_bitequal(s1.carry, s2.carry)
    assert np.asarray(rows["loss"]).shape == (4,)


def _fit_cfg(**kw):
    from distributed_tensorflow_models_tpu.harness import config as configlib

    base = dict(
        train_steps=10,
        global_batch_size=16,
        log_every_steps=5,
        checkpoint_every_secs=10_000.0,
    )
    base.update(kw)
    return configlib.get_config("lenet_mnist", **base)


def test_fit_steps_per_loop_trajectory_identical(mesh8, tmp_path):
    """fit with steps_per_loop=4 must reproduce steps_per_loop=1 exactly:
    same batches (BatchStacker resume-exact state), same rng, same final
    params bit-for-bit on the CPU fake mesh."""
    from distributed_tensorflow_models_tpu.harness import train as trainlib

    r1 = trainlib.fit(_fit_cfg(), str(tmp_path / "spl1"), mesh=mesh8)
    rk = trainlib.fit(
        _fit_cfg(steps_per_loop=4), str(tmp_path / "splk"), mesh=mesh8
    )
    assert r1.steps_run == rk.steps_run == 10
    _assert_trees_bitequal(r1.state.params, rk.state.params)
    assert r1.final_metrics["loss"] == rk.final_metrics["loss"]
    # final_metrics parity includes TelemetryHook's injected scalars (the
    # run ends on a log boundary, so the final row was walked and the
    # injection must land on the returned row, not a throwaway one).
    assert "steps_per_sec" in r1.final_metrics
    assert set(r1.final_metrics) == set(rk.final_metrics)


def test_fit_early_stop_extra_hook_is_step_exact(mesh8, tmp_path):
    """An early StopAtStepHook passed via extra_hooks must stop the fused
    loop at EXACTLY its step (not the chunk end): _chunk_len consults
    Hook.wants_step, so the chunk ends where the stop fires and the
    returned state carries no extra optimizer updates."""
    from distributed_tensorflow_models_tpu.harness import (
        hooks as hooklib2,
        train as trainlib,
    )

    res = trainlib.fit(
        _fit_cfg(steps_per_loop=4), str(tmp_path), mesh=mesh8,
        extra_hooks=[hooklib2.StopAtStepHook(7)],
    )
    assert res.steps_run == 7
    assert int(res.state.step) == 7


def test_fit_kill_mid_chunk_resumes_exact_next_batch(mesh8, tmp_path):
    """A fault injected at a MID-chunk step aborts with the end-of-chunk
    state + data position saved; the resumed run consumes exactly the next
    unconsumed batch, so the final params equal an uninterrupted run's
    bit-for-bit."""
    from distributed_tensorflow_models_tpu.harness import (
        hooks as hooklib2,
        train as trainlib,
    )

    ref = trainlib.fit(
        _fit_cfg(steps_per_loop=4), str(tmp_path / "ref"), mesh=mesh8
    )

    # Without the fault, chunks under log_every=5 are 1-4, 5, 6-9, 10.
    # Step 7 would be mid third chunk — but _chunk_len consults
    # wants_step, so the fault's presence cuts that chunk to end at
    # exactly step 7 and the abort saves the true step-7 state.
    wd = str(tmp_path / "killed")
    fault = hooklib2.FaultInjectionHook(
        7, lambda: RuntimeError("injected mid-chunk kill")
    )
    with pytest.raises(RuntimeError, match="mid-chunk kill"):
        trainlib.fit(
            _fit_cfg(steps_per_loop=4), wd, mesh=mesh8,
            extra_hooks=[fault],
        )
    resumed = trainlib.fit(_fit_cfg(steps_per_loop=4), wd, mesh=mesh8)
    # Resume restores step 7 + the exact next unconsumed batch and runs
    # steps 8-10; the final params equal the uninterrupted run's exactly
    # (scan chunking is length-invariant, so the different chunk split
    # cannot change numerics).
    assert resumed.steps_run == 3
    assert int(resumed.state.step) == 10
    _assert_trees_bitequal(ref.state.params, resumed.state.params)


def test_bn_model_train_step(mesh8):
    """ResNet-32 (with BatchNorm) through the generic step: batch_stats must
    update; BN statistics are global-batch (sync BN, SURVEY.md §7.4.2)."""
    model = get_model("resnet32_cifar")
    tx = optim.tf_momentum(0.1, 0.9)
    state = TrainState.create(
        model, tx, jax.random.key(0), jnp.zeros((2, 32, 32, 3))
    )
    state = train_loop.place_state(state, mesh8)
    step = train_loop.make_train_step(
        train_loop.classification_loss_fn(
            model.apply, weight_decay=1e-4
        )
    )
    rng_np = np.random.RandomState(0)
    batch = shardlib.shard_batch(
        mesh8,
        {
            "image": rng_np.rand(16, 32, 32, 3).astype(np.float32),
            "label": rng_np.randint(0, 10, (16,)),
        },
    )
    stats_before = jax.tree.leaves(state.batch_stats)[0]
    state, metrics = step(state, batch, jax.random.key(0))
    stats_after = jax.tree.leaves(state.batch_stats)[0]
    assert not np.allclose(
        np.asarray(stats_before), np.asarray(stats_after)
    )
    assert np.isfinite(float(metrics["loss"]))


# --------------------------------------------------------------------------
# The state's layout (``state_layout``) against the placement it replaced
# --------------------------------------------------------------------------


def _placement_by_suffix(state, mesh, rules):
    """The placement ``place_state`` decided leaf by leaf until PR 43, kept
    as the plain reference: an optimizer slot goes where the first
    parameter goes whose path is the tail of the slot's and whose spec has
    the slot's rank, else it is replicated."""
    rep = shardlib.replicated(mesh)
    param_sh = shardlib.tree_param_shardings(mesh, state.params, rules)
    by_path = {
        shardlib._path_str(p): s
        for p, s in jax.tree_util.tree_leaves_with_path(param_sh)
    }

    def follow(path, leaf):
        name = shardlib._path_str(path)
        for pname, s in by_path.items():
            if name.endswith(pname) and leaf.ndim == len(s.spec):
                return s
        return rep

    return state.replace(
        step=rep,
        params=param_sh,
        batch_stats=jax.tree.map(lambda _: rep, state.batch_stats),
        opt_state=jax.tree_util.tree_map_with_path(follow, state.opt_state),
        ema_params=None if state.ema_params is None else param_sh,
        carry=(
            None
            if state.carry is None
            else shardlib.tree_batch_shardings(mesh, state.carry)
        ),
    )


def _tiny_program_config(config):
    """One of the seven program configs the benchmark names, at the size
    its own test runs it at, with the EMA shadows on; its own rule set
    off, so that the state is built whatever the rules would divide."""
    from test_lm_fit_smoke import FITS
    from test_transformer import TINY

    from distributed_tensorflow_models_tpu.harness.config import get_config

    if config == "resnet50_synthetic":
        return get_config(
            config, image_size=32, global_batch_size=8, ema_decay=0.999,
            model_kwargs={"num_classes": 16}, param_rules="",
        )
    model = (
        TINY
        if config == "transformer_lm"
        else {**FITS[config]["model"], "vocab_size": 97}
    )
    return get_config(
        config, model_kwargs={**get_config(config).model_kwargs, **model},
        num_steps=32, global_batch_size=8, ema_decay=0.999, param_rules="",
    )


@pytest.fixture(scope="module")
def abstract_states():
    """``config -> (mesh, abstract state)``, each traced once for its two
    cases; data 4 x model 2 over the fake devices."""
    import functools

    from distributed_tensorflow_models_tpu.core import mesh as meshlib
    from distributed_tensorflow_models_tpu.harness import train as trainlib

    mesh = meshlib.create_mesh(meshlib.MeshSpec(data=-1, model=2))

    @functools.cache
    def one(config):
        cfg = _tiny_program_config(config)
        return mesh, jax.eval_shape(lambda: trainlib.build_state(cfg, mesh))

    return one


@pytest.mark.parametrize("tensor_parallel", [False, True], ids=["dp", "tp"])
@pytest.mark.parametrize(
    "config",
    ["resnet50_synthetic", "transformer_lm", "olmoe", "kimi_linear",
     "olmo_hybrid", "granite_h_micro", "nemotron3_nano"],
)
def test_state_layout_is_the_placement_by_suffix(
    abstract_states, config, tensor_parallel
):
    """``state_layout`` finds the optimizer's copies of the parameter tree
    by structure; for every program config the benchmark names it decides
    what the match of every slot's path against every parameter's did.
    Shapes only: nothing is placed or compiled."""
    from distributed_tensorflow_models_tpu.parallel import tensor as tensorlib

    mesh, state = abstract_states(config)
    family = "cnn_tp" if config == "resnet50_synthetic" else "transformer_tp"
    rules = tensorlib.get_rules(family if tensor_parallel else "")
    got = train_loop.state_layout(state, mesh, rules)
    want = _placement_by_suffix(state, mesh, rules)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    assert jax.tree.structure(got) == jax.tree.structure(state)
    differ = [
        (jax.tree_util.keystr(path), g.spec, w.spec)
        for (path, g), w in zip(
            jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)
        )
        if g != w
    ]
    assert not differ, differ
    sharded = [
        s for s in jax.tree.leaves(got.opt_state) if s.spec != shardlib.P()
    ]
    assert bool(sharded) == tensor_parallel  # the rules reached the slots
    assert jax.tree.leaves(got.ema_params) == jax.tree.leaves(got.params)
