"""Cold-start / restart-MTTR tests (harness/startup.py + fit wiring).

Pins the ISSUE 6 contracts: the AOT-compiled train step is bit-identical
to the jit path (K=1 and K>1); the config-derived batch specs match what
the live pipeline produces (so the overlap actually engages); a
mismatch or failure falls back to jit instead of breaking training; the
production compile-cache knob resolves as documented; heartbeats stay
fresh through an artificially slow restore (a steady-state
``--heartbeat-timeout`` cannot kill a cold-starting child); the
launcher stamps relaunch-to-first-step MTTR; and the new telemetry
keys (checkpoint/fence, startup/*) flow through goodput and the schema
lint.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_tensorflow_models_tpu import telemetry
from distributed_tensorflow_models_tpu.core import (
    sharding as shardlib,
    train_loop,
)
from distributed_tensorflow_models_tpu.core.train_state import TrainState
from distributed_tensorflow_models_tpu.harness import (
    checkpoint as ckptlib,
    config as configlib,
    startup as startuplib,
    train as trainlib,
)
from distributed_tensorflow_models_tpu.ops import optim
from distributed_tensorflow_models_tpu.resilience import heartbeat

_SCRIPTS = os.path.join(os.path.dirname(__file__), "..", "scripts")


def _load_script(name):
    from importlib import util as importutil

    spec = importutil.spec_from_file_location(
        name, os.path.join(_SCRIPTS, f"{name}.py")
    )
    mod = importutil.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny_setup(mesh):
    import flax.linen as nn

    class MLP(nn.Module):
        @nn.compact
        def __call__(self, x, train=False, **kw):
            x = x.reshape((x.shape[0], -1))
            return nn.Dense(10)(nn.relu(nn.Dense(16)(x)))

    model = MLP()
    state = TrainState.create(
        model, optim.sgd(0.1), jax.random.key(0),
        jnp.zeros((2, 8, 8, 1), jnp.float32),
    )
    state = train_loop.place_state(state, mesh)
    loss = train_loop.classification_loss_fn(model.apply)

    def batch(i):
        rng = np.random.RandomState(i)
        return shardlib.shard_batch(mesh, {
            "image": rng.rand(16, 8, 8, 1).astype(np.float32),
            "label": rng.randint(0, 10, (16,)).astype(np.int32),
        })

    return state, loss, batch


def _bit_identical(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _spec_of(batch):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding),
        batch,
    )


# --------------------------------------------------------------------------
# AOT executable == jit path, bit for bit
# --------------------------------------------------------------------------


def test_aot_step_bit_identical_to_jit_k1(mesh8):
    state, loss, batch = _tiny_setup(mesh8)
    jit_fn = train_loop.make_train_step(loss)
    rng = jax.random.key(7)
    aot = startuplib.AotTrainStep(
        jit_fn, (state, _spec_of(batch(0)), rng),
        registry=telemetry.MetricsRegistry(),
    ).start()
    exe, first = aot.acquire(startuplib.AotTrainStep.signature(batch(0)))
    assert exe is not None and first

    s_aot, s_jit = state, state
    for i in range(3):
        s_aot, m_aot = exe(s_aot, batch(i), rng)
        s_jit, m_jit = jit_fn(s_jit, batch(i), rng)
    _bit_identical(s_aot.params, s_jit.params)
    _bit_identical(s_aot.opt_state, s_jit.opt_state)
    assert float(m_aot["loss"]) == float(m_jit["loss"])


def test_aot_step_bit_identical_to_jit_multi(mesh8):
    state, loss, batch = _tiny_setup(mesh8)
    multi = train_loop.make_multi_step(loss)
    rng = jax.random.key(7)
    K = 3
    chunk = jax.tree.map(
        lambda *xs: jnp.stack(xs), *[batch(i) for i in range(K)]
    )
    aot = startuplib.AotTrainStep(
        multi,
        (state, startuplib.stacked_batch(_spec_of(batch(0)), K), rng),
        registry=telemetry.MetricsRegistry(),
    ).start()
    exe, _ = aot.acquire(startuplib.AotTrainStep.signature(chunk))
    assert exe is not None
    s_aot, rows_aot = exe(state, chunk, rng)
    s_jit, rows_jit = multi(state, chunk, rng)
    _bit_identical(s_aot.params, s_jit.params)
    _bit_identical(s_aot.opt_state, s_jit.opt_state)
    np.testing.assert_array_equal(
        np.asarray(rows_aot["loss"]), np.asarray(rows_jit["loss"])
    )


def test_aot_mismatch_falls_back_and_failure_propagates(mesh8):
    state, loss, batch = _tiny_setup(mesh8)
    jit_fn = train_loop.make_train_step(loss)
    rng = jax.random.key(0)
    aot = startuplib.AotTrainStep(
        jit_fn, (state, _spec_of(batch(0)), rng),
        registry=telemetry.MetricsRegistry(),
    ).start()
    wrong_sig = ((("nope",), "float32"),)
    assert aot.acquire(wrong_sig) == (None, False)
    good_sig = startuplib.AotTrainStep.signature(batch(0))
    exe, first = aot.acquire(good_sig)
    assert exe is not None and first
    _, again = aot.acquire(good_sig)
    assert not again  # first_use exactly once: compile-event accounting
    aot.disable()
    assert aot.acquire(good_sig) == (None, False)

    # A failed compile is re-raised where the step is first asked for:
    # the jit path would only fail the same way later.
    def broken(state, batch, rng):
        raise RuntimeError("boom at trace time")

    bad = startuplib.AotTrainStep(
        jax.jit(broken), (state, _spec_of(batch(0)), rng),
        registry=telemetry.MetricsRegistry(),
    ).start()
    assert bad.acquire(wrong_sig) == (None, False)
    with pytest.raises(RuntimeError, match="boom at trace time"):
        bad.acquire(good_sig)


def test_jit_init_bit_identical_to_eager(mesh8):
    """TrainState.create's cache-gated jitted init (the relaunch-MTTR
    init path) must produce byte-identical parameters, BN stats and
    optimizer slots to the eager init it replaces."""
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model("resnet32_cifar")
    sample = jnp.zeros((2, 32, 32, 3), jnp.float32)
    a = TrainState.create(
        model, optim.sgd(0.1), jax.random.key(3), sample, jit_init=False
    )
    b = TrainState.create(
        model, optim.sgd(0.1), jax.random.key(3), sample, jit_init=True
    )
    _bit_identical(a.params, b.params)
    _bit_identical(a.batch_stats, b.batch_stats)
    _bit_identical(a.opt_state, b.opt_state)


# --------------------------------------------------------------------------
# Config-derived specs must match the live pipeline
# --------------------------------------------------------------------------


def test_abstract_batch_matches_live_classification_batch(mesh8):
    cfg = configlib.get_config("lenet_mnist", global_batch_size=32)
    dataset = trainlib.build_dataset(cfg, "train")
    live = shardlib.shard_batch(mesh8, next(iter(dataset)))
    spec = startuplib.abstract_batch(cfg, mesh8)
    assert startuplib.AotTrainStep.signature(
        spec
    ) == startuplib.AotTrainStep.signature(live)
    # Shardings too — an AOT executable rejects sharding drift.
    for s, l in zip(
        jax.tree_util.tree_leaves(spec), jax.tree_util.tree_leaves(live)
    ):
        assert s.sharding == l.sharding


def test_abstract_batch_unknown_dataset_is_none(mesh8):
    cfg = configlib.get_config("lenet_mnist").replace(dataset="exotic")
    assert startuplib.abstract_batch(cfg, mesh8) is None


def test_dominant_chunk_len_mirrors_chunk_shrink_triggers():
    cfg = configlib.get_config(
        "lenet_mnist", steps_per_loop=16, train_steps=1000,
        log_every_steps=8,
    )
    assert startuplib.dominant_chunk_len(cfg) == 8
    assert startuplib.dominant_chunk_len(
        cfg.replace(checkpoint_every_steps=2)
    ) == 2
    assert startuplib.dominant_chunk_len(
        cfg.replace(preempt_poll_steps=4), nproc=2
    ) == 4
    assert startuplib.dominant_chunk_len(
        cfg.replace(log_every_steps=0)
    ) == 16
    assert startuplib.dominant_chunk_len(cfg.replace(train_steps=3)) == 3


# --------------------------------------------------------------------------
# Compile-cache knob resolution
# --------------------------------------------------------------------------


@pytest.mark.parametrize(
    "case", ["env_set", "unset_default", "explicit", "disabled"]
)
def test_apply_compile_cache_resolution(case, tmp_path, monkeypatch):
    """One helper places the cache: ``JAX_COMPILATION_CACHE_DIR`` wins
    when set, else an explicit path, else the fixed in-checkout
    directory (never a workdir or the cwd); ``""`` disables."""
    old = startuplib.configured_cache_dir()
    placed = str(tmp_path / "placed-from-outside")
    explicit = str(tmp_path / "cache-x")
    try:
        if case == "env_set":
            monkeypatch.setenv(startuplib.CACHE_DIR_ENV, placed)
            # Neither the default nor a config path moves a cache that
            # was placed from outside.
            assert startuplib.apply_compile_cache() == placed
            assert startuplib.apply_compile_cache(explicit) == placed
            assert startuplib.configured_cache_dir() == placed
            # ... but "" still switches it off.
            assert startuplib.apply_compile_cache("") is None
            assert startuplib.configured_cache_dir() is None
        elif case == "unset_default":
            monkeypatch.delenv(startuplib.CACHE_DIR_ENV, raising=False)
            repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            want = os.path.join(repo, ".xla_cache")
            # The same fixed path whichever workdir a run sits in.
            for workdir in ("run-a", "run-b"):
                (tmp_path / workdir).mkdir()
                monkeypatch.chdir(tmp_path / workdir)
                assert startuplib.apply_compile_cache() == want
                assert startuplib.configured_cache_dir() == want
            assert not list(tmp_path.rglob("*xla_cache*"))
        elif case == "explicit":
            monkeypatch.delenv(startuplib.CACHE_DIR_ENV, raising=False)
            assert startuplib.apply_compile_cache(explicit) == explicit
            assert startuplib.configured_cache_dir() == explicit
        else:
            monkeypatch.delenv(startuplib.CACHE_DIR_ENV, raising=False)
            assert old  # conftest configured it
            assert startuplib.apply_compile_cache("") is None
            assert startuplib.configured_cache_dir() is None
    finally:
        # Back to what conftest configured, through the same helper
        # (the one place that writes the setting).
        monkeypatch.undo()
        startuplib.apply_compile_cache(old or "")


def test_cli_startup_knob_overrides():
    from types import SimpleNamespace

    from distributed_tensorflow_models_tpu.harness import cli

    args = SimpleNamespace(
        train_steps=None, batch_size=None, seed=None,
        xla_cache_dir="/tmp/c", aot_compile=False,
    )
    out = cli._overrides(args)
    assert out["xla_cache_dir"] == "/tmp/c"
    assert out["aot_compile"] is False


# --------------------------------------------------------------------------
# fit end-to-end: AOT on/off bit-identity + startup telemetry
# --------------------------------------------------------------------------


def test_fit_aot_on_off_bit_identical(mesh8, tmp_path):
    cfg = configlib.get_config(
        "lenet_mnist", train_steps=4, global_batch_size=32,
        log_every_steps=2, checkpoint_every_secs=10_000.0,
    )
    on = trainlib.fit(cfg, str(tmp_path / "on"), mesh=mesh8)
    off = trainlib.fit(
        cfg.replace(aot_compile=False), str(tmp_path / "off"), mesh=mesh8
    )
    _bit_identical(on.state.params, off.state.params)
    _bit_identical(on.state.opt_state, off.state.opt_state)

    rep = json.load(open(tmp_path / "on" / "telemetry.json"))
    assert rep["startup"]["aot_compile_s"] > 0  # the thread really ran
    assert rep["startup"]["time_to_first_step_s"] > 0
    assert rep["compile_events"] >= 1  # first AOT use counts as compile
    rep_off = json.load(open(tmp_path / "off" / "telemetry.json"))
    assert rep_off["startup"]["aot_compile_s"] == 0.0

    # Rows carry the startup set (full set — the schema lint's contract).
    rows = [
        json.loads(line)
        for line in (tmp_path / "on" / "metrics.jsonl")
        .read_text().splitlines()
    ]
    telem = [r for r in rows if "data_wait_s" in r]
    assert telem
    for r in telem:
        for key in (
            "startup/restore_s", "startup/aot_compile_s",
            "startup/time_to_first_step_s", "checkpoint/fence_s",
        ):
            assert key in r, key
            assert r[key] >= 0


# --------------------------------------------------------------------------
# orbax comes without its cloud-logging stack (harness/startup.py::import_orbax)
# --------------------------------------------------------------------------

_NAME = "google.cloud.logging"
_HELPER = (
    "from distributed_tensorflow_models_tpu.harness.startup import "
    "import_orbax\n"
)
# Each script runs in a process of its own, so that what the test
# worker has imported does not decide it, and prints "ok" at its end.
_IMPORT_SCRIPTS = {
    "fit_imports_orbax_without_it": f"""
import sys
import distributed_tensorflow_models_tpu.harness.train
from distributed_tensorflow_models_tpu.harness import checkpoint
assert {_NAME!r} not in sys.modules, "the cloud-logging stack was imported"
ocp = checkpoint.ocp
assert ocp is sys.modules["orbax.checkpoint"]
ocp.CheckpointManager, ocp.StandardCheckpointer, ocp.args.StandardSave
ocp.logging.StandardLogger, ocp.logging.CompositeLogger
assert not hasattr(ocp.logging, "CloudLogger")
""",
    "imported_first_is_left_alone": f"""
import sys
import google.cloud.logging as first
{_HELPER}
ocp = import_orbax()
assert sys.modules[{_NAME!r}] is first
ocp.CheckpointManager, ocp.logging.CloudLogger
""",
    "importable_afterwards": f"""
import sys
{_HELPER}
ocp = import_orbax()
assert {_NAME!r} not in sys.modules  # neither the module nor a None
import google.cloud.logging
assert sys.modules[{_NAME!r}] is google.cloud.logging
assert import_orbax() is ocp
assert not hasattr(ocp.logging, "CloudLogger")
""",
    "restored_when_orbax_fails": f"""
import sys
{_HELPER}
sys.modules["orbax"] = None  # makes `import orbax.checkpoint` raise
try:
    import_orbax()
except ModuleNotFoundError as e:
    assert "orbax" in str(e), e
else:
    raise AssertionError("the import did not raise")
assert {_NAME!r} not in sys.modules
del sys.modules["orbax"]
import_orbax().CheckpointManager
assert {_NAME!r} not in sys.modules
""",
    # An orbax (a stub of one, ahead of the real on the path) that
    # imports the stack outside any try: refused, it fails, and the
    # helper imports it again with nothing refused.
    "an_orbax_that_needs_it_gets_it": f"""
import os, sys, tempfile
{_HELPER}
stub = os.path.join(tempfile.mkdtemp(), "orbax", "checkpoint")
os.makedirs(stub)
open(os.path.join(stub, "..", "__init__.py"), "w").close()
with open(os.path.join(stub, "__init__.py"), "w") as f:
    f.write("import google.cloud.logging as needed\\n")
sys.path.insert(0, os.path.dirname(os.path.dirname(stub)))
ocp = import_orbax()
assert ocp.__file__.startswith(stub), ocp.__file__
assert ocp.needed is sys.modules[{_NAME!r}]
""",
}


@pytest.mark.parametrize("case", list(_IMPORT_SCRIPTS))
def test_orbax_is_imported_without_the_cloud_logging_stack(case):
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.abspath(root), env.get("PYTHONPATH", "")]
    )
    done = subprocess.run(
        [sys.executable, "-c", _IMPORT_SCRIPTS[case] + 'print("ok")'],
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    assert done.stdout.strip().endswith("ok")


# --------------------------------------------------------------------------
# Heartbeat liveness through a slow cold start
# --------------------------------------------------------------------------


def test_heartbeat_stays_fresh_during_slow_restore(tmp_path):
    """The heartbeat writer free-runs on its own thread, so a restore +
    AOT compile of any length keeps the file fresh — a
    ``--heartbeat-timeout`` sized for steady-state steps can never kill
    a legitimately cold-starting child.  Simulated: a 0.6 s 'restore'
    (12x the write interval) against a 0.25 s timeout."""
    timeout_s = 0.25
    w = heartbeat.HeartbeatWriter(str(tmp_path), 0, interval_s=0.05).start()
    try:
        deadline = time.monotonic() + 0.6  # the artificially slow restore
        worst = 0.0
        while time.monotonic() < deadline:
            view = heartbeat.read_fleet(str(tmp_path), 1)[0]
            assert view is not None
            worst = max(worst, view["age_s"])
            assert view["step"] == -1  # not looping yet — and that's fine
            time.sleep(0.05)
        assert worst <= timeout_s, worst
        summary = heartbeat.fleet_summary(
            str(tmp_path), 1, stale_after_s=timeout_s
        )
        assert summary["peers_alive"] == 1
    finally:
        w.stop()


def test_launch_local_stamps_startup_mttr(tmp_path):
    """launch_local's startup_stats: spawn→first-beat→loop-entry→
    first-step milestones read off the heartbeat files (jax-free child
    that writes its own heartbeats, like a real worker's writer
    thread)."""
    from distributed_tensorflow_models_tpu import launch

    import sys

    child = (
        "import json, os, time\n"
        "d = os.environ['DTM_HEARTBEAT_DIR']\n"
        "i = os.environ['DTM_PROCESS_ID']\n"
        "def beat(step):\n"
        "    p = os.path.join(d, f'p{i}.json')\n"
        "    with open(p + '.tmp', 'w') as f:\n"
        "        json.dump({'pid': os.getpid(), 'time': time.time(),"
        " 'step': step}, f)\n"
        "    os.replace(p + '.tmp', p)\n"
        "beat(-1); time.sleep(0.3)\n"   # 'restoring'
        "beat(5); time.sleep(0.3)\n"    # entered the loop at step 5
        "beat(7); time.sleep(0.3)\n"    # first chunk done
    )
    stats: dict = {}
    codes = launch.launch_local(
        1, [sys.executable, "-c", child], timeout=30.0,
        startup_stats=stats,
    )
    assert codes == [0]
    st = stats[0]
    assert 0 <= st["first_beat_s"] <= st["loop_entry_s"]
    assert st["loop_entry_s"] <= st["first_step_s"]
    assert "_entry_step" not in st


# --------------------------------------------------------------------------
# Fence accounting + goodput/schema plumbing
# --------------------------------------------------------------------------


def test_checkpoint_fence_records_only_when_pending(tmp_path):
    reg = telemetry.MetricsRegistry()
    mgr = ckptlib.CheckpointManager(
        str(tmp_path), registry=reg, process_index=0, process_count=1
    )

    class StubOrbax:
        def __init__(self):
            self.pending = True

        def is_saving_in_progress(self):
            return self.pending

        def wait_until_finished(self):
            self.pending = False

    mgr._mgr.close()
    mgr._mgr = StubOrbax()
    mgr.fence()  # pending -> records one fence
    mgr.fence()  # idle -> no record
    snap = reg.snapshot()
    assert snap["checkpoint/fence/count"] == 1.0
    # wait() always records — the explicit-fence paths want the block
    # visible even when it cost nothing.
    mgr.wait()
    mgr.wait()
    assert reg.snapshot()["checkpoint/wait/count"] == 2.0


def test_goodput_report_counts_fence_and_carries_startup():
    reg = telemetry.MetricsRegistry()
    reg.timer(telemetry.CKPT_SAVE).record(0.05)
    reg.timer(telemetry.CKPT_FENCE).record(0.15)
    reg.gauge(telemetry.STARTUP_RESTORE).set(1.5)
    reg.gauge(telemetry.STARTUP_AOT_COMPILE).set(0.7)
    reg.gauge(telemetry.STARTUP_FIRST_STEP).set(2.5)
    reg.counter(telemetry.STARTUP_CACHE_HITS).inc(3)
    rep = telemetry.goodput_report(reg, total_s=1.0, steps=4, kind="CPU")
    assert rep["fractions"]["checkpoint"] == pytest.approx(0.2)
    assert sum(rep["fractions"].values()) == pytest.approx(1.0)
    # The whole timeline, each key under its name less the prefix, and an
    # explicit zero for what this registry never saw.
    assert list(rep["startup"]) == [
        key.split("/", 1)[1]
        for key in (*telemetry.STARTUP_GAUGES, *telemetry.STARTUP_COUNTERS)
    ]
    assert {k: v for k, v in rep["startup"].items() if v} == {
        "restore_s": 1.5, "aot_compile_s": 0.7,
        "time_to_first_step_s": 2.5, "cache_hits": 3.0,
    }


# --------------------------------------------------------------------------
# The start-up timeline (ISSUE 34)
# --------------------------------------------------------------------------

_TIMELINE_CFG = dict(
    train_steps=6, global_batch_size=32, log_every_steps=3,
    checkpoint_every_secs=10_000.0, trace_export=True,
)


def _startup_events(workdir):
    """The ``startup/*`` complete events of a fit's span export, by
    thread: ``{tid: [(name, start_s, end_s), ...]}`` in start order."""
    trace = json.load(open(os.path.join(workdir, "trace_p0.json")))
    by_tid = {}
    for e in trace["traceEvents"]:
        if e["name"].startswith("startup/") and e["ph"] == "X":
            by_tid.setdefault(e["tid"], []).append(
                (e["name"], e["ts"] * 1e-6, (e["ts"] + e["dur"]) * 1e-6)
            )
    return {tid: sorted(evs, key=lambda e: e[1]) for tid, evs in by_tid.items()}


@pytest.mark.parametrize("steps_per_loop", [1, 3])
def test_fit_stamps_the_startup_timeline(mesh8, tmp_path, steps_per_loop):
    """Every start-up gauge and counter is in telemetry.json, the
    exclusive phases tile fit entry to the first chunk, the first loss
    row comes no earlier than the first chunk, and the span export shows
    the main thread's phases in order beside the AOT thread's."""
    cfg = configlib.get_config(
        "lenet_mnist", steps_per_loop=steps_per_loop, **_TIMELINE_CFG
    )
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    rep = json.load(open(tmp_path / "telemetry.json"))
    snap, startup = rep["metrics"], rep["startup"]
    for key in (*telemetry.STARTUP_GAUGES, *telemetry.STARTUP_COUNTERS):
        assert key in snap, key
        assert startup[key.split("/", 1)[1]] == snap[key]
    assert _load_script("check_metrics_schema").check_startup_section(rep) == []

    phases = [snap[k] for k in telemetry.STARTUP_PHASES]
    assert all(v > 0 for v in phases)
    first_step = snap[telemetry.STARTUP_FIRST_STEP]
    unattributed = snap[telemetry.STARTUP_UNATTRIBUTED]
    assert first_step - sum(phases) == pytest.approx(unattributed, abs=1e-9)
    assert -1e-3 <= unattributed < 0.05
    # A fresh run walks no checkpoint: the restore phase is there and empty.
    assert snap[telemetry.STARTUP_RESTORE] < 0.1
    assert snap[telemetry.STARTUP_FIRST_LOSS_ROW] >= first_step
    if steps_per_loop == 1:
        # Steps 1 and 2 run between the first chunk and the first row.
        assert snap[telemetry.STARTUP_FIRST_LOSS_ROW] > first_step
    assert 0 <= snap[telemetry.STARTUP_FIRST_DATA_WAIT] <= snap[
        telemetry.STARTUP_FIRST_CHUNK
    ]
    assert snap[telemetry.STARTUP_AOT_JOIN] <= snap[telemetry.STARTUP_FIRST_CHUNK]
    assert snap[telemetry.STARTUP_AOT_COMPILE] >= snap[
        telemetry.STARTUP_AOT_LOWER
    ] > 0
    assert snap[telemetry.STARTUP_COMPILE_REQUESTS] >= 1
    assert snap[telemetry.STARTUP_CACHE_HITS] <= snap[
        telemetry.STARTUP_COMPILE_REQUESTS
    ]
    assert 500 < snap[telemetry.STARTUP_MODULES_AT_FIT] <= len(sys.modules)
    assert snap[telemetry.STARTUP_CLOUD_LOGGING_IMPORTED] == (
        "google.cloud.logging" in sys.modules
    )

    by_tid = _startup_events(str(tmp_path))
    main = next(
        evs for evs in by_tid.values()
        if any(name == "startup/build_state" for name, _, _ in evs)
    )
    # The wait for the background compile, where there was one, is the
    # loop's own and lies inside its first iteration.
    joins = [e for e in main if e[0] == "startup/aot_join"]
    main = [e for e in main if e[0] != "startup/aot_join"]
    assert [name for name, _, _ in main] == [
        "startup/process_to_fit",
        *(key[: -len("_s")] for key in telemetry.STARTUP_PHASES),
    ]
    for (_, _, end), (name, start, _) in zip(main, main[1:]):
        # One clock: each phase starts where the one before ended (to
        # the microsecond the export's float64 wall stamps keep).
        assert abs(start - end) < 5e-6, name
    for _, start, end in joins:
        assert main[-1][1] <= start and end <= main[-1][2]
    assert len(joins) == (snap[telemetry.STARTUP_AOT_JOIN] > 0)
    (aot,) = [evs for tid, evs in by_tid.items() if evs[0][0] != main[0][0]]
    assert [name for name, _, _ in aot] == [
        "startup/aot_lower", "startup/aot_compile",
    ]


def test_aot_off_leaves_explicit_zeros(mesh8, tmp_path):
    cfg = configlib.get_config(
        "lenet_mnist", aot_compile=False, **_TIMELINE_CFG
    )
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    snap = json.load(open(tmp_path / "telemetry.json"))["metrics"]
    for key in (
        telemetry.STARTUP_AOT_JOIN,
        telemetry.STARTUP_AOT_LOWER,
        telemetry.STARTUP_AOT_COMPILE,
    ):
        assert snap[key] == 0.0, key
    assert len(_startup_events(str(tmp_path))) == 1  # the main thread alone


def test_aot_join_gauge_is_the_wait_acquire_measured(mesh8):
    """``startup/aot_join_s`` is set from the one measurement ``acquire``
    makes for its span: the wait while the thread still compiles, and
    untouched (the timeline's explicit 0.0) once it had finished."""
    state, loss, batch = _tiny_setup(mesh8)
    step = train_loop.make_train_step(loss)
    rng = jax.random.key(7)
    b = batch(0)

    class Slow:
        def lower(self, *args):
            time.sleep(0.3)
            return step.lower(*args)

    def run(fn, wait_first):
        reg = telemetry.MetricsRegistry()
        reg.trace = telemetry.Tracer(capacity=64)
        aot = startuplib.AotTrainStep(
            fn, (state, _spec_of(b), rng), registry=reg
        ).start()
        if wait_first:
            aot.join()
        exe, first = aot.acquire(startuplib.AotTrainStep.signature(b))
        assert exe is not None and first
        joins = [
            e for e in reg.trace.events() if e["name"] == "startup/aot_join"
        ]
        return reg.gauge(telemetry.STARTUP_AOT_JOIN).value, joins

    waited, joins = run(Slow(), wait_first=False)
    assert waited > 0.1 and len(joins) == 1
    assert joins[0]["dur_s"] == waited
    done, joins = run(step, wait_first=True)
    assert done == 0.0 and joins == []


def test_process_to_fit_agrees_with_the_benchmarks_clock(mesh8, tmp_path):
    """``startup/process_to_fit_s`` starts where the benchmark's
    ``setup_s`` starts: the kernel's record of the process's start."""
    from importlib import util as importutil

    spec = importutil.spec_from_file_location(
        "benchmark_run_for_test",
        os.path.join(os.path.dirname(_SCRIPTS), "benchmark", "run.py"),
    )
    bench_run = importutil.module_from_spec(spec)
    spec.loader.exec_module(bench_run)

    cfg = configlib.get_config("lenet_mnist", **_TIMELINE_CFG)
    t_before = time.perf_counter()
    trainlib.fit(cfg, str(tmp_path), mesh=mesh8)
    since_start = bench_run.seconds_since_process_start()
    age = time.perf_counter() - t_before
    snap = json.load(open(tmp_path / "telemetry.json"))["metrics"]
    assert snap[telemetry.STARTUP_PROCESS_TO_FIT] == pytest.approx(
        since_start - age, abs=0.5
    )
    assert startuplib.seconds_since_process_start() == pytest.approx(
        bench_run.seconds_since_process_start(), abs=0.05
    )


def test_two_fits_count_their_own_compile_requests(mesh8, tmp_path):
    """One listener a process, however often the cache is placed, and
    each fit's report holds what its own start-up asked of the cache."""
    import jax.monitoring

    seen = []

    def on_event(event, **_):
        if event == startuplib._COMPILE_REQUEST_EVENT:
            seen.append(event)

    shared = telemetry.get_registry().counter(
        telemetry.STARTUP_COMPILE_REQUESTS
    )
    startuplib.apply_compile_cache()
    startuplib.apply_compile_cache()
    jax.monitoring.register_event_listener(on_event)
    try:
        cfg = configlib.get_config("lenet_mnist", **_TIMELINE_CFG)
        for name in ("a", "b"):
            seen_before, shared_before = len(seen), shared.value
            trainlib.fit(cfg, str(tmp_path / name), mesh=mesh8)
            in_fit = len(seen) - seen_before
            # Counted once, not once per apply_compile_cache call.
            assert shared.value - shared_before == in_fit
            rep = json.load(open(tmp_path / name / "telemetry.json"))
            requests = rep["metrics"][telemetry.STARTUP_COMPILE_REQUESTS]
            # Its own start-up's, up to the first chunk: at least the
            # step program, at most what the whole fit asked for.
            assert 1 <= requests <= in_fit, (name, requests, in_fit)
    finally:
        jax.monitoring.unregister_event_listener(on_event)


def test_metrics_schema_startup_and_checkpoint_keys():
    check_lines = _load_script("check_metrics_schema").check_lines

    def row(**kw):
        return json.dumps({"step": 1, "time": 1.0, **kw})

    full = {
        "startup/restore_s": 0.5,
        "startup/aot_compile_s": 0.2,
        "startup/time_to_first_step_s": 1.0,
        "checkpoint/fence_s": 0.0,
    }
    errors, rows, _ = check_lines([row(**full)])
    assert errors == [] and rows == 1
    errors, _, _ = check_lines([row(**{"startup/restore_s": 0.5})])
    assert any("partial startup key set" in e for e in errors)
    errors, _, _ = check_lines(
        [row(**{**full, "startup/restore_s": -1.0})]
    )
    assert any("startup gauge" in e and "negative" in e for e in errors)
    errors, _, _ = check_lines([row(**{"checkpoint/fence_s": -0.1})])
    assert any("checkpoint key" in e and "negative" in e for e in errors)


def test_metrics_schema_startup_section():
    """telemetry.json's start-up timeline is a set: every key, numbers,
    none negative, phases + remainder = time to the first step."""
    schema = _load_script("check_metrics_schema")
    check = schema.check_startup_section
    good = dict.fromkeys(schema.STARTUP_REPORT_KEYS, 0.0)
    assert set(good) == {
        key.split("/", 1)[1]
        for key in (*telemetry.STARTUP_GAUGES, *telemetry.STARTUP_COUNTERS)
    }
    good.update(
        process_to_fit_s=20.0, build_state_s=4.0, first_chunk_s=5.5,
        unattributed_s=0.5, time_to_first_step_s=10.0,
        first_loss_row_s=12.0, compile_requests=4.0, cache_hits=3.0,
        modules_at_fit=1400.0,
    )
    assert check({"startup": good}) == []
    assert check({"startup": dict.fromkeys(good, 0.0)}) == []  # no step yet
    assert check({}) == ["report carries no 'startup' section object"]
    lacking = {k: v for k, v in good.items() if k != "aot_join_s"}
    assert any("lacks 'aot_join_s'" in e for e in check({"startup": lacking}))
    for change, message in (
        ({"dataset_s": -0.1}, "is negative"),
        ({"unattributed_s": -0.5}, "is negative"),
        ({"first_chunk_s": 7.0}, "do not add up"),
        ({"cache_hits": 5.0}, "exceed"),
        ({"restore_s": "0"}, "not a number"),
    ):
        errors = check({"startup": {**good, **change}})
        assert any(message in e for e in errors), (change, errors)
    # The remainder may read a rounding below zero.
    assert check({"startup": {**good, "unattributed_s": -1e-6,
                              "first_chunk_s": 6.000001}}) == []
