"""Continuous deployment: hot-swap, canary gate, and deploy journal.

Pinned here (ISSUE 20):

- :class:`CanaryController` state machine — promote only after warmup
  plus a healthy streak, rollback on a breach streak (which accrues
  even during warmup), streak resets on opposite evidence (no-flap),
  terminal states latch, and ctor validation.
- Candidate admission gate — ``gate_candidate`` rejects torn layouts
  (structural, retryable), incomplete fleet sidecars (structural),
  non-finite weights and aval drift (semantic, final), and restores a
  good step.  Torn/sidecar cases run jax-free on fabricated
  directories; NaN / aval-drift cases restore real orbax saves.
- Deterministic rid-hash routing — the same (seed, rid) always routes
  the same way, the observed canary share tracks the fraction, and the
  edges (no canary, fraction 0 and 1) are exact.
- ``deploy_events.jsonl`` — append/load round-trip, a torn tail line
  is skipped, and non-event rows are filtered.
- The tentpole hot path: a weight swap at a burst boundary leaves an
  in-flight stream byte-identical to a solo run under its admitted
  version, pins new admissions to the new version, and never
  recompiles (``compile_counts`` unchanged).
- :class:`CheckpointFollower` end-to-end against a real checkpoint
  dir: gate → canary_start → promote on healthy SLO windows, rollback
  on breaching ones, immediate final reject of a NaN-poisoned step —
  each with its journal row and public-registry counters.

The pure-python tests deliberately avoid jax: the controller/journal
half of ``serving/deploy.py`` must work on supervisor hosts with no
accelerator stack (it is in the lint jax-free zone).
"""

import json
import os

import numpy as np
import pytest

from distributed_tensorflow_models_tpu.serving import deploy as deploylib
from distributed_tensorflow_models_tpu.telemetry import registry as reglib


# ---------------------------------------------------------------------------
# CanaryController
# ---------------------------------------------------------------------------


def test_canary_controller_promotes_after_warmup_and_streak():
    ctl = deploylib.CanaryController(
        warmup=3, promote_after=2, rollback_after=2
    )
    assert ctl.state == "warmup"
    # Healthy evaluations before warmup absorb no promote evidence.
    assert ctl.observe(samples=0, breached=False) is None
    assert ctl.observe(samples=2, breached=False) is None
    assert ctl.state == "warmup"
    # The evaluation that crosses warmup counts toward the streak.
    assert ctl.observe(samples=3, breached=False) is None
    assert ctl.state == "observe"
    assert ctl.observe(samples=5, breached=False) == "promote"
    assert ctl.state == "promoted"


def test_canary_controller_breach_during_warmup_rolls_back():
    # A candidate bad enough to breach while barely warmed is exactly
    # the one to pull fastest: breach evidence accrues during warmup.
    ctl = deploylib.CanaryController(
        warmup=100, promote_after=2, rollback_after=2
    )
    assert ctl.observe(samples=1, breached=True) is None
    assert ctl.observe(samples=2, breached=True) == "rollback"
    assert ctl.state == "rolled_back"


def test_canary_controller_no_flap_on_alternating_evidence():
    ctl = deploylib.CanaryController(
        warmup=0, promote_after=2, rollback_after=2
    )
    assert ctl.state == "observe"  # warmup=0 starts observing
    for _ in range(10):  # alternating evidence never reaches a verdict
        assert ctl.observe(samples=50, breached=False) is None
        assert ctl.observe(samples=50, breached=True) is None
    assert ctl.state == "observe"


def test_canary_controller_terminal_states_latch():
    ctl = deploylib.CanaryController(
        warmup=0, promote_after=1, rollback_after=1
    )
    assert ctl.observe(samples=1, breached=False) == "promote"
    for breached in (True, False, True):
        assert ctl.observe(samples=99, breached=breached) is None
    ctl2 = deploylib.CanaryController(
        warmup=0, promote_after=1, rollback_after=1
    )
    assert ctl2.observe(samples=1, breached=True) == "rollback"
    assert ctl2.observe(samples=99, breached=False) is None


def test_canary_controller_ctor_validation():
    with pytest.raises(ValueError):
        deploylib.CanaryController(warmup=-1)
    with pytest.raises(ValueError):
        deploylib.CanaryController(promote_after=0)
    with pytest.raises(ValueError):
        deploylib.CanaryController(rollback_after=0)


# ---------------------------------------------------------------------------
# Deterministic rid-hash routing
# ---------------------------------------------------------------------------


def test_rid_routing_deterministic_and_tracks_fraction():
    rids = [str(i) for i in range(4000)]
    fracs = [deploylib.rid_fraction(7, rid) for rid in rids]
    # Pure: same (seed, rid) -> same score, every time.
    assert fracs == [deploylib.rid_fraction(7, rid) for rid in rids]
    assert all(0.0 <= f < 1.0 for f in fracs)
    # A different seed reshuffles the population.
    assert fracs != [deploylib.rid_fraction(8, rid) for rid in rids]
    share = sum(
        deploylib.route_version(7, rid, 0.25, 10, 20) == 20 for rid in rids
    ) / len(rids)
    assert abs(share - 0.25) < 0.03  # crc32 is uniform enough at n=4000


def test_route_version_edges():
    assert deploylib.route_version(0, "r", 1.0, 10, None) == 10  # no canary
    for rid in ("a", "b", "c"):
        assert deploylib.route_version(0, rid, 0.0, 10, 20) == 10
        assert deploylib.route_version(0, rid, 1.0, 10, 20) == 20


# ---------------------------------------------------------------------------
# Signatures / finiteness (numpy trees, jax-free)
# ---------------------------------------------------------------------------


def test_tree_signature_and_diff():
    a = {"w": np.zeros((2, 3), np.float32), "b": {"v": np.ones(4, np.int32)}}
    sig = deploylib.tree_signature(a)
    assert sig == deploylib.tree_signature(
        {"b": {"v": np.zeros(4, np.int32)}, "w": np.ones((2, 3), np.float32)}
    )  # values and dict order do not matter, shapes/dtypes/paths do
    drift = {"w": np.zeros((2, 4), np.float32), "b": {"v": np.ones(4, np.int32)}}
    msgs = deploylib.signature_diff(sig, deploylib.tree_signature(drift))
    assert msgs and any("(2, 3)" in m and "(2, 4)" in m for m in msgs)
    missing = deploylib.signature_diff(
        sig, deploylib.tree_signature({"w": np.zeros((2, 3), np.float32)})
    )
    assert missing
    assert deploylib.signature_diff(sig, sig) == []


def test_check_finite_flags_nan_and_inf_paths():
    good = {"a": np.ones((2, 2), np.float32), "n": np.arange(3)}
    assert deploylib.check_finite(good) == []
    bad = {
        "a": np.array([1.0, np.nan], np.float32),
        "b": {"c": np.array([np.inf], np.float32)},
        "n": np.arange(3),  # integer leaves are never flagged
    }
    paths = deploylib.check_finite(bad)
    assert any("a" in p for p in paths) and any("c" in p for p in paths)
    assert len(paths) == 2


# ---------------------------------------------------------------------------
# deploy_events.jsonl journal
# ---------------------------------------------------------------------------


def test_deploy_events_roundtrip_and_torn_tail(tmp_path):
    wd = str(tmp_path)
    deploylib.append_deploy_event(
        wd, {"ts_wall": 1.0, "proc": 0, "event": "canary_start", "step": 4}
    )
    deploylib.append_deploy_event(
        wd, {"ts_wall": 2.0, "proc": 0, "event": "promote", "step": 4}
    )
    # Non-event rows and a torn tail line must both be tolerated.
    with open(deploylib.deploy_events_path(wd), "a") as f:
        f.write(json.dumps({"note": "not a deploy event"}) + "\n")
        f.write('{"ts_wall": 3.0, "event": "rollb')  # torn write
    rows = deploylib.load_deploy_events(wd)
    assert [r["event"] for r in rows] == ["canary_start", "promote"]
    assert rows[0]["step"] == 4 and rows[1]["ts_wall"] == 2.0
    assert deploylib.load_deploy_events(str(tmp_path / "nowhere")) == []


# ---------------------------------------------------------------------------
# Candidate gate: structural failures on fabricated layouts (jax-free)
# ---------------------------------------------------------------------------


def _fake_step(ckpt_dir, step, *, torn=None):
    """Fabricate an orbax-shaped step dir; ``torn`` names a file to omit."""
    step_dir = os.path.join(ckpt_dir, str(step))
    os.makedirs(os.path.join(step_dir, "state"), exist_ok=True)
    layout = {
        "_CHECKPOINT_METADATA": os.path.join(step_dir, "_CHECKPOINT_METADATA"),
        "state/_METADATA": os.path.join(step_dir, "state", "_METADATA"),
        "state/manifest.ocdbt": os.path.join(
            step_dir, "state", "manifest.ocdbt"
        ),
    }
    for name, path in layout.items():
        if name != torn:
            with open(path, "w") as f:
                f.write("{}")
    return step_dir


def test_gate_candidate_rejects_torn_layout_as_structural(tmp_path):
    ckpt = str(tmp_path)
    _fake_step(ckpt, 3, torn="state/manifest.ocdbt")
    params, reasons, structural = deploylib.gate_candidate(ckpt, 3)
    assert params is None and structural
    assert any(r.startswith("fsck:") and "manifest.ocdbt" in r
               for r in reasons)
    params, reasons, structural = deploylib.gate_candidate(ckpt, 99)
    assert params is None and structural  # missing step dir entirely
    assert any("missing step directory" in r for r in reasons)


def test_gate_candidate_rejects_incomplete_fleet_sidecars(tmp_path):
    ckpt = str(tmp_path)
    _fake_step(ckpt, 5)
    side = os.path.join(ckpt, "dataset_states", "5")
    os.makedirs(side)
    with open(os.path.join(side, "p0.json"), "w") as f:
        json.dump({"step": 5, "process_count": 2}, f)
    params, reasons, structural = deploylib.gate_candidate(
        ckpt, 5, process_count=2
    )
    assert params is None and structural
    assert any("not fleet-valid" in r for r in reasons)


# ---------------------------------------------------------------------------
# Candidate gate + follower against real orbax saves (jax)
# ---------------------------------------------------------------------------


def _save_candidate(ckpt_dir, step, tree):
    """Write a real orbax save in the trainer's step layout."""
    from distributed_tensorflow_models_tpu.harness.startup import import_orbax

    step_dir = os.path.join(ckpt_dir, str(step))
    os.makedirs(step_dir, exist_ok=True)
    ckptr = import_orbax().StandardCheckpointer()
    ckptr.save(os.path.join(step_dir, "state"), {"params": tree})
    ckptr.wait_until_finished()  # StandardCheckpointer saves async
    with open(os.path.join(step_dir, "_CHECKPOINT_METADATA"), "w") as f:
        f.write("{}")
    return step_dir


@pytest.fixture(scope="module")
def deploy_lm():
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model(
        "transformer_lm",
        vocab_size=32,
        num_layers=1,
        num_heads=2,
        d_model=16,
        d_ff=32,
        max_len=32,
        dropout_rate=0.0,
        dtype=jnp.float32,
        attn_impl="reference",
    )
    dummy = jnp.zeros((1, 4), jnp.int32)
    params_a = model.init(jax.random.key(0), dummy)["params"]
    params_b = model.init(jax.random.key(1), dummy)["params"]
    return model, params_a, params_b


def test_gate_candidate_semantic_rejects_and_accepts(tmp_path, deploy_lm):
    import jax

    _, params_a, params_b = deploy_lm
    ckpt = str(tmp_path)
    expected = deploylib.tree_signature(params_a)

    # Good step: restores, finite, same avals.
    _save_candidate(ckpt, 2, params_b)
    params, reasons, structural = deploylib.gate_candidate(
        ckpt, 2, expected_signature=expected
    )
    assert reasons == [] and not structural
    assert deploylib.tree_signature(params) == expected

    # NaN-poisoned step: semantic, final.
    poisoned = jax.tree_util.tree_map(
        lambda x: np.asarray(x).astype(np.float32) * np.nan, params_a
    )
    _save_candidate(ckpt, 4, poisoned)
    params, reasons, structural = deploylib.gate_candidate(
        ckpt, 4, expected_signature=expected
    )
    assert params is None and not structural
    assert any(r.startswith("non-finite leaves:") for r in reasons)

    # Aval drift: semantic, final.
    _save_candidate(ckpt, 6, {"w": np.zeros((3, 3), np.float32)})
    params, reasons, structural = deploylib.gate_candidate(
        ckpt, 6, expected_signature=expected
    )
    assert params is None and not structural
    assert any(r.startswith("avals:") for r in reasons)


# ---------------------------------------------------------------------------
# The tentpole hot path: swap at a burst boundary, streams byte-identical
# ---------------------------------------------------------------------------


def _drain(sched):
    out = {}
    while sched.has_work:
        for comp in sched.step():
            out[comp.request_id] = comp
    return out


def test_hot_swap_mid_stream_byte_identity_and_compile_pins(deploy_lm):
    """r1 decodes under v0 while the canary for step 7 installs and
    promotes at a burst boundary; r2 admits under v7.  Both streams
    must be byte-identical to solo runs under their admitted weights,
    and the swap must not compile anything new."""
    from distributed_tensorflow_models_tpu.serving.engine import (
        InferenceEngine,
    )
    from distributed_tensorflow_models_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    model, params_a, params_b = deploy_lm
    prompt = np.asarray([5, 9, 2, 11, 3], np.int32)

    def solo(params):
        eng = InferenceEngine(
            model, params, max_slots=2, prefill_chunk=8,
            registry=reglib.MetricsRegistry(),
        )
        sched = ContinuousBatchingScheduler(eng, registry=eng.registry)
        sched.submit(Request(request_id=0, prompt=prompt, max_new_tokens=10))
        return _drain(sched)[0].tokens

    ref_a, ref_b = solo(params_a), solo(params_b)
    assert list(ref_a) != list(ref_b)  # the swap must be observable

    eng = InferenceEngine(
        model, params_a, max_slots=2, prefill_chunk=8,
        registry=reglib.MetricsRegistry(),
    )
    sched = ContinuousBatchingScheduler(eng, registry=eng.registry)
    sched.submit(Request(request_id=1, prompt=prompt, max_new_tokens=10))
    for _ in range(4):  # r1 mid-stream: prefill + a few decode bursts
        sched.step()
    pins = eng.compile_counts()

    # Burst boundary between sched.step() calls: install + promote.
    eng.install_canary(7, params_b)
    assert eng.canary_version == 7
    eng.promote_canary()
    assert eng.version == 7 and eng.canary_version is None

    sched.submit(Request(request_id=2, prompt=prompt, max_new_tokens=10))
    done = _drain(sched)

    # In-flight r1 stayed pinned to v0 weights; r2 ran under v7.
    assert done[1].version == 0 and done[2].version == 7
    assert list(done[1].tokens) == list(ref_a)
    assert list(done[2].tokens) == list(ref_b)
    # The swap compiled nothing: same two programs before and after.
    assert eng.compile_counts() == pins

    # install_canary refuses non-newer steps and double canaries.
    with pytest.raises(ValueError):
        eng.install_canary(7, params_b)
    eng.install_canary(8, params_b)
    with pytest.raises(ValueError):
        eng.install_canary(9, params_a)
    eng.rollback_canary()
    assert eng.version == 7 and eng.canary_version is None


def test_install_canary_restored_params_do_not_retrace(tmp_path, deploy_lm):
    """Checkpoint restores hand back device-committed arrays while boot
    params are uncommitted; jit keys on that bit, so an unnormalised
    install would retrace both programs on the first canary burst.
    Regression: dispatch canary traffic from an orbax round-trip and
    pin compile_counts."""
    from distributed_tensorflow_models_tpu.serving.engine import (
        InferenceEngine,
    )
    from distributed_tensorflow_models_tpu.serving.scheduler import (
        ContinuousBatchingScheduler,
        Request,
    )

    model, params_a, params_b = deploy_lm
    ckpt = str(tmp_path / "ckpts")
    os.makedirs(ckpt)
    _save_candidate(ckpt, 2, params_b)
    restored, reasons, _ = deploylib.gate_candidate(
        ckpt, 2, expected_signature=deploylib.tree_signature(params_a)
    )
    assert reasons == []

    eng = InferenceEngine(
        model, params_a, max_slots=2, prefill_chunk=8,
        registry=reglib.MetricsRegistry(),
    )
    sched = ContinuousBatchingScheduler(eng, registry=eng.registry)
    prompt = np.asarray([5, 9, 2, 11, 3], np.int32)
    sched.submit(Request(request_id=0, prompt=prompt, max_new_tokens=6))
    _drain(sched)
    pins = eng.compile_counts()

    eng.install_canary(2, restored)
    eng.promote_canary()
    sched.submit(Request(request_id=1, prompt=prompt, max_new_tokens=6))
    done = _drain(sched)
    assert done[1].version == 2
    assert eng.compile_counts() == pins


# ---------------------------------------------------------------------------
# CheckpointFollower end-to-end (gate -> canary -> promote / rollback)
# ---------------------------------------------------------------------------


def _mk_follower_engine(deploy_lm):
    from distributed_tensorflow_models_tpu.serving.engine import (
        InferenceEngine,
    )

    model, params_a, _ = deploy_lm
    return InferenceEngine(
        model, params_a, max_slots=2, prefill_chunk=8,
        registry=reglib.MetricsRegistry(),
    )


def test_follower_promotes_healthy_candidate(tmp_path, deploy_lm):
    _, _, params_b = deploy_lm
    eng = _mk_follower_engine(deploy_lm)
    ckpt = str(tmp_path / "ckpts")
    wd = str(tmp_path / "serve")
    os.makedirs(ckpt)
    os.makedirs(wd)
    reg = reglib.MetricsRegistry()
    fol = deploylib.CheckpointFollower(
        ckpt, eng, workdir=wd, registry=reg,
        canary_fraction=0.5, canary_warmup=1, promote_after=1,
        rollback_after=1, poll_interval_s=0.0,
        slo_specs=["serve/ttft_s:p50<1.0@60s"],
    )
    assert fol.poll(1.0, 100.0) == []  # nothing to adopt yet
    _save_candidate(ckpt, 3, params_b)
    rows = fol.poll(2.0, 101.0)
    assert [r["event"] for r in rows] == ["canary_start"]
    assert fol.canary_vid == 3 and eng.canary_version == 3
    assert reg.gauge(reglib.SERVE_VERSION_CANARY).value == 3
    # Routing now splits traffic; both versions appear over many rids.
    routed = {fol.route(str(i)) for i in range(64)}
    assert routed == {0, 3}
    # One healthy sample satisfies warmup; next poll evaluates+promotes.
    fol.observe_sample(3, reglib.SERVE_TTFT, 0.05, 2.5)
    rows = fol.poll(3.0, 102.0)
    assert [r["event"] for r in rows] == ["promote"]
    assert eng.version == 3 and eng.canary_version is None
    assert reg.counter(reglib.SERVE_DEPLOY_SWAPS).value == 1
    assert reg.gauge(reglib.SERVE_VERSION_ACTIVE).value == 3
    assert reg.gauge(reglib.SERVE_VERSION_CANARY).value == deploylib.NO_CANARY
    events = deploylib.load_deploy_events(wd)
    assert [e["event"] for e in events] == ["canary_start", "promote"]
    assert events[1]["step"] == 3 and events[1]["from_version"] == 0


def test_follower_rolls_back_breaching_candidate_and_rejects_nan(
    tmp_path, deploy_lm
):
    import jax

    _, params_a, params_b = deploy_lm
    eng = _mk_follower_engine(deploy_lm)
    ckpt = str(tmp_path / "ckpts")
    wd = str(tmp_path / "serve")
    os.makedirs(ckpt)
    os.makedirs(wd)
    reg = reglib.MetricsRegistry()
    fol = deploylib.CheckpointFollower(
        ckpt, eng, workdir=wd, registry=reg,
        canary_warmup=1, promote_after=1, rollback_after=1,
        poll_interval_s=0.0, reject_after_polls=2,
        slo_specs=["serve/ttft_s:p50<0.1@60s"],
    )
    # NaN-poisoned candidate: rejected before touching the engine.
    poisoned = jax.tree_util.tree_map(
        lambda x: np.asarray(x).astype(np.float32) * np.nan, params_a
    )
    _save_candidate(ckpt, 2, poisoned)
    rows = fol.poll(1.0, 100.0)
    assert [r["event"] for r in rows] == ["reject"]
    assert rows[0]["step"] == 2
    assert any("non-finite" in r for r in rows[0]["reasons"])
    assert eng.canary_version is None and eng.version == 0
    assert reg.counter(reglib.SERVE_DEPLOY_REJECTED).value == 1
    flights = [f for f in os.listdir(wd) if f.startswith("flight_deploy_")]
    assert flights  # forensics for the reject landed on disk

    # Healthy-looking save that breaches its SLO once serving: canary
    # starts, one slow sample satisfies warmup AND breaches, rollback.
    _save_candidate(ckpt, 5, params_b)
    rows = fol.poll(2.0, 101.0)
    assert [r["event"] for r in rows] == ["canary_start"]
    fol.observe_sample(5, reglib.SERVE_TTFT, 3.0, 2.5)  # >> 0.1s p50
    rows = fol.poll(3.0, 102.0)
    assert [r["event"] for r in rows] == ["rollback"]
    assert rows[0]["keep_version"] == 0 and rows[0]["breached"]
    assert eng.version == 0 and eng.canary_version is None
    assert reg.counter(reglib.SERVE_DEPLOY_ROLLBACKS).value == 1
    assert reg.gauge(reglib.SERVE_VERSION_ACTIVE).value == 0
    # A rejected/rolled-back step is terminal: never re-examined.
    assert fol.poll(4.0, 103.0) == []
    events = [e["event"] for e in deploylib.load_deploy_events(wd)]
    assert events == ["reject", "canary_start", "rollback"]


def test_follower_retries_torn_step_then_rejects(tmp_path, deploy_lm):
    eng = _mk_follower_engine(deploy_lm)
    ckpt = str(tmp_path / "ckpts")
    wd = str(tmp_path / "serve")
    os.makedirs(ckpt)
    os.makedirs(wd)
    fol = deploylib.CheckpointFollower(
        ckpt, eng, workdir=wd, registry=reglib.MetricsRegistry(),
        poll_interval_s=0.0, reject_after_polls=3,
    )
    _fake_step(ckpt, 4, torn="state/manifest.ocdbt")
    # Structural failures look like a save still landing: retried.
    assert fol.poll(1.0, 100.0) == []
    assert fol.poll(2.0, 101.0) == []
    rows = fol.poll(3.0, 102.0)  # third strike: rejected for good
    assert [r["event"] for r in rows] == ["reject"]
    assert any(r.startswith("fsck:") for r in rows[0]["reasons"])
    assert eng.canary_version is None
    assert fol.poll(4.0, 103.0) == []  # terminal
