"""The join of a device trace with the program's scope map
(benchmark/lib/scoped_trace.py), on the recorded v5e trace and a
hand-written map for its instruction names."""

import os

import pytest

import bench_testlib
from benchmark.lib import scoped_trace as st
from benchmark.lib import trace_reduce as tr

# The toy program's instructions (tests/benchmark/data/toy_v5e.xplane.pb:
# four runs of jit_step), as a program with scopes would have named them.
# The two copy-starts and copy-done.1 are left out: XLA's own.
TOY_MAP = {
    "jit_step": {
        "convert_reduce_fusion": "jit(step)/jvp(Net)/blocks_0/attn/attention_core/reduce_sum",
        "fusion": "jit(step)/transpose(jvp(unembed_loss))/jvp(unembed_loss)/checkpoint/mul",
        "broadcast_subtract_fusion": "jit(step)/optimizer/sub",
        "copy-done": "jit(step)/jit(_threefry_fold_in)/per_step_rngs/add",
    }
}


# What the program's map would say of the toy's fusions: the first holds
# the gradient norm's partial sum beside forward work, the second a
# recomputed forward piece (no mixture: that is backward work anyway),
# the third is all optimizer.
TOY_FUSED = {
    "jit_step": {
        "names": [
            "jit(step)/jvp(Net)/blocks_0/attn/attention_core/reduce_sum",
            "jit(step)/optimizer/reduce_sum",
            "jit(step)/transpose(jvp(unembed_loss))/jvp(unembed_loss)/checkpoint/mul",
            "jit(step)/jvp(unembed_loss)/exp",
            "jit(step)/optimizer/sub",
            "jit(step)/optimizer/mul",
        ],
        "inside": {
            "convert_reduce_fusion": [0, 1],
            "fusion": [2, 3],
            "broadcast_subtract_fusion": [4, 5],
        },
    }
}


@pytest.fixture(scope="module")
def toy_planes():
    from jax.profiler import ProfileData

    path = os.path.join(bench_testlib.DATA, "toy_v5e.xplane.pb")
    return tr.read_planes(ProfileData.from_file(path))


def _seconds_by_instruction(planes):
    """No instruction of the toy nests in another: durations are self times."""
    names, spans = planes["devices"][0][tr.OPS_LINE]
    out = {}
    for n, (s, e) in zip(names, spans):
        out[tr.op_name(n)] = out.get(tr.op_name(n), 0.0) + (e - s)
    return out


@pytest.mark.parametrize(
    "op_name, want",
    [
        ("jit(one_step)/while/body/closed_call/optimizer/mul", "optimizer"),
        ("jit(s)/transpose(jvp(TransformerLM))/blocks_1/mlp/up/dot_general", "bwd"),
        ("jit(s)/transpose(jvp(unembed_loss))/jvp(unembed_loss)/checkpoint/exp", "bwd"),
        ("jit(s)/jvp(TransformerLM)/blocks_0/attn/attention_core/div", "fwd"),
        ("jit(s)/jvp(ResNet)/optimizer_state_reader/add", "fwd"),
        ("jit(s)/jit(_threefry_fold_in)/per_step_rngs/xor", "other"),
        ("", "other"),
        (None, "other"),
    ],
)
def test_classes(op_name, want):
    assert st.classify(op_name) == want


def test_a_scope_is_a_whole_path_element():
    assert st.in_scope("jit(s)/jvp(M)/attn/attention_core/div", "attention_core")
    assert st.in_scope("jit(s)/transpose(jvp(unembed_loss))/mul", "unembed_loss")
    assert st.in_scope("unembed_loss", "unembed_loss")
    assert not st.in_scope("jit(s)/jvp(M)/my_attention_core/div", "attention_core")
    assert not st.in_scope("jit(s)/jvp(M)/attention_core_2/div", "attention_core")


def test_class_sums_are_chip_0s_busy_time(toy_planes):
    got = st.reduce_scoped(toy_planes, TOY_MAP)
    secs = _seconds_by_instruction(toy_planes)
    assert got["module_runs"] == 4
    # Mapped under no transform, and not mapped at all.
    unmapped = secs["copy-start"] + secs["copy-start.1"] + secs["copy-done.1"]
    assert got["seconds"] == {
        "fwd": pytest.approx(secs["convert_reduce_fusion"]),
        "bwd": pytest.approx(secs["fusion"]),
        "optimizer": pytest.approx(secs["broadcast_subtract_fusion"]),
        "other": pytest.approx(secs["copy-done"] + unmapped),
        "attention_core": pytest.approx(secs["convert_reduce_fusion"]),
        "unembed_loss": pytest.approx(secs["fusion"]),
        "optimizer_mixed": 0.0,
    }
    assert got["outside_s"] == 0.0
    busy = tr.reduce_planes(toy_planes)["busy_s_chip0"]
    assert sum(got["seconds"][c] for c in st.CLASSES) == pytest.approx(busy, rel=1e-9)
    assert got["busy_s_chip0"] == pytest.approx(busy, rel=1e-9)
    assert got["module_s"] == pytest.approx(sum(secs.values()))


def test_kernels_that_mix_the_optimizer_with_the_model(toy_planes, monkeypatch):
    assert st.mixed_fusions(TOY_FUSED["jit_step"]) == {"convert_reduce_fusion"}
    assert st.mixed_fusions(None) == set() == st.mixed_fusions({"names": [], "inside": {}})
    got = st.reduce_scoped(toy_planes, TOY_MAP, fused=TOY_FUSED)
    secs = _seconds_by_instruction(toy_planes)
    # Counted beside its class, not instead of it.
    assert got["seconds"]["optimizer_mixed"] == pytest.approx(secs["convert_reduce_fusion"])
    plain = st.reduce_scoped(toy_planes, TOY_MAP)["seconds"]
    assert {**got["seconds"], "optimizer_mixed": 0.0} == plain
    monkeypatch.setattr(st, "summary", lambda ctx: got)
    assert st.ms_per_step({"trace": {"steps": 4}}, "optimizer_mixed") == pytest.approx(
        1e3 * secs["convert_reduce_fusion"] / 4
    )
    assert st.ms_per_step({}, "optimizer_mixed") is None


def test_coverage_is_what_the_map_implies(toy_planes, monkeypatch):
    secs = _seconds_by_instruction(toy_planes)
    named = secs["convert_reduce_fusion"] + secs["fusion"] + secs["broadcast_subtract_fusion"]
    monkeypatch.setattr(st, "summary", lambda ctx: st.reduce_scoped(toy_planes, TOY_MAP))
    ctx = {"trace": {"steps": 4}}
    assert st.coverage_percent(ctx) == pytest.approx(100.0 * named / sum(secs.values()))
    assert st.ms_per_step(ctx, "fwd") == pytest.approx(1e3 * secs["convert_reduce_fusion"] / 4)
    assert st.ms_per_step(ctx, "unembed_loss") == pytest.approx(1e3 * secs["fusion"] / 4)
    # An empty map names nothing: a stale or missing map shows as coverage.
    monkeypatch.setattr(st, "summary", lambda ctx: st.reduce_scoped(toy_planes, {"jit_step": {}}))
    assert st.coverage_percent(ctx) == 0.0
    assert st.ms_per_step(ctx, "other") == pytest.approx(1e3 * sum(secs.values()) / 4)
    # No trace in the context (an untraced run, a rehearsal): nothing to read.
    for empty in ({}, {"trace": None}, {"trace": {"steps": 0}}):
        assert st.coverage_percent(empty) is None
        assert st.ms_per_step(empty, "fwd") is None
        assert st.ms_per_step(empty, "attention_core") is None


def test_a_map_for_another_module_yields_none(toy_planes):
    assert st.reduce_scoped(toy_planes, {"jit_other_step": TOY_MAP["jit_step"]}) is None
    assert st.reduce_scoped(toy_planes, {}) is None
    assert st.reduce_scoped({"devices": {}, "host": []}, TOY_MAP) is None


def test_instructions_outside_a_mapped_module_are_set_apart(toy_planes):
    """From the third run on: the clip the marker gives in a real run."""
    names, spans = toy_planes["devices"][0][tr.MODULES_LINE]
    third = float(spans[2][0])
    late = st.reduce_scoped(toy_planes, TOY_MAP, start_s=third)
    whole = st.reduce_scoped(toy_planes, TOY_MAP)
    assert late["module_runs"] == 2
    assert late["module_s"] == pytest.approx(whole["module_s"] / 2, rel=0.02)
    # With the modules line cut off from the second of them, the last
    # run's instructions belong to no module of the map.
    planes = {"devices": {0: dict(toy_planes["devices"][0])}, "host": []}
    planes["devices"][0][tr.MODULES_LINE] = (names[:3], spans[:3])
    cut = st.reduce_scoped(planes, TOY_MAP)
    assert cut["outside_s"] == pytest.approx(whole["module_s"] / 4, rel=0.02)
    assert cut["outside_s"] + cut["module_s"] == pytest.approx(whole["busy_s_chip0"])


def test_read_run_reads_the_files(tmp_path):
    import json

    scopes = tmp_path / "step_scopes_p0.json"
    xplane = os.path.join(bench_testlib.DATA, "toy_v5e.xplane.pb")
    scopes.write_text(json.dumps({"version": 1, "modules": TOY_MAP}))
    got = st.read_run(xplane, str(scopes))
    # The toy's marker has another name: nothing is clipped.
    assert got["module_runs"] == 4 and got["seconds"]["optimizer"] > 0
    assert got["seconds"]["optimizer_mixed"] == 0.0  # a map without the table
    scopes.write_text(json.dumps({"version": 1, "modules": TOY_MAP, "fused": TOY_FUSED}))
    assert st.read_run(xplane, str(scopes))["seconds"]["optimizer_mixed"] > 0
