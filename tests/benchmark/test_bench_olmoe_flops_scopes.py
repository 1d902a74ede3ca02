"""What PR 25 added to the benchmark beside the reference: the analytic
FLOPs of the ``olmoe`` configuration against a count by hand and against
the program's own, the expert products' operations and bytes, the
readers of the new scopes (``benchmark/lib/named_scopes.py``) on the
recorded v5e trace with a scope map of its own, and the routing
statistic's reader."""

import json
import os

import pytest

import bench_testlib
from benchmark.lib import cells, named_scopes
from benchmark.lib import trace_reduce as tr

# The toy program's instructions (tests/benchmark/data/toy_v5e.xplane.pb:
# four runs of jit_step), named as a step with an expert layer would
# name them.  The two copy-starts and copy-done.1 are left out: XLA's own.
TOY_MAP = {
    "jit_step": {
        "convert_reduce_fusion": "jit(step)/jvp(TransformerLM)/blocks_0/moe/moe_experts/ragged_dot_general",
        "fusion": "jit(step)/transpose(jvp(TransformerLM))/blocks_0/moe/moe_dispatch/gather",
        "broadcast_subtract_fusion": "jit(step)/optimizer/sub",
        "copy-done": "jit(step)/jvp(TransformerLM)/blocks_0/attn/attention_core/while",
    }
}


@pytest.fixture(scope="module")
def toy_planes():
    from jax.profiler import ProfileData

    path = os.path.join(bench_testlib.DATA, "toy_v5e.xplane.pb")
    return tr.read_planes(ProfileData.from_file(path))


def _seconds_by_instruction(planes):
    names, spans = planes["devices"][0][tr.OPS_LINE]
    out = {}
    for n, (s, e) in zip(names, spans):
        out[tr.op_name(n)] = out.get(tr.op_name(n), 0.0) + (e - s)
    return out


def test_olmoe_flops_hand_counted():
    m = cells.load_module("flops", "olmoe")
    attention = 4 * 2048**2 + 2 * 4096 * 2048
    experts = 8 * 3 * 2048 * 1024
    router = 2048 * 64
    assert (attention, experts, router) == (33_554_432, 50_331_648, 131_072)
    macs = attention + experts + router + 2048 * 50304
    assert macs == 187_039_744
    assert m.forward_macs_per_token(1, 2048, 1024, 64, 8, 50304, 4096) == macs
    assert m.flops_per_item(1, 2048, 1024, 64, 8, 50304, 4096) == 6 * macs
    # The published depth: 16 layers, one head.
    assert m.forward_macs_per_token(16, 2048, 1024, 64, 8, 50304, 4096) == (
        16 * (attention + experts + router) + 2048 * 50304
    )


def test_config_file_names_its_flops_and_its_expert_products():
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "olmoe.json")) as f:
        config = json.load(f)
    assert 1.12e9 < cells.flops_per_item(config) < 1.125e9
    spec = config["expert_products"]
    need = cells.load_module("flops", spec["function"]).expert_products_per_step(
        tokens=4 * 4096, **spec["kwargs"]
    )
    rows = 8 * 4 * 4096
    # Three passes of three products; rows in and out and the three
    # intermediates, and the weight stacks, in bf16, once per pass.
    assert need["flops"] == 3 * 2 * rows * 3 * 2048 * 1024 == 4_947_802_324_992
    assert need["bytes"] == 3 * 2 * (rows * (2 * 2048 + 3 * 1024) + 64 * 3 * 2048 * 1024)
    # Compute-bound on a v5e by these counts: operations take longer than bytes.
    assert need["flops"] / 197e12 > 2 * need["bytes"] / 819e9


def test_no_width_differs_from_the_published_configuration():
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "olmoe.json")) as f:
        config = json.load(f)
    published, kw = config["published"], config["overrides"]["model_kwargs"]
    for key, value in published.items():
        if key != "num_hidden_layers":
            assert config[key] == value, key
    assert (config["num_hidden_layers"], published["num_hidden_layers"]) == (1, 16)
    assert kw["d_model"] == published["hidden_size"]
    assert kw["d_ff"] == published["intermediate_size"]
    assert kw["num_heads"] == published["num_attention_heads"] == published["num_key_value_heads"]
    assert kw["num_experts"] == published["num_experts"]
    assert kw["moe_top_k"] == published["num_experts_per_tok"]
    assert kw["vocab_size"] == config["overrides"]["vocab_size"] == published["vocab_size"]
    assert kw["max_len"] == config["overrides"]["num_steps"] == published["max_position_embeddings"]
    assert kw["norm_eps"] == published["rms_norm_eps"] and kw["rope_theta"] == published["rope_theta"]
    assert kw["use_bias"] is published["attention_bias"] is False
    assert kw["num_layers"] == config["num_hidden_layers"]
    assert kw["dropout_rate"] == 0.0 and kw["moe_z_loss_weight"] == 0.001


def test_analytic_flops_against_the_program_s_own_count(tmp_path):
    """``train/flops_per_step`` of the program at the small size.  Off
    the TPU the grouped products run in Pallas' interpret mode, whole
    512-row tiles at a time, so the program's count there is far above
    the model's and only bounds it from above: the analytic count (the
    ``top_k`` active experts) may not exceed it.  On the chip the two
    are printed side by side in every run's notes: 1.245e9 against
    1.122e9 a token, 11% apart, the head's recomputed forward (PERF.md
    section 4)."""
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    cell = cells.load_cell("olmoe_train", rehearse=True)
    over = cell.config["overrides"]
    mk = over["model_kwargs"]
    cfg = get_config(
        cell.config["program_config"], **over, global_batch_size=8,
        train_steps=2, log_every_steps=1,
    )
    trainlib.fit(cfg, str(tmp_path))
    with open(tmp_path / "telemetry.json") as f:
        program = json.load(f)["metrics"]["train/flops_per_step"] / (8 * over["num_steps"])
    analytic = cells.load_module("flops", "olmoe").flops_per_item(
        mk["num_layers"], mk["d_model"], mk["d_ff"], mk["num_experts"], mk["moe_top_k"],
        mk["vocab_size"], over["num_steps"],
    )
    assert 0 < analytic < program
    # The configuration's own arguments name the same function the cell's mfu reads.
    assert cell.config["flops_per_item"]["function"] == "olmoe"


def test_a_scope_s_time_is_the_time_of_the_instructions_under_it(toy_planes, monkeypatch):
    secs = _seconds_by_instruction(toy_planes)
    table = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    unmapped = secs["copy-start"] + secs["copy-start.1"] + secs["copy-done.1"]
    assert table[""] == pytest.approx(unmapped)
    assert sum(table.values()) == pytest.approx(sum(secs.values()))
    assert sum(table.values()) == pytest.approx(tr.reduce_planes(toy_planes)["busy_s_chip0"])
    monkeypatch.setattr(named_scopes, "table", lambda ctx: table)
    ctx = {"trace": {"steps": 4}}
    per_step = lambda *names: pytest.approx(1e3 * sum(secs[n] for n in names) / 4)
    assert named_scopes.ms_per_step(ctx, "moe") == per_step("convert_reduce_fusion", "fusion")
    assert named_scopes.ms_per_step(ctx, "moe_experts") == per_step("convert_reduce_fusion")
    assert named_scopes.ms_per_step(ctx, "moe_dispatch") == per_step("fusion")
    assert named_scopes.ms_per_step(ctx, "attention_core") == per_step("copy-done")
    assert named_scopes.ms_per_step(ctx, "optimizer") == per_step("broadcast_subtract_fusion")
    # A scope the program does not have, and a run without a trace: nothing to read.
    assert named_scopes.ms_per_step(ctx, "moe_exchange") is None
    for empty in ({}, {"trace": None}, {"trace": {"steps": 0}}):
        assert named_scopes.ms_per_step(empty, "moe") is None


def test_the_readers_of_the_new_entries(toy_planes, monkeypatch):
    table = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    monkeypatch.setattr(named_scopes, "table", lambda ctx: table)
    secs = _seconds_by_instruction(toy_planes)
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "olmoe.json")) as f:
        config = json.load(f)
    ctx = {
        "trace": {"steps": 4}, "config": config, "device_kind": "TPU v5 lite",
        "items_per_step": 4 * 4096, "chips": 1,
    }
    read = lambda name: cells.load_module("layer_metrics", name).read(ctx)
    experts_ms = 1e3 * secs["convert_reduce_fusion"] / 4
    assert read("moe_experts_device_ms") == pytest.approx(experts_ms)
    assert read("moe_device_ms") == pytest.approx(read("moe_experts_device_ms") + read("moe_dispatch_device_ms"))
    # 4.948e12 operations at 197e12 a second are 25.1 ms (bytes: 9.8 ms).
    assert read("moe_experts_roofline_share") == pytest.approx(100.0 * 25.1158 / experts_ms, rel=1e-4)
    # The parent's program has no such scope, and the line leaves the metric out.
    monkeypatch.setattr(named_scopes, "table", lambda ctx: {"jit(s)/jvp(M)/mlp/dot_general": 1.0})
    for name in ("moe_device_ms", "moe_experts_device_ms", "moe_dispatch_device_ms", "moe_experts_roofline_share"):
        assert read(name) is None
    monkeypatch.setattr(named_scopes, "table", lambda ctx: None)
    assert read("moe_device_ms") is None and read("moe_experts_roofline_share") is None


def test_a_map_for_another_module_or_no_device_yields_none(toy_planes):
    assert named_scopes.seconds_by_op_name(toy_planes, {"jit_other": TOY_MAP["jit_step"]}) is None
    assert named_scopes.seconds_by_op_name(toy_planes, {}) is None
    assert named_scopes.seconds_by_op_name({"devices": {}, "host": []}, TOY_MAP) is None
    # From the third run on: the clip the marker gives in a real run.
    _, spans = toy_planes["devices"][0][tr.MODULES_LINE]
    late = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP, start_s=float(spans[2][0]))
    whole = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    assert sum(late.values()) == pytest.approx(sum(whole.values()) / 2, rel=0.02)


def test_read_run_reads_the_files_and_agrees_with_scoped_trace(tmp_path):
    from benchmark.lib import scoped_trace

    scopes = tmp_path / "step_scopes_p0.json"
    xplane = os.path.join(bench_testlib.DATA, "toy_v5e.xplane.pb")
    scopes.write_text(json.dumps({"version": 1, "modules": TOY_MAP}))
    table = named_scopes.read_run(xplane, str(scopes))
    fixed = scoped_trace.read_run(xplane, str(scopes))
    # The same rules: the scope the old reader knows reads the same.
    assert named_scopes.scope_seconds(table, "attention_core") == pytest.approx(
        fixed["seconds"]["attention_core"]
    )
    assert sum(table.values()) == pytest.approx(fixed["module_s"])


@pytest.mark.parametrize(
    "rows, want",
    [
        ([{"moe_load_max_over_mean": 2.0, "interval_steps": 10},
          {"moe_load_max_over_mean": 3.0, "interval_steps": 30}], 2.75),
        ([{"loss": 1.0, "interval_steps": 10}], None),
        ([], None),
    ],
    ids=["weighted_by_steps", "a_program_without_the_row", "no_rows"],
)
def test_routing_statistic_is_the_window_rows_mean(rows, want):
    read = cells.load_module("layer_metrics", "moe_load_max_over_mean").read
    assert read({"window_rows": rows}) == want
    assert read({}) is None
