"""What PR 30 added to the benchmark beside the reference: the
``kimi_linear`` configuration file against the catalog row it was cut
from, its analytic FLOPs against a count by hand, the two cores'
operations and bytes, and the readers of the new scopes and of the
held-share statistic on the recorded v5e trace."""

import json
import os

import pytest

import bench_testlib
from benchmark.lib import cells, named_scopes
from benchmark.lib import trace_reduce as tr

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = (
    "linear_attn_device_ms.tokens", "kda_core_device_ms.tokens", "kda_core_roofline_share.tokens",
    "moe_held_share.tokens", "mla_core_roofline_share.tokens",
)

# The toy program's instructions (tests/benchmark/data/toy_v5e.xplane.pb:
# four runs of jit_step), named as a step of this configuration names them.
TOY_MAP = {
    "jit_step": {
        "convert_reduce_fusion": "jit(step)/jvp(TransformerLM)/blocks_1/blocks_1._mix/linear_attn/kda_core/dot_general",
        "fusion": "jit(step)/transpose(jvp(TransformerLM))/blocks_1/blocks_1._mix/linear_attn/query/dot_general",
        "broadcast_subtract_fusion": "jit(step)/optimizer/sub",
        "copy-done": "jit(step)/jvp(TransformerLM)/blocks_3/blocks_3._mix/attn/attention_core/pallas_call",
    }
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "kimi_linear.json")) as f:
        return json.load(f)


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


def test_published_is_the_catalog_row_and_only_three_keys_differ(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert config["published"] == row["config"]
    assert config["source"].startswith(row["source_url"])
    entry = next(c for c in bench_testlib.read_bench()["configs"] if c["name"] == "kimi_linear")
    assert entry["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(entry["reduced"]) == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert (config["num_hidden_layers"], config["num_experts"], config["vocab_size"]) == (5, 8, 20480)
    assert len(config["reduced"]) == 3 and all(k in " ".join(config["reduced"]) for k in differ)
    for key in ("stands_for", "assumed", "departures"):
        assert config[key]


def test_the_program_runs_the_published_widths(config):
    mk, pub = config["overrides"]["model_kwargs"], config["published"]
    lin = pub["linear_attn_config"]
    assert (mk["d_model"], mk["num_heads"], mk["dense_d_ff"], mk["d_ff"]) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["intermediate_size"], pub["moe_intermediate_size"])
    assert (mk["kda_num_heads"], mk["kda_head_dim"], mk["kda_conv_size"]) == (
        lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"])
    assert (mk["mla_kv_lora_rank"], mk["mla_nope_dim"], mk["mla_rope_dim"], mk["mla_v_dim"]) == (
        pub["kv_lora_rank"], pub["qk_nope_head_dim"], pub["qk_rope_head_dim"], pub["v_head_dim"])
    # The router keeps its published outputs, top-8, scale and shared expert.
    assert (mk["num_experts"], mk["moe_top_k"], mk["moe_routed_scale"], mk["moe_shared_experts"]) == (
        pub["num_experts"], pub["num_experts_per_token"], pub["routed_scaling_factor"], pub["num_shared_experts"])
    assert (mk["moe_scoring"], mk["moe_renormalize"], mk["norm_eps"]) == (
        pub["moe_router_activation_func"], pub["moe_renormalize"], pub["rms_norm_eps"])
    assert mk["moe_held"] == [0, config["num_experts"]] and mk["moe_first_dense"] == pub["first_k_dense_replace"]
    assert mk["vocab_size"] == config["vocab_size"] and mk["num_layers"] == config["num_hidden_layers"]
    # Layers 1 to 5 of the published pattern, by kind.
    kinds = ["mla" if i in lin["full_attn_layers"] else "kda" for i in range(1, 6)]
    assert mk["layer_mixers"] == kinds and all(i in lin["kda_layers"] for i in (1, 2, 3, 5))
    assert mk["pos_encoding"] == "none" and pub["mla_use_nope"] is True and mk["use_bias"] is False
    assert abs(config["parameters"]["count"] / 602.4e6 - 1) < 0.02


def test_kimi_linear_flops_hand_counted(config):
    m = cells.load_module("flops", "kimi_linear")
    kw = config["flops_per_item"]["kwargs"]
    kda = 4 * 2304 * 4096 + 2 * (2304 * 128 + 128 * 4096) + 2304 * 32 + 3 * 32 * 128 * 128
    mla = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304 + 32 * 8192 * 320
    expert = 3 * 2304 * 1024
    experts = 2304 * 256 + expert + 8 * 8 / 256 * expert
    assert (kda, mla, expert) == (41_033_728, 113_000_448, 7_077_888)
    macs = 4 * kda + mla + 3 * 2304 * 9216 + 4 * experts + 2304 * 20480
    assert m.forward_macs_per_token(**kw) == macs == 425_771_008
    assert m.flops_per_item(**kw) == 6 * macs
    assert cells.flops_per_item(config) == 6 * macs
    # Everything held and no cut: the published model's active parameters a token.
    full = dict(kw, kda_layers=20, mla_layers=7, held=256, vocab_size=163840)
    assert m.forward_macs_per_token(**full) == (
        20 * kda + 7 * mla + 3 * 2304 * 9216 + 26 * (2304 * 256 + 9 * expert) + 2304 * 163840
    )


def test_the_cores_operations_and_bytes(config):
    m = cells.load_module("flops", "kimi_linear")
    kda = m.kda_core_per_step(tokens=16384, **config["kda_core"]["kwargs"])
    # A chunk and head: 10 blocks of 16 x 16 x 128 twice, T [rhs] 64 x 64 x 256,
    # three 64 x 128 x 128 and one 64 x 64 x 128, in MACs.
    per_chunk = 2 * 10 * 16 * 16 * 128 + 64 * 64 * 256 + 3 * 64 * 128 * 128 + 64 * 64 * 128
    assert per_chunk == 5_373_952
    assert kda["flops"] == 6 * per_chunk * (16384 / 64) * 32 * 4
    assert kda["bytes"] == 3 * (4 * 128 * 2 + 128 * 4 + 4) * 16384 * 32 * 4
    mla = m.mla_core_per_step(tokens=16384, **config["mla_core"]["kwargs"])
    assert mla["flops"] == 6 * 4096 * 320 * 16384 * 32
    assert mla["bytes"] == 3 * 640 * 2 * 16384 * 32
    # On a v5e the bytes bind the delta rule (11.8 ms against 5.4) and the
    # operations the attention (20.9 ms against 2.5).
    assert kda["bytes"] / 819e9 > kda["flops"] / 197e12
    assert mla["flops"] / 197e12 > mla["bytes"] / 819e9


def test_the_cell_lists_the_new_metrics_and_the_token_metrics_that_apply():
    bench = bench_testlib.read_bench()
    cell = cells.load_cell("kimi_linear_train")
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    for metric in bench["per_layer"]:
        if metric["name"] in NEW:
            assert metric["workloads"] == ["kimi_linear_train"] and metric["moves"] == "train_tokens_per_s"
    # Every token metric olmoe_train reports, but the share that reckons
    # every expert's rows from the token count.
    olmoe = {m["name"] for m in cells.load_cell("olmoe_train").per_layer}
    assert olmoe - names == {"moe_experts_roofline_share.tokens"}
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.runner == "train_fit"
    assert cell.traffic["fit"]["per_chip_batch"] * cell.config["overrides"]["num_steps"] == 16384


def test_the_readers_of_the_new_scopes(toy_planes, monkeypatch, config):
    table = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    monkeypatch.setattr(named_scopes, "table", lambda ctx: table)
    secs = _seconds_by_instruction(toy_planes)
    ctx = {
        "trace": {"steps": 4}, "config": config, "device_kind": "TPU v5 lite",
        "items_per_step": 16384, "chips": 1,
    }
    read = lambda name: cells.load_module("layer_metrics", name).read(ctx)
    core_ms = 1e3 * secs["convert_reduce_fusion"] / 4
    assert read("kda_core_device_ms") == pytest.approx(core_ms)
    assert read("linear_attn_device_ms") == pytest.approx(core_ms + 1e3 * secs["fusion"] / 4)
    # 9.689e9 bytes at 819e9 a second are 11.83 ms (operations: 5.36 ms).
    assert read("kda_core_roofline_share") == pytest.approx(100.0 * 11.8301 / core_ms, rel=1e-4)
    # 4.123e12 operations at 197e12 a second are 20.93 ms.
    attention_ms = 1e3 * secs["copy-done"] / 4
    assert read("mla_core_roofline_share") == pytest.approx(100.0 * 20.9298 / attention_ms, rel=1e-4)
    # A configuration without the counts (olmoe's file): its attention is no MLA core.
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "olmoe.json")) as f:
        ctx["config"] = json.load(f)
    assert read("mla_core_roofline_share") is None and read("kda_core_roofline_share") is None
    ctx["config"] = config
    # The parent's program has no such scope, and the line leaves the metrics out.
    monkeypatch.setattr(named_scopes, "table", lambda ctx: {"jit(s)/jvp(M)/mlp/dot_general": 1.0})
    for name in ("linear_attn_device_ms", "kda_core_device_ms", "kda_core_roofline_share", "mla_core_roofline_share"):
        assert read(name) is None
    monkeypatch.setattr(named_scopes, "table", lambda ctx: None)
    assert read("kda_core_device_ms") is None and read("kda_core_roofline_share") is None
    for empty in ({}, {"trace": None}, {"trace": {"steps": 0}}):
        assert cells.load_module("layer_metrics", "kda_core_roofline_share").read(empty) is None


@pytest.mark.parametrize(
    "rows, want",
    [
        ([{"moe_held_share": 0.03, "interval_steps": 10},
          {"moe_held_share": 0.05, "interval_steps": 30}], 4.5),
        ([{"moe_load_max_over_mean": 2.0, "interval_steps": 10}], None),
        ([], None),
    ],
    ids=["weighted_by_steps_in_percent", "every_expert_held", "no_rows"],
)
def test_held_share_is_the_window_rows_mean(rows, want):
    read = cells.load_module("layer_metrics", "moe_held_share").read
    got = read({"window_rows": rows})
    assert got == (want if want is None else pytest.approx(want))
    assert read({}) is None


def test_the_parent_cannot_run_the_cell_and_says_so_at_once(tmp_path, capsys, monkeypatch):
    """``run.py`` on a checkout whose BENCHMARK.json lacks the cell (the
    parent's): exit 2 with the unknown-cell error, before jax is asked
    for a device."""
    import functools

    from benchmark import run as runlib

    bench = bench_testlib.read_bench()
    bench["workloads"] = [w for w in bench["workloads"] if w["name"] != "kimi_linear_train"]
    checkout = bench_testlib.checkout_with(tmp_path, bench)
    monkeypatch.setattr(cells, "load_cell", functools.partial(cells.load_cell, repo_dir=checkout))
    monkeypatch.setenv("DTM_DATA_DIR", os.environ.get("DTM_DATA_DIR", ""))
    monkeypatch.setattr("sys.path", list(__import__("sys").path))
    assert runlib.main(["--workload", "kimi_linear_train", "--seed", "1", "--seconds", "1"]) == 2
    captured = capsys.readouterr()
    assert "no workload 'kimi_linear_train'" in captured.err and captured.out == ""
