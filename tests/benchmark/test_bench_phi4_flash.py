"""What PR 44 added to the benchmark beside the reference: the
``phi4_mini_flash`` configuration file against the catalog row it was cut
from, its analytic FLOPs against a count by hand, the two new kernels'
operations and bytes, the cell's lists of metrics, the readers of the
three new scopes on the recorded v5e trace, the rehearsal, and that the
parent cannot run the cell and says so at once.  Listings are held in the
form that stays true when a cell is appended ("after", never "last")."""

import functools
import json
import math
import os

import pytest

import bench_testlib
from benchmark.lib import cells, named_scopes
from benchmark.lib import trace_reduce as tr

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "phi4_flash_train"
CONFIG = "phi4_mini_flash"
TRAFFIC = "fit_lm_1x8192_phi4flash"
NEW = ("sscan_core_device_ms.tokens", "sscan_core_roofline_share.tokens", "swa_core_device_ms.tokens",
       "swa_core_roofline_share.tokens", "gmu_device_ms.tokens")
REDUCED = ["num_hidden_layers", "vocab_size"]
KINDS = ["mamba1", "attention", "mamba1", "attention_full", "gmu", "cross"]
IDS = [0, 1, 16, 17, 18, 19]

# The toy program's instructions (tests/benchmark/data/toy_v5e.xplane.pb:
# four runs of jit_step), named as a step of this configuration names them.
TOY_MAP = {
    "jit_step": {
        "convert_reduce_fusion": "jit(step)/jvp(TransformerLM)/blocks_0/blocks_0._mix/ssm/jit(_kernel_fwd)/sscan_core/pallas_call",
        "fusion": "jit(step)/transpose(jvp(TransformerLM))/blocks_1/blocks_1._mix/attn/attention_core/swa_core/pallas_call",
        "broadcast_subtract_fusion": "jit(step)/optimizer/sub",
        "copy-done": "jit(step)/jvp(TransformerLM)/blocks_4/blocks_4._mix/ssm/gmu/in_proj/dot_general",
    }
}


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", f"{CONFIG}.json")) as f:
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


def _after(names, earlier, later):
    """``later`` are adjacent, in order, somewhere after ``earlier``."""
    at = names.index(later[0])
    return names[at : at + len(later)] == list(later) and all(names.index(n) < at for n in earlier)


def test_published_is_the_catalog_row_and_only_depth_and_vocabulary_differ(config):
    if not os.path.isfile(CATALOG):
        pytest.skip("the model-configs catalog is not on this machine")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Phi-4-mini-flash-reasoning")
    assert config["published"] == row["config"]
    assert config["source"].startswith(row["source_url"])
    entry = next(c for c in bench_testlib.read_bench()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(entry["reduced"]) == REDUCED
    # Depth (six layers: every kind) and the vocabulary (one of 8): the
    # guide's floors; no width, no head.
    assert [config[k] for k in REDUCED] == [6, 25008] and 25008 * 8 == row["config"]["vocab_size"]
    assert not any(k.endswith(("_dim", "_rank")) or "hidden_size" in k or "intermediate" in k or "head" in k
                   for k in entry["reduced"])
    assert len(config["reduced"]) == 2 and all(k in " ".join(config["reduced"]) for k in differ)
    for key in ("stands_for", "assumed", "departures"):
        assert config[key]
    for word in ("0, 1, 16, 17, 18, 19", "rows 0-25007", "11.15 GB", "What the cut distorts", "9 of 32", "8 of 32",
                 "7 of 32", "one reader where the model has seven", "No head is cut", "14.6 GB"):
        assert word in config["stands_for"], word
    for key in ("mamba", "mamba_init", "layers", "attention", "gated_memory_unit", "cross_attention", "norm",
                "mamba1_chunk", "optimizer", "compute_dtype", "recomputation"):
        assert key in config["assumed"]
    assert "If the published modelling code differs, it wins" in config["assumed"]["mamba"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_program_runs_the_published_widths(config):
    mk, pub = config["overrides"]["model_kwargs"], config["published"]
    assert (mk["d_model"], mk["d_ff"], mk["norm_eps"]) == (
        pub["hidden_size"], pub["intermediate_size"], pub["layer_norm_eps"]) == (2560, 10240, 1e-5)
    assert (mk["num_heads"], mk["num_kv_heads"], mk["attn_window"]) == (
        pub["num_attention_heads"], pub["num_key_value_heads"], pub["sliding_window"]) == (40, 20, 512)
    assert mk["d_model"] // mk["num_heads"] == 64 and "head_dim" not in mk
    assert (mk["norm"], mk["mlp"], mk["pos_encoding"], mk["use_bias"], mk["attn_bias"]) == (
        "layernorm", "gated_silu", "none", pub["mlp_bias"], True)
    assert mk["tie_embeddings"] is pub["tie_word_embeddings"] is True and pub["lm_head_bias"] is False
    # Mamba-1 at mamba_ssm's defaults: expand 2, state 16, convolution 4, dt_rank ceil(2560 / 16).
    assert (mk["mamba1_inner"], mk["mamba1_state_dim"], mk["mamba1_conv_size"], mk["mamba1_dt_rank"]) == (
        2 * 2560, 16, 4, math.ceil(2560 / 16))
    assert pub["mb_per_layer"] == 2 and mk["attn_differential"] is True and mk["remat"] is True
    assert mk["vocab_size"] == config["vocab_size"] == pub["vocab_size"] // 8
    assert mk["num_layers"] == config["num_hidden_layers"] == 6
    # Every kind of layer once, lambda_init from the published indices.
    assert mk["layer_mixers"] == KINDS and mk["layer_ids"] == IDS
    assert config["parameters"]["count"] == 697_094_272
    assert config["parameters"]["state_gb_at_16_bytes"] == pytest.approx(697_094_272 * 16 / 1e9, abs=5e-3)
    assert 697_094_272 * 16 > 0.25 * 16e9  # over a quarter of the chip before one activation
    # The program config itself is the uncut model, and the cut's layers are its.
    from distributed_tensorflow_models_tpu.harness.config import get_config

    full = get_config(CONFIG).model_kwargs
    assert (full["num_layers"], full["vocab_size"]) == (32, 200064) and "layer_ids" not in full
    assert [full["layer_mixers"][i] for i in IDS] == KINDS
    same = [k for k in mk if k not in ("vocab_size", "num_layers", "layer_mixers", "layer_ids", "mamba1_chunk")]
    assert all(full[k] == mk[k] for k in same)
    assert config["reference_kwargs"] == {
        "layers": ["mamba", "window", "mamba", "full", "gmu", "cross"], "layer_ids": IDS, "num_heads": 40,
        "num_kv_heads": 20, "window": 512, "eps": 1e-05,
    }


def test_phi4_flash_flops_hand_counted(config):
    m = cells.load_module("flops", "phi4_flash")
    kw = config["flops_per_item"]["kwargs"]
    feed = 3 * 2560 * 10240
    mamba = 2560 * 10240 + 5120 * 192 + 160 * 5120 + 5120 * 2560 + 2 * 5120 * 16
    projections = 2 * 2560 * 2560 + 2 * 2560 * 1280
    core = lambda span: 40 * (64 + 128) * span
    assert (feed, mamba, projections, core(8192), core(512)) == (
        78_643_200, 41_287_680, 19_660_800, 62_914_560, 3_932_160)
    macs = (
        6 * feed + 2 * mamba + (projections + core(512)) + (projections + core(8192))
        + (2 * 2560 * 2560 + core(8192)) + 2 * 2560 * 5120 + 2560 * 25008
    )
    assert m.forward_macs_per_token(**kw) == macs == 826_859_520  # ISSUE 44's "about 827 M"
    assert m.flops_per_item(**kw) == cells.flops_per_item(config) == 6 * macs
    # Every layer, the whole vocabulary, no scores: the published model's
    # parameters in its matrices ("3.8B": 3.34 B beside the embedding's
    # rows, which multiply once, as the tied head).
    full = dict(kw, mamba_layers=9, window_layers=8, full_layers=1, cross_layers=7, gmu_layers=7,
                vocab_size=200064, seq_len=0, window=0)
    assert 3.7e9 < m.forward_macs_per_token(**full) < 3.9e9


def test_the_two_kernels_operations_and_bytes(config):
    m = cells.load_module("flops", "phi4_flash")
    scan = m.sscan_core_per_step(tokens=8192, **config["sscan_core"]["kwargs"])
    # Three multiply-adds a token, channel and state, forward and twice
    # that backward; x, y in bf16, dt in float32, B and C in bf16, three times.
    assert scan["flops"] == 3 * 6 * 5120 * 16 * 8192 * 2
    assert scan["bytes"] == 3 * (2 * 5120 * 2 + 5120 * 4 + 2 * 16 * 2) * 8192 * 2
    # The bytes bind, and neither is the scan's real bound (the vector unit).
    assert scan["bytes"] / 819e9 > 10 * scan["flops"] / 197e12 and 2e-3 < scan["bytes"] / 819e9 < 3e-3
    assert "bytes" in config["sscan_core"]["what"] and "vector unit" in config["sscan_core"]["what"]
    window = m.swa_core_per_step(tokens=8192, **config["swa_core"]["kwargs"])
    seen = sum(min(t + 1, 512) for t in range(8192))
    assert window["flops"] == 6 * 40 * 192 * seen
    assert window["bytes"] == 3 * (40 * 64 + 2 * 20 * 64 + 40 * 128) * 2 * 8192
    assert window["flops"] / 197e12 > window["bytes"] / 819e9  # the operations bind
    assert m.swa_core_per_step(tokens=100, **config["swa_core"]["kwargs"])["flops"] == 6 * 40 * 192 * 5050
    # A full causal layer sees 8.3 times the keys the window layer sees.
    assert 8.2 < (8192 * 8193 / 2) / seen < 8.3


def test_the_cell_lists_the_new_metrics_and_the_token_metrics_that_apply():
    bench = bench_testlib.read_bench()
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        share = "share" in name
        assert by_name[name] == {
            "name": name, "unit": "%" if share else "ms", "better": "higher" if share else "lower",
            "source": "device_trace", "layer": "models and ops", "moves": "train_tokens_per_s", "workloads": [CELL],
        }
        assert callable(cells.load_module("layer_metrics", cells.reader_name(name)).read)
    # Appended, in ISSUE 44's order: after everything an earlier PR listed
    # (later PRs append after them).
    order = [m["name"] for m in bench["per_layer"]]
    assert _after(order, ("ssd_core_roofline_share.tokens", "moe_shared_device_ms.tokens"), NEW)
    # Every token metric gpt2m_train reports, the whole state-space mixer's
    # time (Mamba-1 and the memory unit run under ``ssm``) and its own five.
    gpt2m = {m["name"] for m in cells.load_cell("gpt2m_train").per_layer}
    assert names == gpt2m | {"ssm_device_ms.tokens"} | set(NEW)
    for name in gpt2m | {"ssm_device_ms.tokens"}:
        if "workloads" in by_name[name]:
            assert _after(by_name[name]["workloads"], ("nemotron_h_train",), (CELL,)), name
    assert CELL not in by_name["ssd_core_device_ms.tokens"]["workloads"]  # another scan, another scope
    assert not any(n.startswith(("moe_", "kda_", "gdn_", "mla_", "linear_attn", "ssd_")) for n in names)
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "train_tokens_per_s")
    assert _after(tokens["workloads"], ("granite_h_train", "nemotron_h_train"), (CELL,)) and tokens["bound"] == 0.01
    assert cell.chips == 1 and cell.runner == "train_fit" and cell.traffic_name == TRAFFIC
    fit = cell.traffic["fit"]
    assert fit["per_chip_batch"] * cell.config["overrides"]["num_steps"] == 8192
    # fit_lm_1x8192_ssm's mix to the letter.
    assert fit == cells.load_cell("granite_h_train").traffic["fit"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": TRAFFIC, "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200
    for word in ("1 x 8192", "0, 1, 16-19", "Mamba-1 scan", "window + full differential attention", "memory unit",
                 "cross layer", "no head cut"):
        assert word in entry["why"], word
    # Nothing that was there is gone or moved: the cell and the
    # configuration come after nemotron's.
    cell_names = [w["name"] for w in bench["workloads"]]
    assert _after(cell_names, ("resnet50_train", "gpt2m_train", "resnet50_dp4", "olmoe_train", "kimi_linear_train",
                               "olmo_hybrid_train", "granite_h_train", "nemotron_h_train"), (CELL,))
    config_names = [c["name"] for c in bench["configs"]]
    assert _after(config_names, ("resnet50", "gpt2m", "olmoe", "kimi_linear", "olmo_hybrid", "granite_h_micro",
                                 "nemotron3_nano"), (CONFIG,))
    # Nine cells at least, one of four chips.
    assert len(cell_names) >= 9 and sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_readers_of_the_new_scopes(toy_planes, monkeypatch, config):
    table = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    monkeypatch.setattr(named_scopes, "table", lambda ctx: table)
    secs = _seconds_by_instruction(toy_planes)
    ctx = {
        "trace": {"steps": 4}, "config": config, "device_kind": "TPU v5 lite",
        "items_per_step": 8192, "chips": 1,
    }
    read = lambda name: cells.load_module("layer_metrics", name).read(ctx)
    scan_ms, window_ms = 1e3 * secs["convert_reduce_fusion"] / 4, 1e3 * secs["fusion"] / 4
    gmu_ms = 1e3 * secs["copy-done"] / 4
    assert read("sscan_core_device_ms") == pytest.approx(scan_ms)
    assert read("swa_core_device_ms") == pytest.approx(window_ms)
    assert read("gmu_device_ms") == pytest.approx(gmu_ms)
    assert read("ssm_device_ms") == pytest.approx(scan_ms + gmu_ms)  # both run under the mixers' scope
    assert read("ssd_core_device_ms") is None  # no instruction of this program is under it
    m = cells.load_module("flops", "phi4_flash")
    for scope, measured in (("sscan_core", scan_ms), ("swa_core", window_ms)):
        need = getattr(m, f"{scope}_per_step")(tokens=8192, **config[scope]["kwargs"])
        least_ms = 1e3 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
        assert read(f"{scope}_roofline_share") == pytest.approx(100.0 * least_ms / measured, rel=1e-6)
    # The parent's program has none of the three scopes: there, in a run
    # without a trace and on an empty context every reader leaves its
    # metric out and does not raise.
    monkeypatch.setattr(named_scopes, "table", lambda ctx: {"jit(s)/jvp(M)/ssm/ssd_core/dot_general": 1.0})
    assert [read(cells.reader_name(name)) for name in NEW] == [None] * 5
    monkeypatch.setattr(named_scopes, "table", lambda ctx: None)
    assert [read(cells.reader_name(name)) for name in NEW] == [None] * 5
    for empty in ({}, {"trace": None}, {"trace": {"steps": 0}}):
        for name in NEW:
            assert cells.load_module("layer_metrics", cells.reader_name(name)).read(empty) is None


def test_the_rehearsal_is_the_cell_at_a_small_size():
    """The traffic file's ``rehearse`` block shrinks widths, lengths and
    counts and nothing else: the same six layers of five kinds with their
    published indices, grouped heads in pairs, a window shorter than the
    sequence, and recomputation."""
    real, tiny = cells.load_cell(CELL), cells.load_cell(CELL, rehearse=True)
    big, small = real.config["overrides"]["model_kwargs"], tiny.config["overrides"]["model_kwargs"]
    changed = {k for k in big if big[k] != small[k]}
    assert changed == {"vocab_size", "num_heads", "num_kv_heads", "d_model", "d_ff", "max_len", "attn_window",
                       "mamba1_inner", "mamba1_state_dim", "mamba1_dt_rank", "mamba1_chunk"}
    assert small["layer_mixers"] == big["layer_mixers"] == KINDS and small["layer_ids"] == IDS
    assert small["num_heads"] % 2 == 0 and small["num_kv_heads"] % 2 == 0
    assert (small["num_heads"] // 2) % (small["num_kv_heads"] // 2) == 0 and small["num_kv_heads"] < small["num_heads"]
    assert small["attn_window"] < 80 and 80 % small["mamba1_chunk"] == 0 and 80 // small["mamba1_chunk"] > 1
    assert tiny.traffic["fit"]["per_chip_batch"] == 1 and tiny.config["overrides"]["num_steps"] == 80
    # It runs end to end as ``test_bench_rehearse.py::test_rehearse_cell[phi4_flash_train-*]``.


@pytest.mark.parametrize(
    "missing",
    ["the cell (the parent's own BENCHMARK.json)", "the program config (this PR's benchmark files over the parent)"],
    ids=["unknown_cell", "unknown_program_config"],
)
def test_the_parent_cannot_run_the_cell_and_says_so_at_once(missing, tmp_path, capsys, monkeypatch):
    """``run.py`` on the parent: with its own ``BENCHMARK.json`` exit 2 on
    the unknown cell before jax is asked for a device; with this PR's
    benchmark files laid over it (what the driver does) the runner's
    ``get_config`` raises on the program config the parent lacks, before
    anything is built or compiled (on the chip: exit 1 after 15 s with
    ``KeyError: unknown config 'phi4_mini_flash'``, my chip run, PR 44)."""
    from benchmark import run as runlib

    bench = bench_testlib.read_bench()
    monkeypatch.setenv("DTM_DATA_DIR", os.environ.get("DTM_DATA_DIR", ""))
    monkeypatch.setattr("sys.path", list(__import__("sys").path))
    if missing.startswith("the cell"):
        bench["workloads"] = [w for w in bench["workloads"] if w["name"] != CELL]
        checkout = bench_testlib.checkout_with(tmp_path, bench)
        monkeypatch.setattr(cells, "load_cell", functools.partial(cells.load_cell, repo_dir=checkout))
        assert runlib.main(["--workload", CELL, "--seed", "1", "--seconds", "1"]) == 2
        captured = capsys.readouterr()
        assert f"no workload '{CELL}'" in captured.err and captured.out == ""
        return
    from distributed_tensorflow_models_tpu.harness import config as configlib

    parents = {k: v for k, v in configlib._CONFIGS.items() if k != CONFIG}
    monkeypatch.setattr(configlib, "_CONFIGS", parents)
    with pytest.raises(KeyError, match=f"unknown config '{CONFIG}'"):
        runlib.main(["--workload", CELL, "--seed", "1", "--seconds", "1", "--rehearse"])
    assert capsys.readouterr().out == ""
