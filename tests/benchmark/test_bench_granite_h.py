"""What PR 38 added to the benchmark beside the reference: the
``granite_h_micro`` configuration file against the catalog row it was cut
from, its analytic FLOPs against a count by hand, the state-space scan's
operations and bytes, the cell's lists of metrics, the readers of the two
new scopes on the recorded v5e trace, and what two pinned listing tests
of earlier PRs held, in the form that stays true when a cell is appended
("after", never "last")."""

import functools
import glob
import json
import os

import pytest

import bench_testlib
from benchmark.lib import cells, named_scopes
from benchmark.lib import trace_reduce as tr

CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CELL = "granite_h_train"
CONFIG = "granite_h_micro"
NEW = ("ssm_device_ms.tokens", "ssd_core_device_ms.tokens", "ssd_core_roofline_share.tokens")
STARTUP = (
    "startup_process_to_fit_s", "startup_build_state_s", "startup_dataset_s", "startup_aot_lower_s",
    "startup_aot_compile_s", "startup_aot_join_s", "startup_first_chunk_s", "startup_first_loss_row_s",
    "startup_coverage", "startup_cache_hit_share",
)
GDN = ("gdn_core_device_ms.tokens", "gdn_core_roofline_share.tokens")
REDUCED = ["num_hidden_layers", "vocab_size"]

# The toy program's instructions (tests/benchmark/data/toy_v5e.xplane.pb:
# four runs of jit_step), named as a step of this configuration names them.
TOY_MAP = {
    "jit_step": {
        "convert_reduce_fusion": "jit(step)/jvp(TransformerLM)/blocks_1/blocks_1._mix/ssm/jit(plain_ssd)/ssd_core/dot_general",
        "fusion": "jit(step)/transpose(jvp(TransformerLM))/blocks_1/blocks_1._mix/ssm/in_proj/dot_general",
        "broadcast_subtract_fusion": "jit(step)/optimizer/sub",
        "copy-done": "jit(step)/jvp(TransformerLM)/blocks_5/blocks_5._mix/attn/attention_core/pallas_call",
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
        row = next(r for r in map(json.loads, f) if r["name"] == "granite-4.0-h-micro")
    assert config["published"] == row["config"]
    assert config["source"].startswith(row["source_url"])
    entry = next(c for c in bench_testlib.read_bench()["configs"] if c["name"] == CONFIG)
    assert entry["source"] == row["source_url"] and entry["file"] == f"benchmark/configs/{CONFIG}.json"
    differ = sorted(k for k, v in row["config"].items() if config[k] != v)
    assert differ == sorted(entry["reduced"]) == REDUCED
    # Depth (one whole period) and the vocabulary (one of 8); no width and no head count.
    assert [config[k] for k in REDUCED] == [10, 12544]
    assert not any(k.endswith(("_dim", "_rank")) or "hidden_size" in k or "intermediate" in k or "head" in k
                   for k in entry["reduced"])
    assert len(config["reduced"]) == 2 and all(k in " ".join(config["reduced"]) for k in differ)
    for key in ("stands_for", "assumed", "departures"):
        assert config[key]
    for word in ("four chips that hold a period of ten layers each as pipeline stages", "shared eight ways",
                 "12.35 GB", "No head is cut", "What the cut distorts"):
        assert word in config["stands_for"], word
    for key in ("in_proj_order", "gated_norm", "dt", "convolution", "attention", "multipliers", "ssm_chunk",
                "optimizer"):
        assert key in config["assumed"]
    assert len(entry["why"]) <= 200 and len(entry["source"]) <= 200


def test_the_program_runs_the_published_widths(config):
    mk, pub = config["overrides"]["model_kwargs"], config["published"]
    assert (mk["d_model"], mk["d_ff"], mk["norm_eps"], mk["use_bias"]) == (
        pub["hidden_size"], pub["shared_intermediate_size"], pub["rms_norm_eps"], pub["attention_bias"])
    assert (mk["ssm_num_heads"], mk["ssm_head_dim"], mk["ssm_state_dim"], mk["ssm_conv_size"]) == (
        pub["mamba_n_heads"], pub["mamba_d_head"], pub["mamba_d_state"], pub["mamba_d_conv"])
    assert pub["mamba_n_heads"] * pub["mamba_d_head"] == pub["mamba_expand"] * pub["hidden_size"] == 4096
    assert pub["mamba_n_groups"] == 1 and pub["mamba_conv_bias"] is True and pub["mamba_proj_bias"] is False
    # No head is cut, of either kind.
    assert (mk["num_heads"], mk["num_kv_heads"]) == (pub["num_attention_heads"], pub["num_key_value_heads"]) == (32, 8)
    assert (config["num_attention_heads"], config["num_key_value_heads"], config["mamba_n_heads"]) == (32, 8, 64)
    assert (mk["embedding_multiplier"], mk["residual_multiplier"], mk["attention_multiplier"], mk["logits_scaling"]) == (
        pub["embedding_multiplier"], pub["residual_multiplier"], pub["attention_multiplier"], pub["logits_scaling"])
    assert mk["tie_embeddings"] is pub["tie_word_embeddings"] is True
    assert pub["position_embedding_type"] == "nope" and mk["pos_encoding"] == "none"
    assert pub["num_local_experts"] == 0 and "num_experts" not in mk
    assert mk["vocab_size"] == config["vocab_size"] == pub["vocab_size"] // 8
    assert mk["num_layers"] == config["num_hidden_layers"] == 10
    # Layers 1 to 10 of the published pattern, by kind: one whole period.
    kinds = [{"mamba": "ssm", "attention": "attention"}[t] for t in pub["layer_types"][:10]]
    assert mk["layer_mixers"] == kinds == ["ssm"] * 5 + ["attention"] + ["ssm"] * 4
    assert pub["layer_types"] == pub["layer_types"][:10] * 4 and config["layer_types"] == pub["layer_types"]
    assert (mk["norm"], mk["mlp"], mk["remat"]) == ("rmsnorm", "gated_silu", True)
    assert config["parameters"]["count"] == 772_160_448
    assert config["parameters"]["state_gb_at_16_bytes"] == pytest.approx(772_160_448 * 16 / 1e9, abs=5e-3)
    # The program config itself is the uncut model.
    from distributed_tensorflow_models_tpu.harness.config import get_config

    full = get_config(CONFIG).model_kwargs
    assert (full["num_layers"], full["vocab_size"]) == (pub["num_hidden_layers"], pub["vocab_size"])
    assert list(full["layer_mixers"]) == [{"mamba": "ssm", "attention": "attention"}[t] for t in pub["layer_types"]]
    # The scan's chunk is the program's, said in the file, and no part of the model.
    same = [k for k in mk if k not in ("vocab_size", "num_layers", "layer_mixers", "ssm_chunk")]
    assert all(full[k] == mk[k] for k in same)
    assert mk["ssm_chunk"] == config["ssd_core"]["kwargs"]["chunk"]


def test_granite_h_flops_hand_counted(config):
    m = cells.load_module("flops", "granite_h")
    kw = config["flops_per_item"]["kwargs"]
    ssm = 2048 * 8512 + 4096 * 2048 + 2 * 64 * 128 * 64
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512 + 2 * 8192 * 32 * 64
    ffn = 3 * 2048 * 8192
    assert (ssm, attention, ffn) == (26_869_760, 44_040_192, 50_331_648)
    macs = 9 * ssm + attention + 10 * ffn + 2048 * 12544
    assert m.forward_macs_per_token(**kw) == macs == 814_874_624
    assert m.flops_per_item(**kw) == cells.flops_per_item(config) == 6 * macs
    assert 6 * macs == pytest.approx(4.89e9, rel=1e-3)  # ISSUE 38's planning figure
    # Everything held and no cut: the published model's parameters in its
    # matrices (the tied matrix once, where it multiplies), no scores.
    full = dict(kw, ssm_layers=36, attention_layers=4, vocab_size=100352, seq_len=0)
    assert m.forward_macs_per_token(**full) == (
        36 * ssm + 4 * (2 * 2048 * 2048 + 2 * 2048 * 512) + 40 * ffn + 2048 * 100352
    )


def test_the_state_space_scan_s_operations_and_bytes(config):
    m = cells.load_module("flops", "granite_h")
    kw = config["ssd_core"]["kwargs"]
    need = m.ssd_core_per_step(tokens=8192, **kw)
    L = kw["chunk"]
    # A chunk: C B^T once (L x L x 128), and per head the masked scores
    # times the values (L x L x 64), the read and the write (L x 128 x 64 each).
    per_chunk = L * L * 128 + 64 * (L * L * 64 + 2 * L * 128 * 64)
    assert need["flops"] == 6 * per_chunk * (8192 / L) * 9
    assert need["bytes"] == 3 * ((2 * 4096 + 2 * 128) * 2 + 64 * 4) * 8192 * 9
    # C B^T is counted once a chunk, not once a head.
    per_head = m.ssd_core_per_step(tokens=8192, **dict(kw, heads=1))
    assert need["flops"] - 64 * per_head["flops"] == -63 * 6 * L * L * 128 * (8192 / L) * 9
    # On a v5e a few milliseconds a step, whichever binds.
    least = max(need["bytes"] / 819e9, need["flops"] / 197e12)
    assert 2e-3 < least < 6e-3


def test_the_cell_lists_the_new_metrics_and_the_token_metrics_that_apply():
    bench = bench_testlib.read_bench()
    cell = cells.load_cell(CELL)
    names = {m["name"] for m in cell.per_layer}
    assert set(NEW) <= names
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert by_name[name] == {
            "name": name, "unit": "%" if "share" in name else "ms", "better": "higher" if "share" in name else "lower",
            "source": "device_trace", "layer": "models and ops", "moves": "train_tokens_per_s", "workloads": [CELL],
        }
        assert callable(cells.load_module("layer_metrics", cells.reader_name(name)).read)
    # Appended: the three are adjacent, in order, after everything an
    # earlier PR listed (later PRs append after them).
    order = [m["name"] for m in bench["per_layer"]]
    assert _after(order, (*GDN, *STARTUP), NEW)
    # Every token metric gpt2m_train reports and its own three; the whole
    # delta-rule mixer's time stays the two delta-rule cells'.
    gpt2m = {m["name"] for m in cells.load_cell("gpt2m_train").per_layer}
    assert names == gpt2m | set(NEW)
    assert by_name["linear_attn_device_ms.tokens"]["workloads"] == ["kimi_linear_train", "olmo_hybrid_train"]
    assert not any(n.startswith(("moe_", "kda_", "gdn_", "mla_", "linear_attn")) for n in names)
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.runner == "train_fit" and cell.traffic_name == "fit_lm_1x8192_ssm"
    fit = cell.traffic["fit"]
    assert fit["per_chip_batch"] * cell.config["overrides"]["num_steps"] == 8192
    # fit_lm_1x8192's mix to the letter.
    assert fit == cells.load_cell("olmo_hybrid_train").traffic["fit"]
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry == {"name": CELL, "config": CONFIG, "traffic": "fit_lm_1x8192_ssm", "chips": 1, "why": entry["why"]}
    assert len(entry["why"]) <= 200 and "layers 1-10" in entry["why"]
    # Nothing that was there is gone or moved: the six cells and five
    # configurations before it, this one after them.
    cell_names = [w["name"] for w in bench["workloads"]]
    assert cell_names[:6] == [
        "resnet50_train", "gpt2m_train", "resnet50_dp4", "olmoe_train", "kimi_linear_train", "olmo_hybrid_train"]
    assert cell_names.index(CELL) == 6
    config_names = [c["name"] for c in bench["configs"]]
    assert config_names[:5] == ["resnet50", "gpt2m", "olmoe", "kimi_linear", "olmo_hybrid"]
    assert config_names.index(CONFIG) == 5
    # One four-chip place still: a second opens with the eighth cell.
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_olmo_hybrid_train_keeps_what_its_pinned_listing_tests_hold():
    """``test_bench_startup.py::test_olmo_hybrid_train_keeps_what_its_own_
    listing_test_holds`` (itself the stand-in for ``test_bench_olmo_hybrid.py``'s
    listing test, which fails since PR 34) wants ``olmo_hybrid`` **last** in
    ``configs`` and ``workloads`` and the two ``gdn_core_*`` entries just
    before the **last ten**; with this PR's cell and three entries appended
    it fails, in plain sight, and may not be edited (PERF.md section 7 asks
    a ``benchmark`` PR).  What it held is held here in the form that stays
    true when the next cell comes."""
    bench = bench_testlib.read_bench()
    cell = cells.load_cell("olmo_hybrid_train")
    names = {m["name"] for m in cell.per_layer}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    order = [m["name"] for m in bench["per_layer"]]
    assert order.index(GDN[0]) + 1 == order.index(GDN[1]) < order.index(STARTUP[0])
    assert (by_name[GDN[0]]["unit"], by_name[GDN[1]]["unit"], by_name[GDN[1]]["better"]) == ("ms", "%", "higher")
    gpt2m = {m["name"] for m in cells.load_cell("gpt2m_train").per_layer}
    assert names == gpt2m | {"linear_attn_device_ms.tokens", *GDN}
    assert by_name["linear_attn_device_ms.tokens"]["workloads"] == ["kimi_linear_train", "olmo_hybrid_train"]
    for name in ("kda_core_device_ms.tokens", "kda_core_roofline_share.tokens", "moe_held_share.tokens",
                 "mla_core_roofline_share.tokens"):
        assert by_name[name]["workloads"] == ["kimi_linear_train"] and name not in names
    assert not any(n.startswith(("moe_", "ssm_", "ssd_")) for n in names)
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.runner == "train_fit" and cell.traffic_name == "fit_lm_1x8192"
    fit = cell.traffic["fit"]
    assert fit["per_chip_batch"] * cell.config["overrides"]["num_steps"] == 8192
    assert (fit["warmup_steps"], fit["trace_steps"], fit["settle_steps"], fit["overrides"]) == (
        10, 10, 10, {"log_every_steps": 10})
    assert [w["name"] for w in bench["workloads"]].index("olmo_hybrid_train") == 5
    assert [c["name"] for c in bench["configs"]].index("olmo_hybrid") == 4


def test_the_startup_entries_keep_what_their_pinned_listing_test_holds():
    """``test_bench_startup.py::test_every_new_entry_has_its_reader_and_every_
    reader_its_entry`` takes ``per_layer[-10:]`` for the ten ``startup_*``
    entries; with this PR's three after them it fails, in plain sight.  What
    it held, with "adjacent, in order, after ``time_to_first_step_s``" where
    it said "last"."""
    per_layer = bench_testlib.read_bench()["per_layer"]
    order = [m["name"] for m in per_layer]
    assert _after(order, ("time_to_first_step_s", *GDN), STARTUP)
    shares = ("startup_coverage", "startup_cache_hit_share")
    for metric in per_layer:
        name = metric["name"]
        if name not in STARTUP:
            continue
        assert metric == {
            "name": name, "unit": "%" if name in shares else "s", "better": "higher" if name in shares else "lower",
            "source": "program_counter", "layer": "start-up", "moves": "setup_s",
        }, name
        assert cells.reader_name(name) == name
        assert callable(cells.load_module("layer_metrics", name).read)
    files = glob.glob(os.path.join(bench_testlib.REPO, "benchmark", "layer_metrics", "startup_*.py"))
    assert sorted(os.path.basename(f)[: -len(".py")] for f in files) == sorted(STARTUP)
    assert [m["name"] for m in per_layer if m["layer"] == "start-up"] == ["time_to_first_step_s", *STARTUP]
    # No list of cells: read in every cell, this PR's too.
    assert all("workloads" not in m for m in per_layer if m["layer"] == "start-up")
    assert set(STARTUP) <= {m["name"] for m in cells.load_cell(CELL).per_layer}


def test_the_readers_of_the_new_scopes(toy_planes, monkeypatch, config):
    table = named_scopes.seconds_by_op_name(toy_planes, TOY_MAP)
    monkeypatch.setattr(named_scopes, "table", lambda ctx: table)
    secs = _seconds_by_instruction(toy_planes)
    ctx = {
        "trace": {"steps": 4}, "config": config, "device_kind": "TPU v5 lite",
        "items_per_step": 8192, "chips": 1,
    }
    read = lambda name: cells.load_module("layer_metrics", name).read(ctx)
    core_ms = 1e3 * secs["convert_reduce_fusion"] / 4
    assert read("ssd_core_device_ms") == pytest.approx(core_ms)
    assert read("ssm_device_ms") == pytest.approx(core_ms + 1e3 * secs["fusion"] / 4)
    m = cells.load_module("flops", "granite_h")
    need = m.ssd_core_per_step(tokens=8192, **config["ssd_core"]["kwargs"])
    least_ms = 1e3 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("ssd_core_roofline_share") == pytest.approx(100.0 * least_ms / core_ms, rel=1e-6)
    # This program has no delta-rule core, and olmo_hybrid's file names no ssd_core need.
    for other in ("gdn_core_device_ms", "kda_core_device_ms", "linear_attn_device_ms", "gdn_core_roofline_share"):
        assert read(other) is None
    with open(os.path.join(bench_testlib.REPO, "benchmark", "configs", "olmo_hybrid.json")) as f:
        ctx["config"] = json.load(f)
    assert read("ssd_core_roofline_share") is None
    ctx["config"] = config
    # The parent's program has no such scopes, and the line leaves the metrics out.
    monkeypatch.setattr(named_scopes, "table", lambda ctx: {"jit(s)/jvp(M)/linear_attn/gdn_core/dot_general": 1.0})
    assert all(read(cells.reader_name(n)) is None for n in NEW)
    monkeypatch.setattr(named_scopes, "table", lambda ctx: None)
    assert all(read(cells.reader_name(n)) is None for n in NEW)
    for empty in ({}, {"trace": None}, {"trace": {"steps": 0}}):
        for name in NEW:
            assert cells.load_module("layer_metrics", cells.reader_name(name)).read(empty) is None


def test_the_rehearsal_is_the_cell_at_a_small_size():
    """The traffic file's ``rehearse`` block shrinks widths and lengths
    and nothing else: the same mixers, grouped heads, multipliers, tie and
    recomputation."""
    real, tiny = cells.load_cell(CELL), cells.load_cell(CELL, rehearse=True)
    big, small = real.config["overrides"]["model_kwargs"], tiny.config["overrides"]["model_kwargs"]
    changed = {k for k in big if big[k] != small[k]}
    assert changed == {"vocab_size", "num_heads", "num_kv_heads", "d_model", "d_ff", "max_len", "ssm_num_heads",
                       "ssm_head_dim", "ssm_state_dim", "ssm_chunk"}
    assert small["num_heads"] % small["num_kv_heads"] == 0 and small["num_kv_heads"] < small["num_heads"]
    assert small["ssm_num_heads"] * small["ssm_head_dim"] != small["d_model"]  # an expansion, as in the cell
    assert 80 % small["ssm_chunk"] == 0 and 80 // small["ssm_chunk"] > 1  # several chunks carry a state
    assert tiny.traffic["fit"]["per_chip_batch"] == 1 and tiny.config["overrides"]["num_steps"] == 80
    # It runs end to end as ``test_bench_rehearse.py::test_rehearse_cell[granite_h_train-*]``.


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
    anything is built or compiled."""
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
