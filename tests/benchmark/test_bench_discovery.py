"""BENCHMARK.json against its contract, and the data-driven discovery:
a configuration, a mix, a runner and a per-layer metric dropped into the
directories are found and run without editing a file that is there."""

import json
import os
import re
import shutil

import pytest

import bench_testlib
from benchmark.lib import cells
from benchmark.lib.result import RunOptions

REPO = bench_testlib.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module", params=["BENCHMARK.json", "with_proposed_cells"])
def bench(request):
    """The file as it is, and as the proposed serving cell's entries
    (``benchmark/proposed/``) would make it: both have to meet the
    contract."""
    if request.param == "BENCHMARK.json":
        return bench_testlib.read_bench()
    return bench_testlib.merged_bench()


@pytest.fixture
def checkout(bench, tmp_path):
    return bench_testlib.checkout_with(tmp_path, bench)


def test_top_level_keys_and_command(bench):
    assert sorted(bench) == sorted(
        ["command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"]
    )
    assert 1 <= bench["run_seconds"] <= 51 and isinstance(bench["run_seconds"], int)
    assert bench["command"][0] in ("python3", "python") and len(bench["command"]) <= 32
    assert any(bench["command"][1].startswith(p + "/") for p in bench["paths"])
    assert len(json.dumps(bench, indent=1)) <= 64 * 1024


def test_names_units_and_lines(bench):
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bench[group]]
        assert len(names) == len(set(names)), group
        assert all(NAME.match(n) for n in names), names
    metrics = bench["end_to_end"] + bench["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for e in bench["end_to_end"]:
        assert set(e) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.1
    for p in bench["per_layer"]:
        assert set(p) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        assert 1 <= len(p["layer"]) <= 200 and "\n" not in p["layer"]
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"] and "\t" not in w["why"]
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200


def test_cells_configs_and_chip_rule(bench):
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = [w["name"] for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 4)
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    for f in files:
        assert any(f.startswith(p + "/") for p in bench["paths"])
        assert os.path.isfile(os.path.join(REPO, f))


def test_every_cell_reports_what_the_contract_asks(bench, checkout):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    assert e2e["setup_s"]["bound"] <= 0.1
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], repo_dir=checkout)
        mine = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in mine and len(mine) >= 2
        assert cell.per_layer, w["name"]
        for p in cell.per_layer:
            # A per-layer metric is reported only where the metric it moves is.
            assert p["moves"] in mine, (w["name"], p["name"])


def test_every_named_file_is_there(bench, checkout):
    for w in bench["workloads"]:
        cell = cells.load_cell(w["name"], repo_dir=checkout)
        assert hasattr(cells.load_module("runners", cell.runner), "run")
        assert cells.flops_per_item(cell.config) > 0
        if "reference" in cell.config:
            assert callable(cells.load_module("references", cell.config["reference"]).forward)
    for p in bench["per_layer"]:
        assert callable(cells.load_module("layer_metrics", cells.reader_name(p["name"])).read)


def test_a_tagged_metric_is_read_by_its_reader():
    """One entry per end-to-end metric moved, one reader file for all."""
    assert cells.reader_name("mfu.tokens") == cells.reader_name("mfu.images") == "mfu"
    assert cells.reader_name("time_to_first_step_s") == "time_to_first_step_s"
    with pytest.raises(ValueError):
        cells.reader_name("../mfu.tokens")
    cell = cells.load_cell("gpt2m_train")
    entry = {"name": "compiles_in_window.tokens", "unit": "count"}
    assert entry["name"] in {m["name"] for m in cell.per_layer}
    cell.per_layer = [entry]
    assert cells.read_layer_metrics(cell, {"compiles_in_window": 0}) == {
        "compiles_in_window.tokens": {"value": 0.0, "unit": "count"}
    }


def test_dropped_in_cell_runner_and_metric_are_found_and_run(tmp_path):
    """What a later PR does: new files and one entry each, nothing edited."""
    repo = tmp_path / "checkout"
    shutil.copytree(
        os.path.join(REPO, "benchmark"), repo / "benchmark",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    bench_dir = str(repo / "benchmark")
    (repo / "benchmark" / "configs" / "tiny.json").write_text(
        json.dumps({"name": "tiny", "overrides": {}, "flops_per_item": {"function": "tiny_flops", "kwargs": {"n": 3}}})
    )
    (repo / "benchmark" / "flops" / "tiny_flops.py").write_text(
        "def flops_per_item(n):\n    return 2.0 * n\n"
    )
    (repo / "benchmark" / "traffic" / "tiny_mix.json").write_text(
        json.dumps({"name": "tiny_mix", "runner": "echo_runner", "rate": 7})
    )
    (repo / "benchmark" / "runners" / "echo_runner.py").write_text(
        "from benchmark.lib.result import RunResult\n"
        "def run(cell, opts):\n"
        "    return RunResult(checks={'ran': True}, attempted=cell.traffic['rate'], failed=0,\n"
        "                     end_to_end={'tiny_rate': 1.5, 'setup_s': 0.1},\n"
        "                     ctx={'answer': 42, 'seed': opts.seed})\n"
    )
    (repo / "benchmark" / "layer_metrics" / "tiny_answer.py").write_text(
        "def read(ctx):\n    return ctx.get('answer')\n"
    )
    (repo / "benchmark" / "layer_metrics" / "tiny_absent.py").write_text(
        "def read(ctx):\n    return ctx.get('nothing_to_read')\n"
    )
    new = bench_testlib.read_bench()
    new["configs"].append({"name": "tiny", "source": "test", "file": "benchmark/configs/tiny.json", "reduced": [], "why": "test"})
    new["workloads"].append({"name": "tiny_cell", "config": "tiny", "traffic": "tiny_mix", "chips": 1, "why": "test"})
    new["end_to_end"].append({"name": "tiny_rate", "unit": "1/s", "better": "higher", "bound": 0.05, "source": "host_clock", "workloads": ["tiny_cell"]})
    for name in ("tiny_answer", "tiny_absent"):
        new["per_layer"].append({"name": name, "unit": "count", "better": "higher", "source": "program_counter", "layer": "test", "moves": "tiny_rate", "workloads": ["tiny_cell"]})
    (repo / "BENCHMARK.json").write_text(json.dumps(new))

    cell = cells.load_cell("tiny_cell", repo_dir=str(repo), bench_dir=bench_dir)
    assert cell.runner == "echo_runner" and cell.traffic["rate"] == 7
    assert sorted(m["name"] for m in cell.end_to_end) == ["setup_s", "tiny_rate"]
    assert cells.flops_per_item(cell.config, bench_dir) == 6.0
    opts = RunOptions(seed=9, seconds=1.0, trace=True, devices=[],
                      workdir=str(tmp_path), since_start=lambda: 0.0, compiles=None)
    result = cells.load_module("runners", cell.runner, bench_dir).run(cell, opts)
    assert result.correct and result.attempted == 7 and result.ctx["seed"] == 9
    # The reader that finds nothing to read is left out of the line.
    assert cells.read_layer_metrics(cell, result.ctx, bench_dir) == {
        "tiny_answer": {"value": 42.0, "unit": "count"}
    }
    # The cells that were there are untouched by the additions.
    old = cells.load_cell("resnet50_train", repo_dir=str(repo), bench_dir=bench_dir)
    assert "tiny_rate" not in {m["name"] for m in old.end_to_end}


def test_rehearsal_view_is_laid_over_the_cell(tmp_path):
    checkout = bench_testlib.checkout_with(tmp_path, bench_testlib.merged_bench())
    real = cells.load_cell("gpt2m_serve_closed", repo_dir=checkout)
    tiny = cells.load_cell("gpt2m_serve_closed", repo_dir=checkout, rehearse=True)
    assert "rehearse" not in real.traffic and "rehearse" not in tiny.traffic
    assert real.traffic["serve"]["engine"]["max_slots"] == real.traffic["arrivals"]["clients"]
    assert real.config["overrides"]["model_kwargs"]["d_model"] == 1024
    assert tiny.config["overrides"]["model_kwargs"]["d_model"] == 32
    assert tiny.traffic["serve"]["engine"]["max_slots"] == 4
    # What the rehearsal does not name stays as the cell has it.
    assert tiny.traffic["serve"]["engine"]["prefix_cache"] is True
    assert tiny.traffic["requests"]["prompt_len"]["dist"] == "log_uniform"
    assert cells.deep_merge({"a": {"b": 1, "c": 2}}, {"a": {"b": 3}, "d": 4}) == {
        "a": {"b": 3, "c": 2}, "d": 4
    }


def test_bad_names_are_refused():
    with pytest.raises(ValueError):
        cells.load_module("runners", "../run")
    with pytest.raises(FileNotFoundError):
        cells.load_module("runners", "no_such_runner")
    with pytest.raises(KeyError):
        cells.load_cell("no_such_cell")
