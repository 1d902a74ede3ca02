"""The start-up layer's per-layer metrics (PR 34): ten readers over the
``startup/*`` gauges and counters ``fit`` leaves in ``telemetry.json``,
all moving ``setup_s``, in every cell like ``time_to_first_step_s``."""

import glob
import json
import os

import pytest

import bench_testlib

from benchmark.lib import cells

NEW = (
    "startup_process_to_fit_s",
    "startup_build_state_s",
    "startup_dataset_s",
    "startup_aot_lower_s",
    "startup_aot_compile_s",
    "startup_aot_join_s",
    "startup_first_chunk_s",
    "startup_first_loss_row_s",
    "startup_coverage",
    "startup_cache_hit_share",
)
SHARES = ("startup_coverage", "startup_cache_hit_share")
CELLS = [w["name"] for w in bench_testlib.read_bench()["workloads"]]
# A fit's start-up as the program reports it (seconds; two counters).
COUNTERS = {
    "startup/process_to_fit_s": 12.5,
    "startup/build_state_s": 4.0,
    "startup/build_step_s": 0.5,
    "startup/restore_s": 0.0,
    "startup/dataset_s": 1.0,
    "startup/pipeline_open_s": 0.5,
    "startup/first_chunk_s": 13.0,
    "startup/aot_join_s": 0.0,
    "startup/first_data_wait_s": 0.25,
    "startup/unattributed_s": 1.0,
    "startup/time_to_first_step_s": 20.0,
    "startup/first_loss_row_s": 22.0,
    "startup/aot_lower_s": 6.0,
    "startup/aot_compile_s": 9.0,
    "startup/compile_requests": 8.0,
    "startup/cache_hits": 6.0,
}


def _read(name, counters):
    return cells.load_module("layer_metrics", name).read({"counters": counters})


def test_every_new_entry_has_its_reader_and_every_reader_its_entry():
    per_layer = bench_testlib.read_bench()["per_layer"]
    names = [m["name"] for m in per_layer]
    # Appended, in the issue's order: the driver reads an entry put in the
    # middle as a change to the one whose place it took (refused once).
    assert names[-len(NEW) :] == list(NEW)
    for metric in per_layer[-len(NEW) :]:
        name = metric["name"]
        assert metric == {
            "name": name,
            "unit": "%" if name in SHARES else "s",
            "better": "higher" if name in SHARES else "lower",
            "source": "program_counter",
            "layer": "start-up",
            "moves": "setup_s",
        }, name
        assert cells.reader_name(name) == name
        assert callable(cells.load_module("layer_metrics", name).read)
    files = glob.glob(os.path.join(bench_testlib.REPO, "benchmark", "layer_metrics", "startup_*.py"))
    assert sorted(os.path.basename(f)[: -len(".py")] for f in files) == sorted(NEW)
    assert [m["name"] for m in per_layer if m["layer"] == "start-up"] == ["time_to_first_step_s", *NEW]


def test_olmo_hybrid_train_keeps_what_its_own_listing_test_holds():
    """``test_bench_olmo_hybrid.py::test_the_cell_lists_the_new_metrics_and_
    the_token_metrics_that_apply`` fails since PR 34, at the line that wants
    the **last two** entries of ``per_layer`` to be ``olmo_hybrid_train``'s:
    the driver takes additions at the end of the list alone (an entry in the
    middle reads as a change to ``fwd_device_ms.images``), and this PR may
    not edit that file (PERF.md section 7 asks a ``benchmark`` PR for the
    one line).  It stops there, so what it held after that line is held
    here until it is repaired, with the line itself in the form that stays
    true: the two are adjacent, in order, and last before this PR's ten."""
    bench = bench_testlib.read_bench()
    cell = cells.load_cell("olmo_hybrid_train")
    names = {m["name"] for m in cell.per_layer}
    by_name = {m["name"]: m for m in bench["per_layer"]}
    theirs = ("gdn_core_device_ms.tokens", "gdn_core_roofline_share.tokens")
    assert [m["name"] for m in bench["per_layer"]][-len(NEW) - 2 : -len(NEW)] == list(theirs)
    assert (by_name[theirs[0]]["unit"], by_name[theirs[1]]["unit"], by_name[theirs[1]]["better"]) == (
        "ms", "%", "higher")
    gpt2m = {m["name"] for m in cells.load_cell("gpt2m_train").per_layer}
    assert names == gpt2m | {"linear_attn_device_ms.tokens", *theirs}
    assert by_name["linear_attn_device_ms.tokens"]["workloads"] == ["kimi_linear_train", "olmo_hybrid_train"]
    for name in ("kda_core_device_ms.tokens", "kda_core_roofline_share.tokens", "moe_held_share.tokens",
                 "mla_core_roofline_share.tokens"):
        assert by_name[name]["workloads"] == ["kimi_linear_train"] and name not in names
    assert not any(n.startswith("moe_") for n in names)
    assert {m["name"] for m in cell.end_to_end} == {"train_tokens_per_s", "setup_s"}
    assert cell.chips == 1 and cell.runner == "train_fit" and cell.traffic_name == "fit_lm_1x8192"
    fit = cell.traffic["fit"]
    assert fit["per_chip_batch"] * cell.config["overrides"]["num_steps"] == 8192
    assert (fit["warmup_steps"], fit["trace_steps"], fit["settle_steps"], fit["overrides"]) == (
        10, 10, 10, {"log_every_steps": 10})
    entry = next(w for w in bench["workloads"] if w["name"] == "olmo_hybrid_train")
    assert bench["workloads"][-1] == entry and bench["configs"][-1]["name"] == "olmo_hybrid"
    assert [w["name"] for w in bench["workloads"]][:5] == [
        "resnet50_train", "gpt2m_train", "resnet50_dp4", "olmoe_train", "kimi_linear_train"]
    assert [c["name"] for c in bench["configs"]][:4] == ["resnet50", "gpt2m", "olmoe", "kimi_linear"]


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_where_the_program_wrote_no_such_gauge(name):
    # The parent's telemetry.json: three start-up gauges, none of the new.
    parent = {
        "startup/restore_s": 0.0,
        "startup/aot_compile_s": 9.0,
        "startup/aot_lower_s": 6.0,
        "startup/time_to_first_step_s": 20.0,
    }
    own = {
        "startup_aot_lower_s": 6.0,
        "startup_aot_compile_s": 9.0,
    }
    assert _read(name, parent) == own.get(name)
    assert _read(name, {}) is None
    assert cells.load_module("layer_metrics", name).read({}) is None


def test_the_readers_values_and_the_explicit_zero():
    got = {name: _read(name, COUNTERS) for name in NEW}
    assert got == {
        "startup_process_to_fit_s": 12.5,
        "startup_build_state_s": 4.0,
        "startup_dataset_s": 1.0,
        "startup_aot_lower_s": 6.0,
        "startup_aot_compile_s": 9.0,
        "startup_aot_join_s": 0.0,  # a value: the thread had finished
        "startup_first_chunk_s": 13.0,
        "startup_first_loss_row_s": 22.0,
        "startup_coverage": 95.0,  # 19 of 20 s in the six phases
        "startup_cache_hit_share": 75.0,
    }
    # Through the harness: a 0.0 stays in the line, a None is left out.
    cell = cells.load_cell("gpt2m_train")
    line = cells.read_layer_metrics(cell, {"counters": COUNTERS}, strict=False)
    assert line["startup_aot_join_s"] == {"value": 0.0, "unit": "s"}
    assert set(NEW) <= set(line)
    cold = {**COUNTERS, "startup/cache_hits": 0.0}
    assert _read("startup_cache_hit_share", cold) == 0.0
    nothing_asked = {**COUNTERS, "startup/compile_requests": 0.0, "startup/cache_hits": 0.0}
    assert _read("startup_cache_hit_share", nothing_asked) is None
    no_first_step = {**COUNTERS, "startup/time_to_first_step_s": 0.0}
    assert _read("startup_coverage", no_first_step) is None


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_lists_the_ten(cell, capsys, monkeypatch):
    from benchmark import run as runlib

    assert set(NEW) <= {m["name"] for m in cells.load_cell(cell).per_layer}
    monkeypatch.setenv("DTM_DATA_DIR", os.environ.get("DTM_DATA_DIR", ""))
    monkeypatch.setattr("sys.path", list(__import__("sys").path))
    rc = runlib.main(["--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True
    assert set(NEW) | {"time_to_first_step_s"} <= set(last["metrics"])
    for name in NEW:
        assert last["metrics"][name] == {"value": None, "unit": "%" if name in SHARES else "s"}
