"""Rehearsal: every runner end to end on the CPU at the tiny size its
traffic file gives, through ``benchmark/run.py --rehearse``: the cells
of ``BENCHMARK.json``, the proposed serving cell
(``benchmark/proposed/``) and the planned four-chip cell on four virtual
devices.  Only the shape of the last line is checked; a rehearsal
prints no value under the name of a device metric and is never a
cell."""

import functools
import json
import os

import pytest

import bench_testlib

REPO = bench_testlib.REPO
CELLS = [w["name"] for w in bench_testlib.merged_bench(dp4=True)["workloads"]]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearse_cell(cell, trace, capsys, monkeypatch, tmp_path):
    from benchmark import run as runlib
    from benchmark.lib import cells

    checkout = bench_testlib.checkout_with(tmp_path, bench_testlib.merged_bench(dp4=True))
    load_cell = functools.partial(cells.load_cell, repo_dir=checkout)
    monkeypatch.setattr(cells, "load_cell", load_cell)
    monkeypatch.setenv("DTM_DATA_DIR", os.environ.get("DTM_DATA_DIR", ""))
    monkeypatch.setattr("sys.path", list(__import__("sys").path))
    rc = runlib.main(
        ["--workload", cell, "--seed", "3", "--seconds", "1", "--trace", str(trace), "--rehearse"]
    )
    assert rc == 0
    lines = [ln for ln in capsys.readouterr().out.splitlines() if ln.strip()]
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True
    assert {"correct", "attempted", "failed", "metrics", "device"} <= set(last)
    assert "breakdown" not in last
    assert last["attempted"] > 0 and last["failed"] == 0
    assert last["device"]["platform"] == "cpu"
    assert all(m["value"] is None for m in last["metrics"].values())
    loaded = load_cell(cell)
    assert last["device"]["count"] == loaded.chips
    wanted = loaded.per_layer if trace else loaded.end_to_end
    assert set(last["metrics"]) <= {m["name"] for m in wanted}
    if not trace:
        assert set(last["metrics"]) == {m["name"] for m in wanted}
    earlier = json.loads(lines[-2]) if trace == 0 else json.loads(lines[0])
    checks = earlier["checks"]
    # What needs no device and no length of training holds on the CPU,
    # and so does the warm-up: nothing is compiled inside the window.
    skip = ("device_ran_in_trace", "all_reduce_on_device", "loss_fell")
    assert all(v for k, v in checks.items() if k not in skip), checks
    if loaded.runner == "serve_loop":
        # float32 on the CPU: the served greedy tokens are the plain
        # reference's own choices, or tied with them to rounding.
        assert earlier["notes"]["reference_margin_max"] < 1e-3
        assert earlier["notes"]["reference_margins_over_tol"][1] > 0
    work = os.path.join(REPO, ".benchmark_work")
    mine = f"{cell}.rehearse{os.getpid()}"
    assert not os.path.isdir(work) or mine not in os.listdir(work)
