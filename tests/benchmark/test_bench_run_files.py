"""``benchmark/lib/run_files.py`` against ``run.py``'s own layout: a
rehearsal of each cell, traced, with the per-layer readers' entry point
wrapped so that the helper is asked while the run's files are there."""

import functools
import json
import os

import pytest

import bench_testlib
from benchmark.lib import run_files

CELLS = [w["name"] for w in bench_testlib.read_bench()["workloads"]]


def test_work_dir_rules(tmp_path):
    root = tmp_path / ".benchmark_work"
    assert run_files.work_dir(str(root), argv=["run.py", "--workload", "a"]) is None
    (root / "a").mkdir(parents=True)
    (root / f"b.rehearse{os.getpid() + 1}").mkdir()  # another process's
    assert run_files.work_dir(str(root), argv=["run.py", "--workload", "a"]) == str(root / "a")
    assert run_files.work_dir(str(root), argv=["run.py", "--workload=a"]) == str(root / "a")
    assert run_files.work_dir(str(root), argv=["run.py", "--workload", "c"]) is None
    assert run_files.work_dir(str(root), argv=["pytest"]) is None
    mine = root / f"a.rehearse{os.getpid()}"
    mine.mkdir()
    assert run_files.work_dir(str(root), argv=["pytest"]) == str(mine)
    assert run_files.xplane_path(str(mine)) is None
    assert run_files.step_scopes_path(str(mine)) is None
    (mine / "fit").mkdir()
    (mine / "fit" / "step_scopes_p0.json").write_text("{}")
    assert run_files.step_scopes_path(str(mine)) == str(mine / "fit" / "step_scopes_p0.json")


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_is_found_and_lists_the_input_metrics(
    cell, capsys, monkeypatch, tmp_path
):
    from benchmark import run as runlib
    from benchmark.lib import cells, scoped_trace

    checkout = bench_testlib.checkout_with(tmp_path, bench_testlib.read_bench())
    monkeypatch.setattr(cells, "load_cell", functools.partial(cells.load_cell, repo_dir=checkout))
    monkeypatch.setenv("DTM_DATA_DIR", os.environ.get("DTM_DATA_DIR", ""))
    monkeypatch.setattr("sys.path", list(__import__("sys").path))
    seen = {}
    read_layer_metrics = cells.read_layer_metrics

    def spying(cell_, ctx, **kwargs):
        # Where run.py calls the readers: the run's files are still there.
        work = run_files.work_dir()
        seen["work"] = work
        seen["xplane"] = run_files.xplane_path(work)
        seen["scopes"] = run_files.step_scopes_path(work)
        seen["modules"] = list(cells.read_json(seen["scopes"])["modules"])
        seen["summary"] = scoped_trace.summary({"trace": None})
        return read_layer_metrics(cell_, ctx, **kwargs)

    monkeypatch.setattr(cells, "read_layer_metrics", spying)
    rc = runlib.main(["--workload", cell, "--seed", "5", "--seconds", "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    # run.py's layout: .benchmark_work/<cell>.rehearse<pid>, fit/ and profile/.
    assert seen["work"] == os.path.join(runlib.WORK_ROOT, f"{cell}.rehearse{os.getpid()}")
    assert seen["scopes"] == os.path.join(seen["work"], "fit", "step_scopes_p0.json")
    assert seen["xplane"].startswith(os.path.join(seen["work"], "profile", "plugins", "profile"))
    assert seen["modules"] == ["jit_one_step"]
    # A CPU trace has no TPU plane: the device readers find nothing.
    assert seen["summary"] is None
    assert not os.path.exists(seen["work"])  # and run.py removed it after
    last = json.loads([ln for ln in capsys.readouterr().out.splitlines() if ln.strip()][-1])
    tag = "images" if cell == "resnet50_train" else "tokens"
    assert {f"input_assemble_ms.{tag}", f"input_transfer_ms.{tag}"} <= set(last["metrics"])
    device_only = ("fwd_device_ms", "bwd_device_ms", "opt_", "scope_coverage")
    assert not any(name.startswith(device_only) for name in last["metrics"])
