"""Shared by the benchmark's tests: puts the checkout's root on the path
(the tests import ``benchmark.lib`` as the harness itself does), and
builds a checkout whose ``BENCHMARK.json`` holds more cells than the
real one (what a later PR's entries would make of it)."""

import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

# The four-chip cell ISSUE 22 planned (PERF.md section 7): the fit_b256
# job on a four-device data mesh.  Rehearsed on four virtual CPU devices.
DP4 = {"name": "resnet50_dp4", "config": "resnet50", "traffic": "fit_b256", "chips": 4, "why": "test"}


def read_bench() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def read_proposed(name: str) -> dict:
    with open(os.path.join(REPO, "benchmark", "proposed", f"{name}.json")) as f:
        return json.load(f)


def merged_bench(dp4: bool = False) -> dict:
    """``BENCHMARK.json`` with the proposed serving cell's entries merged
    in as their file says; with ``dp4`` also the four-chip cell, beside
    the cell whose job it scales (for rehearsals only: it borrows that
    cell's traffic file, which a real entry may not)."""
    bench = read_bench()
    proposed = read_proposed("gpt2m_serve_closed")
    bench["workloads"].append(proposed["workload"])
    bench["end_to_end"] += [{**m, "bound": 0.05} for m in proposed["end_to_end"]]
    bench["per_layer"] += proposed["per_layer"]
    if dp4:
        bench["workloads"].append(DP4)
        for metric in bench["end_to_end"] + bench["per_layer"]:
            if "resnet50_train" in metric.get("workloads", []):
                metric["workloads"] = metric["workloads"] + [DP4["name"]]
    return bench


def checkout_with(tmp_path, bench: dict) -> str:
    """A directory that holds ``bench`` as its ``BENCHMARK.json`` and the
    real ``benchmark/`` (linked, not copied)."""
    root = tmp_path / "checkout"
    root.mkdir()
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    os.symlink(os.path.join(REPO, "benchmark"), root / "benchmark")
    return str(root)
