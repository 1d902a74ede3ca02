"""Finding what belongs to a cell, by name.

``BENCHMARK.json`` (at the root of the checkout) names the cells, the
configurations and the metrics.  Everything else is a file of its own
under ``benchmark/``, found by the name ``BENCHMARK.json`` gives:

- ``configs/<config>.json``   (the path is the entry's ``file``)
- ``traffic/<traffic>.json``  parameters of one traffic mix; names its runner
- ``runners/<runner>.py``     ``run(cell, opts) -> RunResult``
- ``layer_metrics/<reader>.py``  ``read(ctx) -> float | None``
- ``flops/<function>.py``     ``flops_per_item(**kwargs) -> float``

A per-layer metric names the one end-to-end metric it moves.  A
quantity that is read in cells with different end-to-end metrics
therefore has one entry per moved metric, named ``<reader>.<tag>``
(``mfu.images`` moves ``train_images_per_s``, ``mfu.tokens`` moves
``train_tokens_per_s``), and all of them are read by the one file
``layer_metrics/<reader>.py`` (:func:`reader_name`).

So a later PR adds a configuration, a mix, a runner or a per-layer
metric by adding files and one entry, and edits no file that is there.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import re
import sys
from typing import Any, Callable, Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def check_name(name: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"not a valid name: {name!r}")
    return name


def reader_name(metric: str) -> str:
    """The reader file of a per-layer metric: its name up to the first
    ``.`` (``mfu.tokens`` -> ``mfu``; a name without one is its own)."""
    return check_name(metric).split(".", 1)[0]


def read_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """``benchmark/<kind>/<name>.py`` as a module, loaded from its file
    so that dropping a file in is all it takes to add one."""
    check_name(name)
    path = os.path.join(bench_dir, kind, f"{name}.py")
    if not os.path.isfile(path):
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    # Registered like an imported module (dataclasses look their module up).
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    traffic_name: str
    config: dict  # the configuration's file
    traffic: dict  # the traffic mix's file
    end_to_end: list  # BENCHMARK.json entries that apply to this cell
    per_layer: list

    @property
    def runner(self) -> str:
        return check_name(self.traffic["runner"])


def deep_merge(base: dict, over: dict) -> dict:
    """``base`` with ``over`` laid on top, group by group."""
    out = dict(base)
    for key, value in over.items():
        if isinstance(value, dict) and isinstance(out.get(key), dict):
            out[key] = deep_merge(out[key], value)
        else:
            out[key] = value
    return out


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(
    name: str,
    *,
    repo_dir: str = REPO_DIR,
    bench_dir: str = BENCH_DIR,
    rehearse: bool = False,
) -> Cell:
    """The cell as ``BENCHMARK.json`` and its files give it.  With
    ``rehearse`` the traffic file's ``rehearse`` group is laid over it
    (``traffic`` over the mix, ``config_overrides`` over the
    configuration's overrides): the tiny size a CPU rehearsal runs at.
    The runners never know which of the two they were given."""
    bench = read_json(os.path.join(repo_dir, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(
            f"no workload {name!r} in BENCHMARK.json "
            f"(have {[w['name'] for w in bench['workloads']]})"
        )
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    traffic_path = os.path.join(
        bench_dir, "traffic", f"{check_name(entry['traffic'])}.json"
    )
    config_file = read_json(os.path.join(repo_dir, config["file"]))
    traffic = read_json(traffic_path)
    tiny = traffic.pop("rehearse", {})
    if rehearse:
        traffic = deep_merge(traffic, tiny.get("traffic", {}))
        config_file = deep_merge(
            config_file, {"overrides": tiny.get("config_overrides", {})}
        )
    return Cell(
        name=name,
        chips=int(entry["chips"]),
        config_name=entry["config"],
        traffic_name=entry["traffic"],
        config=config_file,
        traffic=traffic,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)],
    )


def flops_per_item(config: dict, bench_dir: str = BENCH_DIR) -> Optional[float]:
    """Model FLOPs of one item (image, token) of a configuration, from
    the function and arguments its file names."""
    spec = config.get("flops_per_item")
    if not spec:
        return None
    module = load_module("flops", spec["function"], bench_dir)
    return float(module.flops_per_item(**spec.get("kwargs", {})))


def read_layer_metrics(
    cell: Cell, ctx: dict, bench_dir: str = BENCH_DIR,
    on_error: Optional[Callable[[str, Exception], None]] = None,
    strict: bool = True,
) -> dict:
    """Every per-layer metric of the cell, through its own reader.  A
    reader that finds nothing to read returns None and the metric is
    left out of the line."""
    out = {}
    for metric in cell.per_layer:
        reader = load_module("layer_metrics", reader_name(metric["name"]), bench_dir)
        try:
            value = reader.read(ctx)
        except (KeyError, TypeError, ZeroDivisionError) if strict else Exception as e:
            # A reader whose source is missing from this run's context
            # (a rehearsal on the CPU also has no peaks to divide by).
            if on_error is not None:
                on_error(metric["name"], e)
            value = None
        if value is not None:
            out[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    return out
