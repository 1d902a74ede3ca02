#!/usr/bin/env python3
"""Run one cell of the benchmark, in this process, on the attached TPU.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``; its configuration, traffic
mix, runner and per-layer readers are files under ``benchmark/`` found
by name (``lib/cells.py``).  The last line of standard output is one
JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` and, when traced, ``breakdown``.  With ``--trace 0`` the
metrics are the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics (the traced run's own end-to-end readings are on an
earlier line).

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  ``--rehearse`` runs the cell's runner end to end
on the CPU at the tiny size its traffic file gives and prints only the
shape of the line (metric names, no values): it is for finding wrong
paths and arguments before chip time is spent, and is never a cell.
"""

from __future__ import annotations

import time

_T_IMPORT = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import logging  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
REPO_DIR = os.path.dirname(BENCH_DIR)
WORK_ROOT = os.path.join(REPO_DIR, ".benchmark_work")


def seconds_since_process_start() -> float:
    """From the kernel's record of when this process started (Linux);
    from this module's import where /proc is not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    return parser.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, REPO_DIR)
    # The program reads its datasets from $DTM_DATA_DIR (default
    # /root/data, outside the checkout): point it at a directory that is
    # never there, so every run takes the synthetic, seeded path.
    os.environ["DTM_DATA_DIR"] = os.path.join(WORK_ROOT, "no_data")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    from benchmark.lib import cells, device
    from benchmark.lib.compile_events import CompileCounter
    from benchmark.lib.result import RunOptions

    try:
        cell = cells.load_cell(args.workload, rehearse=args.rehearse)
    except (KeyError, FileNotFoundError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    try:
        import jax

        import distributed_tensorflow_models_tpu  # noqa: F401
    except ImportError as e:
        print(f"benchmark: the program is not importable here: {e}", file=sys.stderr)
        return 3
    if args.rehearse:
        devices = list(jax.devices()[: cell.chips])
        if devices[0].platform == "tpu":
            print("benchmark: --rehearse is for the CPU", file=sys.stderr)
            return 2
    else:
        try:
            devices = device.require_tpu(cell.chips)
        except device.NoAccelerator as e:
            print(f"benchmark: {e}", file=sys.stderr)
            return 2

    from distributed_tensorflow_models_tpu.harness import startup as startuplib

    logging.basicConfig(
        level=logging.WARNING, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    # JAX_COMPILATION_CACHE_DIR where set, else <checkout>/.xla_cache.
    cache_dir = startuplib.apply_compile_cache()
    compiles = CompileCounter()
    workdir = os.path.join(WORK_ROOT, cell.name)
    if args.rehearse:
        # Rehearsals run inside test workers, several at a time.
        workdir += f".rehearse{os.getpid()}"
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    opts = RunOptions(
        seed=args.seed,
        seconds=args.seconds,
        trace=bool(args.trace),
        devices=devices,
        workdir=workdir,
        since_start=seconds_since_process_start,
        compiles=compiles,
    )
    try:
        result = cells.load_module("runners", cell.runner).run(cell, opts)
        wanted = {m["name"]: m["unit"] for m in cell.end_to_end}
        missing = sorted(set(wanted) - set(result.end_to_end))
        if missing:
            print(
                f"benchmark: runner {cell.runner!r} did not measure {missing}",
                file=sys.stderr,
            )
            return 4
        end_to_end = {
            name: {"value": float(result.end_to_end[name]), "unit": unit}
            for name, unit in wanted.items()
        }
        emit(
            {
                "workload": cell.name,
                "seed": args.seed,
                "trace": args.trace,
                "compile_cache_dir": cache_dir,
                "compile_cache": {
                    "requests": compiles.requests,
                    "hits": compiles.hits,
                    "written": compiles.misses,
                },
                "checks": result.checks,
                "notes": result.notes,
                "end_to_end": end_to_end,
            }
        )
        line = {
            "correct": result.correct,
            "attempted": int(result.attempted),
            "failed": int(result.failed),
        }
        dev = device.device_object(
            devices, result.ctx.get("program_temp_bytes", 0)
        )
        if args.trace:
            errors = {}
            result.ctx["memory_peak_bytes"] = dev["memory_peak_bytes"]
            line["metrics"] = cells.read_layer_metrics(
                cell, result.ctx, strict=not args.rehearse,
                on_error=lambda n, e: errors.update({n: repr(e)}),
            )
            if errors:
                emit({"layer_metric_errors": errors})
            trace = result.ctx.get("trace")
            if trace is not None:
                dev["busy_s"] = trace["busy_s"]
                dev["window_s"] = trace["window_s"]
                line["breakdown"] = {
                    "device_ops": trace["device_ops"],
                    "idle_gaps": trace["idle_gaps"],
                }
        else:
            line["metrics"] = end_to_end
        line["device"] = dev
        if args.rehearse:
            # Shape only: names, no values; a CPU number is never
            # written under the name of a device metric.
            line["metrics"] = {
                k: {"value": None, "unit": v["unit"]} for k, v in line["metrics"].items()
            }
            line.pop("breakdown", None)
            line["device"] = {k: dev[k] for k in ("platform", "kind", "count")}
            line["rehearsal"] = True
        emit(line)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
