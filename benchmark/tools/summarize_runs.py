"""Medians and spreads of a directory of run outputs (development tool).

    python3 benchmark/tools/summarize_runs.py chiprun_out/<dir> [set size]

Reads the last line of every ``*.out`` file (one run each, named
``<cell>.t<trace>.s<seed>.out``), groups the untraced runs by cell in
seed order into sets of ``set size`` and prints, per cell and metric,
each set's median and spread (distance between the quartiles over the
median: the driver's measure) and the bound five times the wider spread
would give.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark.lib import stats  # noqa: E402


def main(argv) -> int:
    directory = argv[1]
    set_size = int(argv[2]) if len(argv) > 2 else 6
    runs: dict = {}
    for path in sorted(glob.glob(os.path.join(directory, "*.out"))):
        m = re.match(r"(.+)\.t(\d)\.s(\d+)\.out$", os.path.basename(path))
        if not m:
            continue
        with open(path) as f:
            lines = [ln for ln in f.read().splitlines() if ln.strip()]
        if not lines:
            print(f"{path}: no output")
            continue
        last = json.loads(lines[-1])
        if m.group(2) != "0":
            continue
        runs.setdefault(m.group(1), []).append((int(m.group(3)), last))
    for cell, items in runs.items():
        items.sort(key=lambda kv: kv[0])
        print(f"== {cell}: {len(items)} runs, correct {[r['correct'] for _, r in items]}")
        names = list(items[0][1]["metrics"])
        for name in names:
            values = [r["metrics"][name]["value"] for _, r in items]
            sets = [values[i:i + set_size] for i in range(0, len(values), set_size)]
            sets = [s for s in sets if len(s) >= 3]
            spreads = [stats.spread(s) for s in sets]
            medians = [stats.percentile(s, 50) for s in sets]
            widest = max(spreads) if spreads else None
            print(
                f"  {name}: values {[round(v, 3) for v in values]}\n"
                f"    set medians {[round(m, 3) for m in medians]} "
                f"spreads {[round(100 * s, 3) for s in spreads]}% "
                f"-> 5x widest = {None if widest is None else round(500 * widest, 2)}%"
            )
        print("  memory_peak_GB", [round(r["device"]["memory_peak_bytes"] / 1e9, 2) for _, r in items])
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
