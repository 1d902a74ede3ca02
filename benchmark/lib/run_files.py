"""Where this run's files are, for readers that get only ``ctx``.

``run.py`` hands a runner its work directory and the per-layer readers
only what the runner put into ``ctx``, which holds no path.  A reader
that needs a file of the run (the profiler's ``.xplane.pb``, what
``fit`` left in its work directory) finds it here, by ``run.py``'s own
layout:

- the work directory is ``<checkout>/.benchmark_work/<cell>``, and
  ``<cell>.rehearse<pid>`` in a rehearsal (``run.py::main``);
- ``train_fit`` gives ``fit`` the directory ``fit`` under it and the
  profiler ``profile``.

Exactly one such directory belongs to this process: ``run.py`` empties
it before a run and removes it after.  A rehearsal's carries this
process's id; otherwise the cell is the ``--workload`` this process was
started with.  ``tests/benchmark/test_bench_run_files.py`` pins this to
what ``run.py`` does.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

from benchmark.lib import trace_reduce

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORK_ROOT = os.path.join(os.path.dirname(BENCH_DIR), ".benchmark_work")


def _workload_of(argv: list) -> Optional[str]:
    for i, arg in enumerate(argv):
        if arg == "--workload" and i + 1 < len(argv):
            return argv[i + 1]
        if arg.startswith("--workload="):
            return arg.split("=", 1)[1]
    return None


def work_dir(work_root: str = WORK_ROOT, argv: Optional[list] = None) -> Optional[str]:
    """This run's work directory, or None when there is none (a reader
    called outside ``run.py``)."""
    if not os.path.isdir(work_root):
        return None
    suffix = f".rehearse{os.getpid()}"
    mine = [d for d in os.listdir(work_root) if d.endswith(suffix)]
    if len(mine) == 1:
        return os.path.join(work_root, mine[0])
    cell = _workload_of(sys.argv if argv is None else argv)
    if cell and not mine and os.path.isdir(os.path.join(work_root, cell)):
        return os.path.join(work_root, cell)
    return None


def xplane_path(work: Optional[str] = None) -> Optional[str]:
    """The newest ``.xplane.pb`` of this run's profile, if it has one."""
    work = work or work_dir()
    return work and trace_reduce.find_xplane(os.path.join(work, "profile"))


def step_scopes_path(work: Optional[str] = None) -> Optional[str]:
    """``fit``'s scope map of the step program (process 0), if the
    program wrote one (a program from before PR 23 writes none)."""
    work = work or work_dir()
    path = work and os.path.join(work, "fit", "step_scopes_p0.json")
    return path if path and os.path.isfile(path) else None
