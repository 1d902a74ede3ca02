"""What a runner hands back to ``run.py``."""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class RunOptions:
    seed: int
    seconds: float
    trace: bool
    devices: list  # the jax devices the cell runs on
    workdir: str  # emptied before and removed after the run
    since_start: object  # () -> seconds since the process started
    compiles: object  # CompileCounter


@dataclasses.dataclass
class RunResult:
    checks: dict  # name -> bool; ``correct`` is their conjunction
    attempted: int
    failed: int
    end_to_end: dict  # metric name -> value (every one the runner measures)
    ctx: dict  # what the per-layer readers read from
    notes: dict = dataclasses.field(default_factory=dict)  # printed on an earlier line

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(self.checks.values())
