"""The timing hook the training runner passes to ``fit``.

It lets the warm-up steps pass, blocks on the state, stamps ``t0``,
counts steps until ``seconds`` are over, blocks again, stamps ``t1`` and
raises ``StopRequested``.  It reads no metric row, so it forces no
device sync of its own: the device is synced exactly twice, at ``t0``
and at ``t1``.  With ``steps_per_loop > 1`` it asks for a step only at
chunk ends (``check_every``), so it counts whole chunks.

With ``trace_steps > 0`` the run goes on after ``t1`` under
``jax.profiler``: start, ``settle_steps`` steps (starting the profiler
stalls the loop for seconds, first the device idles and then the input
buffers are full, so the first steps after it are not steady state),
sync, the marker, ``trace_steps`` steps, sync, stop.  The traced
sub-window lies outside the measured one, so the end-to-end readings of
a traced run are taken with the profiler off, and the rate inside the
traced sub-window is reported beside them.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

SYNC_MARKER = "benchmark_sync_marker"
# The benchmark idles this long inside the marker; the reduction drops
# device events that start in the first half of it (trace_reduce).
MARKER_IDLE_S = 0.005


def start_profiler(trace_dir: str) -> None:
    """``jax.profiler`` with what the reduction reads and no more: no
    Python function events, no HLO protos, host annotations only at the
    level ``TraceAnnotation`` writes."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.enable_hlo_proto = False
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def make_window_hook(
    *,
    warmup_steps: int,
    seconds: float,
    check_every: int = 1,
    trace_steps: int = 0,
    settle_steps: int = 10,
    trace_dir: Optional[str] = None,
    on_open: Optional[Callable] = None,
    on_close: Optional[Callable] = None,
):
    """Built in a function so that importing this module needs neither
    jax nor the program."""
    import jax

    from distributed_tensorflow_models_tpu.harness import hooks as hooklib

    class WindowHook(hooklib.Hook):
        def __init__(self):
            self.t0 = self.t1 = None
            self.step0 = self.step1 = None
            self.trace_t0 = self.trace_t1 = None
            self.trace_step0 = self.trace_step1 = None
            self.marker_wall = None
            self._settle_from = None
            self._tracing = False
            self._done = False

        def wants_step(self, step):
            if self._done:
                return False
            if self.t0 is None:
                return step >= warmup_steps
            return (step - self.step0) % check_every == 0

        def after_step(self, state, metrics, step):
            if self._done or not self.wants_step(step):
                return
            if self.t0 is None:
                jax.block_until_ready(state)
                self.step0 = step
                if on_open is not None:
                    on_open(state)
                self.t0 = time.perf_counter()
                return
            if self.t1 is None:
                if time.perf_counter() - self.t0 < seconds:
                    return
                jax.block_until_ready(state)
                self.t1 = time.perf_counter()
                self.step1 = step
                if on_close is not None:
                    on_close(state)
                if trace_steps <= 0:
                    self._done = True
                    raise hooklib.StopRequested
                self._start_trace(step)
                return
            if self.trace_step0 is None:
                if step - self._settle_from >= settle_steps:
                    jax.block_until_ready(state)
                    self._stamp_marker(step)
                return
            if step - self.trace_step0 >= trace_steps:
                jax.block_until_ready(state)
                self.trace_t1 = time.perf_counter()
                self.trace_step1 = step
                self._stop_trace()
                self._done = True
                raise hooklib.StopRequested

        def _start_trace(self, step):
            start_profiler(trace_dir)
            self._tracing = True
            self._settle_from = step

        def _stamp_marker(self, step):
            self.marker_wall = time.time()
            with jax.profiler.TraceAnnotation(SYNC_MARKER):
                time.sleep(MARKER_IDLE_S)
            self.trace_step0 = step
            self.trace_t0 = time.perf_counter()

        def _stop_trace(self):
            if self._tracing:
                self._tracing = False
                jax.profiler.stop_trace()

        def end(self, state):
            self._stop_trace()

        @property
        def steps(self) -> int:
            return self.step1 - self.step0

        @property
        def window_s(self) -> float:
            return self.t1 - self.t0

    return WindowHook()
