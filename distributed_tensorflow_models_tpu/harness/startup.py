"""Cold-start acceleration: persistent compile cache + overlapped AOT.

A supervisor relaunch (``launch.supervise_local``) pays two dominant
serial costs before the first training step: the checkpoint restore and
the first XLA compile of the train-step program.  Both are attackable
without touching training semantics:

- **Persistent compilation cache** (:func:`apply_compile_cache`): the
  jax on-disk cache, placed by one helper for every entry point — a
  relaunch of the same config deserializes the train-step program
  instead of recompiling it.  ``JAX_COMPILATION_CACHE_DIR`` places it
  from outside; otherwise ``ExperimentConfig.xla_cache_dir`` names a
  path, ``None`` means the fixed ``<checkout>/.xla_cache``, and ``""``
  disables.
- **AOT compile overlapped with restore** (:class:`AotTrainStep`): the
  train-step program is ``.lower().compile()``'d on a background thread
  *while the main thread restores the checkpoint*, against input specs
  derived from the config (:func:`abstract_batch` — the exact global
  shapes/shardings ``DevicePrefetcher``/``BatchStacker`` will produce).
  The compiled executable is bit-identical to what the jit path would
  build (same program, same compiler — pinned in
  ``tests/test_startup.py``), and the instrumented step uses it only
  when the live batch signature matches, falling back to the ordinary
  jit call otherwise — a wrong guess costs a wasted background compile,
  never a wrong program.

Telemetry: the start-up timeline (:class:`Timeline`, README
"Observability").  ``fit`` cuts its main thread's time from the
process's start to the end of the first loop iteration into exclusive
phases on ``perf_counter``, each a ``startup/<phase>_s`` gauge and a
``startup/<phase>`` span in the run's event ring, through the one
helper :func:`stamp`; the AOT thread stamps ``startup/aot_compile_s``
(the full ``lower().compile()`` duration, mostly hidden behind the
restore) and ``startup/aot_lower_s`` (its tracing-and-lowering part)
the same way, from its own thread, so the export shows them
overlapping the phases.  ``startup/aot_join_s`` is the *non-overlapped
remainder* the first step actually blocked on; the same wait, plus the
first dispatch, is the first record of the ``train/compile`` timer (the
first AOT use is accounted as the run's compile event, mirroring how a
persistent-cache hit still records a compile event today).
``startup/compile_requests`` and ``startup/cache_hits`` say whether the
start was warm, ``startup/modules_at_fit`` what the imports before
``fit`` brought into the process (:func:`import_orbax` keeps orbax's
unused cloud-logging stack out of it).  The goodput report surfaces all
of it as its ``startup`` section and ``launch.py`` reads the fleet-side
equivalent off the heartbeat files.
"""

from __future__ import annotations

import logging
import os
import sys
import threading
import time
from typing import Any, Optional

from distributed_tensorflow_models_tpu import telemetry

log = logging.getLogger("dtm")

PyTree = Any


# --------------------------------------------------------------------------
# Persistent compilation cache
# --------------------------------------------------------------------------

# Cache programs costing >= 0.5 s to compile, and let XLA cache its
# internal artifacts too.
_MIN_COMPILE_TIME_S = 0.5

# Where the cache lives when nothing outside places it: a fixed,
# git-ignored directory at the root of the checkout, derived from this
# package's location.  The directory is part of the cache key (XLA's own
# caches are addressed by path), so it must not move between runs —
# never a workdir, a temp name, a pid or a time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".xla_cache",
)

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def configured_cache_dir() -> Optional[str]:
    """The process's currently configured jax compilation cache dir (or
    None)."""
    import jax

    return jax.config.jax_compilation_cache_dir


def apply_compile_cache(xla_cache_dir: Optional[str] = None) -> Optional[str]:
    """The one place the persistent compilation cache is placed; returns
    the active cache dir (None = disabled).  ``fit``, the serving worker,
    ``chip_smoke.py`` and ``tests/conftest.py`` all come through here.

    Resolution: ``""`` disables the cache.  Otherwise, when
    ``JAX_COMPILATION_CACHE_DIR`` is set the cache stays there — whoever
    runs the program placed it, and an ``xla_cache_dir`` that disagrees
    is ignored with a warning.  Otherwise an explicit ``xla_cache_dir``
    is used as-is, and ``None`` means :data:`DEFAULT_CACHE_DIR`.

    Must run before the first trace of the run (``fit`` calls it before
    ``build_state``, whose ``model.init`` is the first compile).
    """
    import jax

    _count_cache_events()
    if xla_cache_dir == "":
        jax.config.update("jax_compilation_cache_dir", None)
        log.info("persistent XLA compilation cache disabled")
        return None
    placed = os.environ.get(CACHE_DIR_ENV)
    if placed and xla_cache_dir and xla_cache_dir != placed:
        log.warning(
            "%s=%s is set; ignoring xla_cache_dir=%s",
            CACHE_DIR_ENV, placed, xla_cache_dir,
        )
    path = placed or xla_cache_dir or DEFAULT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs", _MIN_COMPILE_TIME_S
    )
    jax.config.update("jax_persistent_cache_enable_xla_caches", "all")
    # jax leaves instruction metadata (op_name: module paths, named
    # scopes) out of the cache key by default, so an executable cached
    # before a scope was renamed would be served afterwards with the old
    # names in its text — and the scope map (telemetry/scopes.py) would
    # carry them into the trace's split.  With the metadata in the key a
    # renamed scope is another program: it compiles once, like any
    # change to the step.  Metadata also holds source locations, by
    # default the whole Python traceback of every operation, callers
    # included: the same step lowered from another call site (the AOT
    # thread, the jit call, the FLOP count's and the scope map's
    # lowerings) would then be another key and compile again.  One frame
    # per location (the line that made the operation; the name stack
    # that op_name is built from stays whole) keeps the key a function
    # of the program's own code; an edit that moves traced lines
    # compiles once.
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_traceback_in_locations_limit", 1)
    log.info("persistent XLA compilation cache at %s", path)
    return path


# jax's monitoring events for the persistent cache: a request is every
# compile that consulted it, a hit every one it answered.
_COMPILE_REQUEST_EVENT = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_cache_events_lock = threading.Lock()
_cache_events_counted = False


def _count_cache_events() -> None:
    """Install, once a process, the listener that counts the persistent
    cache's requests and hits into the process-global registry
    (``startup/compile_requests``, ``startup/cache_hits``).  A
    :class:`Timeline` copies what its own start-up raised, as ``fit``
    does for the ops' trace-time counters, so a second ``fit`` in the
    process neither listens twice nor counts the first one's."""
    global _cache_events_counted
    with _cache_events_lock:
        if _cache_events_counted:
            return
        _cache_events_counted = True
    import jax.monitoring

    shared = telemetry.get_registry()
    by_event = {
        _COMPILE_REQUEST_EVENT: shared.counter(
            telemetry.STARTUP_COMPILE_REQUESTS
        ),
        _CACHE_HIT_EVENT: shared.counter(telemetry.STARTUP_CACHE_HITS),
    }

    def on_event(event: str, **_) -> None:
        counter = by_event.get(event)
        if counter is not None:
            # The main thread and the AOT thread both compile.
            with _cache_events_lock:
                counter.inc()

    jax.monitoring.register_event_listener(on_event)


def cache_entry_count(cache_dir: Optional[str]) -> int:
    """Number of files under the cache dir (0 when unset/missing) — the
    before/after delta is the cache-hit signal for the first compile."""
    if not cache_dir or not os.path.isdir(cache_dir):
        return 0
    total = 0
    for _, _, files in os.walk(cache_dir):
        total += len(files)
    return total


# --------------------------------------------------------------------------
# orbax without its cloud-logging stack
# --------------------------------------------------------------------------

# What orbax's optional cloud logger pulls in: the Google Cloud Logging
# client with its gRPC, protobuf and OpenTelemetry stack, some 600
# modules and most of the time ``import orbax.checkpoint`` takes.
_REFUSED_IMPORT = "google.cloud.logging"


def import_orbax():
    """``orbax.checkpoint``, imported without the cloud-logging stack:
    the one place this program imports it.

    ``orbax/checkpoint/logging/__init__.py`` imports its ``CloudLogger``
    inside ``try: ... except ImportError: pass``: orbax treats
    ``google.cloud.logging`` as optional, nothing else in orbax names the
    class, and this program never logs to the cloud.  So for the length
    of the import, and no longer, ``sys.modules["google.cloud.logging"]``
    is ``None``, which makes ``import google.cloud.logging`` raise
    ``ModuleNotFoundError``; orbax takes that as "not installed" and
    ``ocp.logging.CloudLogger`` (with ``CloudLoggerOptions``) is then
    absent, everything else as ever.  ``sys.modules`` is put back in a
    ``finally``, so a later ``import google.cloud.logging`` by anyone
    works.  An orbax that imports the stack unconditionally fails the
    refused import; it is then imported plainly, stack and all
    (``startup/cloud_logging_imported`` reads 1).  Nothing is refused
    where either module is already in the process (a caller imported it
    first): the import is then a lookup.

    The refusal is process-wide while it lasts: another thread's own
    ``import google.cloud.logging`` in that window is refused too.
    ``fit``'s import runs at ``harness/checkpoint.py``'s module import,
    before it starts a thread; a process whose threads import Google's
    client libraries themselves calls this before it starts them.
    """
    if not (
        "orbax.checkpoint" in sys.modules or _REFUSED_IMPORT in sys.modules
    ):
        sys.modules[_REFUSED_IMPORT] = None
        try:
            import orbax.checkpoint  # noqa: F401
        except ImportError:
            pass  # needed after all, or no orbax: the plain import says
        finally:
            del sys.modules[_REFUSED_IMPORT]
    import orbax.checkpoint as ocp

    return ocp


# --------------------------------------------------------------------------
# The start-up timeline
# --------------------------------------------------------------------------

_T_IMPORT = time.perf_counter()


def seconds_since_process_start() -> float:
    """From the kernel's record of when this process started (Linux:
    ``/proc/self/stat`` field 22 against ``/proc/uptime``, to the
    kernel's clock tick); from this module's import where ``/proc`` is
    not there."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return uptime - start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _T_IMPORT


def stamp(
    registry: telemetry.MetricsRegistry,
    key: str,
    t0: float,
    t1: Optional[float] = None,
    *,
    args: Optional[dict] = None,
    ts_wall: Optional[float] = None,
) -> float:
    """The one way a start-up duration is recorded: the ``perf_counter``
    interval ``t0`` to ``t1`` (default: now) becomes the gauge ``key``
    (``startup/<name>_s``) and the complete event ``startup/<name>`` in
    the registry's event ring, from the calling thread.  Returns
    ``t1``, where the next phase starts."""
    if t1 is None:
        t1 = time.perf_counter()
    registry.gauge(key).set(t1 - t0)
    registry.trace.complete(
        key[: -len("_s")], t1 - t0, ts_mono=t0, ts_wall=ts_wall, args=args
    )
    return t1


class Timeline:
    """``fit``'s main thread from the process's start to its first loss
    row, as the ``startup/*`` gauges and spans (``telemetry/registry.py``
    says what each holds).

    Built at ``fit`` entry: creates every start-up gauge and counter, so
    that each is in ``telemetry.json`` with an explicit zero where
    nothing happened, and stamps ``startup/process_to_fit_s``.  Each
    :meth:`mark` then closes the phase that began where the last one
    ended, so the phases are exclusive and contiguous by construction
    and a new one cannot overlap its neighbours; they are cut at
    statement boundaries because they span ``fit``'s three guarded
    blocks, which no ``with`` could.  No call waits for the device.
    """

    def __init__(self, registry: telemetry.MetricsRegistry, t_fit: float):
        self._registry = registry
        self.t_fit = t_fit
        self._t = t_fit
        # One wall-clock origin for the main thread's spans, so that
        # they tile in the export as they do on perf_counter.
        self._wall0 = time.time() - time.perf_counter()
        for key in telemetry.STARTUP_GAUGES:
            registry.gauge(key)
        for key in telemetry.STARTUP_COUNTERS:
            registry.counter(key)
        registry.gauge(telemetry.STARTUP_MODULES_AT_FIT).set(len(sys.modules))
        registry.gauge(telemetry.STARTUP_CLOUD_LOGGING_IMPORTED).set(
            _REFUSED_IMPORT in sys.modules
        )
        self._cache0 = self._cache_counts()
        before_fit = seconds_since_process_start() - (
            time.perf_counter() - t_fit
        )
        self._stamp(
            telemetry.STARTUP_PROCESS_TO_FIT, t_fit - before_fit, t_fit
        )

    @staticmethod
    def _cache_counts() -> tuple[float, ...]:
        shared = telemetry.get_registry()
        return tuple(
            shared.counter(key).value for key in telemetry.STARTUP_COUNTERS
        )

    def _stamp(self, key: str, t0: float, t1: Optional[float] = None):
        return stamp(
            self._registry, key, t0, t1, ts_wall=self._wall0 + t0
        )

    def mark(self, key: str) -> None:
        """End the phase ``key`` here; the next begins."""
        self._t = self._stamp(key, self._t)

    def first_chunk_done(self) -> None:
        """The end of the first loop iteration: closes
        ``startup/first_chunk_s``, stamps ``startup/time_to_first_step_s``
        (``fit`` entry to here), what the phases leave of it, the
        iteration's wait for its first batch and what the persistent
        cache was asked and answered since ``fit`` entry."""
        reg = self._registry
        self.mark(telemetry.STARTUP_FIRST_CHUNK)
        reg.gauge(telemetry.STARTUP_FIRST_STEP).set(
            time.perf_counter() - self.t_fit
        )
        reg.gauge(telemetry.STARTUP_FIRST_DATA_WAIT).set(
            reg.timer(telemetry.DATA_WAIT).total
        )
        reg.gauge(telemetry.STARTUP_UNATTRIBUTED).set(
            reg.gauge(telemetry.STARTUP_FIRST_STEP).value
            - sum(reg.gauge(k).value for k in telemetry.STARTUP_PHASES)
        )
        for key, now, then in zip(
            telemetry.STARTUP_COUNTERS, self._cache_counts(), self._cache0
        ):
            reg.counter(key).inc(now - then)

    def first_loss_row(self) -> None:
        """The end of the first hook walk that fetched a loss row: a
        gauge and an instant (as a span it would lie over every phase)."""
        self._registry.gauge(telemetry.STARTUP_FIRST_LOSS_ROW).set(
            time.perf_counter() - self.t_fit
        )
        self._registry.trace.instant("startup/first_loss_row")


# --------------------------------------------------------------------------
# Config-derived input specs (must mirror the live pipeline exactly)
# --------------------------------------------------------------------------


def _leaf_spec(mesh, shape, dtype, seq_dim):
    """ShapeDtypeStruct with the sharding ``sharding.shard_batch`` gives
    this leaf (leading data axis; ``seq`` on ``seq_dim`` when divisible)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributed_tensorflow_models_tpu.core import sharding as shardlib
    from distributed_tensorflow_models_tpu.core.mesh import AxisNames

    n_seq = mesh.shape[AxisNames.SEQ]
    if (
        seq_dim is not None
        and n_seq > 1
        and len(shape) > seq_dim
        and shape[seq_dim] % n_seq == 0
    ):
        axes = [AxisNames.DATA] + [None] * (len(shape) - 1)
        axes[seq_dim] = AxisNames.SEQ
        sharding = NamedSharding(mesh, P(*axes))
    else:
        sharding = shardlib.batch_sharding(mesh, len(shape))
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def abstract_batch(cfg, mesh, seq_dim=None) -> Optional[PyTree]:
    """Abstract (shape/dtype/sharding) pytree matching the batches
    ``DevicePrefetcher`` will hand the train step for ``cfg``, or None
    when the dataset's batch structure is unknown (AOT then stays off —
    the jit path is always correct).  Shapes are the *global* batch: the
    prefetcher assembles per-process slices into one global array."""
    import jax.numpy as jnp

    b = cfg.global_batch_size
    if cfg.task == "lm":
        if cfg.dataset != "ptb":
            return None
        shape = (b, cfg.num_steps)
        return {
            "inputs": _leaf_spec(mesh, shape, jnp.int32, seq_dim),
            "targets": _leaf_spec(mesh, shape, jnp.int32, seq_dim),
        }
    if cfg.dataset not in (
        "mnist", "cifar10", "imagenet", "imagenet_synthetic"
    ):
        return None
    size = cfg.image_size
    channels = 3 if size > 28 else 1
    return {
        "image": _leaf_spec(
            mesh, (b, size, size, channels), jnp.float32, seq_dim
        ),
        "label": _leaf_spec(mesh, (b,), jnp.int32, seq_dim),
    }


def stacked_batch(batch: PyTree, k: int) -> PyTree:
    """The K-stacked chunk spec for the fused multi-step program: leading
    length-``k`` axis, replicated across it (``P(None, <row spec>)``) —
    the exact layout ``BatchStacker`` emits."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    def one(leaf):
        sharding = NamedSharding(
            leaf.sharding.mesh, P(None, *tuple(leaf.sharding.spec))
        )
        return jax.ShapeDtypeStruct(
            (k, *leaf.shape), leaf.dtype, sharding=sharding
        )

    return jax.tree.map(one, batch)


def dominant_chunk_len(cfg, nproc: int = 1) -> int:
    """The chunk length most ``fit`` chunks will have under ``cfg`` —
    what the AOT compiler targets.  Mirrors ``train._chunk_len``'s
    config-deterministic shrink triggers (log cadence, train_steps, the
    step-cadence checkpoint, the multi-host preemption poll); clock-due
    and user-hook boundaries can still produce other lengths, which
    simply compile lazily on the jit path as today."""
    k = max(1, min(int(cfg.steps_per_loop), int(cfg.train_steps)))
    if cfg.log_every_steps and cfg.log_every_steps > 0:
        k = min(k, int(cfg.log_every_steps))
    if cfg.checkpoint_every_steps:
        k = min(k, int(cfg.checkpoint_every_steps))
    if nproc > 1:
        from distributed_tensorflow_models_tpu.harness.config import (
            PREEMPT_POLL_STEPS_DEFAULT,
        )

        k = min(
            k,
            max(1, int(cfg.preempt_poll_steps or PREEMPT_POLL_STEPS_DEFAULT)),
        )
    return max(1, k)


# --------------------------------------------------------------------------
# Background AOT compile
# --------------------------------------------------------------------------


class AotTrainStep:
    """Ahead-of-time compile of one train-step program on a daemon
    thread, started while the caller restores a checkpoint.

    ``jit_fn`` is the very jit callable ``fit`` will drive (so the
    program is identical by construction); ``example_args`` the
    ``(state, batch, rng)`` it will be called with — a concrete template
    state (avals only are used; the restored state is re-placed to the
    same layout) plus the abstract batch spec.  ``acquire(sig)`` hands
    the executable to the instrumented step when the live batch
    signature matches the spec'd one, blocking on the thread if the
    compile is still in flight — that blocked remainder is the only
    cold-start cost the overlap failed to hide, and the caller accounts
    it (plus the first dispatch) as the run's compile event.

    Only a batch-signature mismatch falls back to the jit path.  A
    failure of the compile itself (a trace-time error, a program the
    device's compiler refuses) is re-raised from ``acquire`` on the main
    thread: the jit path would compile the same program and fail the
    same way, later and less legibly.
    """

    def __init__(
        self,
        jit_fn,
        example_args: tuple,
        *,
        registry: Optional[telemetry.MetricsRegistry] = None,
        cache_dir: Optional[str] = None,
        label: str = "train-step",
    ):
        self._fn = jit_fn
        self._args = example_args
        self._registry = (
            registry if registry is not None else telemetry.get_registry()
        )
        self._cache_dir = cache_dir
        self._label = label
        self._sig = self.signature(example_args[1])
        self._exe = None
        self._error: Optional[BaseException] = None
        self._disabled = False
        self._used = False
        self._thread = threading.Thread(
            target=self._compile, name="aot-compile", daemon=True
        )

    @staticmethod
    def signature(batch) -> tuple:
        """Leaf (shape, dtype) signature — the same format
        ``InstrumentedStep._signature`` computes for live batches."""
        import jax

        return tuple(
            (tuple(leaf.shape), str(leaf.dtype))
            for leaf in jax.tree_util.tree_leaves(batch)
        )

    def start(self) -> "AotTrainStep":
        self._thread.start()
        return self

    def _compile(self) -> None:
        t0, wall = time.perf_counter(), time.time()  # both events' start
        entries_before = cache_entry_count(self._cache_dir)
        try:
            lowered = self._fn.lower(*self._args)
            # Tracing and lowering apart from the compile (or cache
            # read) that follows: the part of a warm start no cache
            # shortens.
            stamp(
                self._registry, telemetry.STARTUP_AOT_LOWER, t0,
                args={"label": self._label}, ts_wall=wall,
            )
            self._exe = lowered.compile()
        except BaseException as e:  # noqa: BLE001 — re-raised by acquire()
            self._error = e
            return
        finally:
            dt = time.perf_counter() - t0
            # Full background duration; the goodput report shows it
            # beside (not inside) the exclusive wall split — only the
            # acquire() remainder is wall the main thread lost.  Traced
            # from THIS thread, so the flight-recorder timeline shows the
            # compile overlapping the main thread's restore span — the
            # overlap is the whole point of the design, and the trace is
            # where it's visible.
            stamp(
                self._registry, telemetry.STARTUP_AOT_COMPILE, t0, t0 + dt,
                args={"label": self._label, "ok": self._error is None},
                ts_wall=wall,  # the export orders a thread's events by it
            )
        new_entries = cache_entry_count(self._cache_dir) - entries_before
        if self._cache_dir is None:
            cache_note = "persistent cache off"
        elif new_entries > 0:
            cache_note = f"persistent cache MISS ({new_entries} new entries)"
        else:
            # No new entries: a hit — or a program under the cache's
            # min-compile-time floor, which costs the same either way.
            cache_note = "persistent cache hit (no new entries)"
        log.info(
            "AOT %s compile finished in %.2fs (%s)", self._label, dt,
            cache_note,
        )

    def acquire(self, sig: tuple):
        """``(executable, first_use)`` when ``sig`` matches the compiled
        program (blocking on an in-flight compile), else ``(None,
        False)``."""
        if self._disabled or sig != self._sig:
            return None, False
        if self._thread.is_alive():
            # The non-overlapped remainder: wall the main thread actually
            # lost to the compile.  Traced separately from the compile
            # span so the timeline shows hidden vs. paid cold-start cost.
            t0 = time.perf_counter()
            self._thread.join()
            stamp(
                self._registry, telemetry.STARTUP_AOT_JOIN, t0,
                args={"label": self._label},
            )
        if self._error is not None:
            raise self._error
        if self._exe is None:  # thread never ran (start() skipped)
            self._disabled = True
            return None, False
        first, self._used = (not self._used), True
        return self._exe, first

    def executable(self, sig: tuple):
        """The compiled program if this handle serves ``sig``, else None.
        No first-use accounting: the instrumented step reads the
        program's cost analysis through this after its first call, by
        which time ``acquire`` has already waited for the compile."""
        if self._disabled or sig != self._sig:
            return None
        if self._thread.is_alive():
            self._thread.join()
        return self._exe

    def disable(self) -> None:
        """Stop offering the executable (the instrumented step calls this
        after a failed AOT dispatch so every later call goes via jit)."""
        self._disabled = True

    def join(self, timeout: Optional[float] = None) -> None:
        """Wait for the background thread (teardown hygiene: an XLA
        compile cannot be cancelled, so an aborted fit must reap the
        thread rather than leak it into the caller)."""
        if self._thread.is_alive():
            self._thread.join(timeout)
            if self._thread.is_alive():
                log.warning(
                    "AOT %s compile still running after %.0fs teardown "
                    "join; leaving the daemon thread to finish",
                    self._label, timeout or 0.0,
                )
