"""Unified telemetry: dependency-free metrics registry + goodput accounting.

The reference's observability is ``tf.summary`` scalars plus a steps/sec
hook (SURVEY.md §5.1, §5.5) — enough to plot a loss curve, not enough to
answer the production question "where did the wall time go?".  This package
is the layer every perf PR proves its claims against:

- :mod:`registry` — counters, gauges, timers (p50/p95/max over a bounded
  reservoir) and a ``span(name)`` context manager.  Stdlib only, safe to
  import from any layer (it imports nothing from this repo).
- :mod:`goodput` — turns a registry snapshot into the end-of-run
  ``telemetry.json`` goodput report: compute / data-stall / checkpoint /
  compile fractions of total wall time (summing to exactly 1.0), live MFU
  from XLA-cost-analysis FLOPs, and compile-event counts so recompile
  storms are diagnosable.
- :mod:`trace` — the structured event tracer behind the fleet flight
  recorder: a bounded ring of wall-clock-stamped span/instant events
  (attached to each registry as ``registry.trace``), dumped as
  ``flight_recorder_p<i>.json`` on abnormal exits and exportable as
  Chrome-trace JSON that ``scripts/fleet_report.py`` merges across
  hosts.
- :mod:`slo` — declarative rolling-window SLO specs (metric key,
  percentile, threshold, window) evaluated with hysteresis into
  ``serve/slo_breach/<name>`` counters, ``serve/slo_margin/<name>``
  gauges, and breach/recovery trace instants.  jax-free.
- :mod:`timeseries` — the periodic atomic-append ``timeseries.jsonl``
  snapshot writer (registry snapshot + offered/served request counts,
  monotonic-stamped): the raw material for latency-vs-load curves and
  ``scripts/serving_report.py``'s throughput timeline.  jax-free.

Wiring (all via an injectable registry, defaulting to the process-global
one): ``data/pipeline.py`` records queue depth / producer wait / prefetch
fill stalls, ``core/train_loop.py::InstrumentedStep`` records compile
events + FLOPs, ``harness/checkpoint.py`` records save/restore/wait
durations, ``harness/hooks.py::TelemetryHook`` snapshots everything into
``metrics.jsonl`` + TensorBoard at the logging cadence, and
``harness/train.py::fit`` writes the final ``telemetry.json``.
"""

from distributed_tensorflow_models_tpu.telemetry.registry import (  # noqa: F401
    ASSEMBLE,
    ATTN_ROUTE_BLOCKWISE,
    ATTN_ROUTE_FUSED,
    BUFFER_FRESH,
    BUFFER_REUSED,
    CHAOS_ARMED_UNFIRED,
    CKPT_FENCE,
    CKPT_RESIZE_RESTORES,
    CKPT_RESTORE,
    CKPT_SAVE,
    CKPT_SIDECAR_FALLBACKS,
    CKPT_WAIT,
    COMPILE,
    CONSENSUS_OVERRIDES,
    DATA_WAIT,
    DISPATCH,
    FLEET_HEARTBEAT_AGE,
    FLEET_PEERS_ALIVE,
    FLEET_STEP_LAG,
    FLOPS_PER_STEP,
    FLOPS_TOTAL,
    HOOKS,
    HOOK_WALKS,
    GDN_ROUTE_PLAIN,
    HOST_QUEUE_DEPTH,
    KDA_MIXER_FUSED,
    KDA_MIXER_PLAIN,
    KDA_ROUTE_KERNEL,
    KDA_ROUTE_PLAIN,
    MOE_PLAN_KEPT,
    PIPELINE_BYTES,
    PREFETCH_FILL,
    PRODUCER_WAIT,
    REASSEMBLY_WAIT,
    REMAT_BYTES_KEPT,
    REMAT_CORES_KEPT,
    REMAT_PRODUCTS_KEPT,
    RESTARTS,
    ROLLBACKS,
    SHARD,
    SKIPPED_BATCHES,
    SSD_ROUTE_KERNEL,
    SSD_ROUTE_PLAIN,
    SSCAN_ROUTE_KERNEL,
    SSCAN_ROUTE_PLAIN,
    STARTUP_AOT_COMPILE,
    STARTUP_AOT_JOIN,
    STARTUP_AOT_LOWER,
    STARTUP_BUILD_STATE,
    STARTUP_BUILD_STEP,
    STARTUP_CACHE_HITS,
    STARTUP_CLOUD_LOGGING_IMPORTED,
    STARTUP_COMPILE_REQUESTS,
    STARTUP_COUNTERS,
    STARTUP_DATASET,
    STARTUP_FIRST_CHUNK,
    STARTUP_FIRST_DATA_WAIT,
    STARTUP_FIRST_LOSS_ROW,
    STARTUP_FIRST_STEP,
    STARTUP_GAUGES,
    STARTUP_MODULES_AT_FIT,
    STARTUP_PHASES,
    STARTUP_PIPELINE_OPEN,
    STARTUP_PROCESS_TO_FIT,
    STARTUP_RESTORE,
    STARTUP_UNATTRIBUTED,
    STEP_TIME,
    TRACE_DROPPED,
    TRACE_EVENTS,
    UNEMBED_GRAD_IN_FORWARD,
    WATCHDOG_LAST_PROGRESS,
    WORKER_BUSY,
    Counter,
    Gauge,
    MetricsRegistry,
    Timer,
    get_registry,
)
from distributed_tensorflow_models_tpu.telemetry.slo import (  # noqa: F401
    RollingWindow,
    SLOMonitor,
    SLOSpec,
    parse_slo_spec,
)
from distributed_tensorflow_models_tpu.telemetry.timeseries import (  # noqa: F401
    TimeseriesWriter,
)
from distributed_tensorflow_models_tpu.telemetry.trace import (  # noqa: F401
    NULL_TRACER,
    FlightWatcher,
    Tracer,
    chrome_trace_path,
    flight_record_path,
)
from distributed_tensorflow_models_tpu.telemetry.goodput import (  # noqa: F401
    device_count,
    device_kind,
    goodput_report,
    peak_flops,
    write_report,
)
