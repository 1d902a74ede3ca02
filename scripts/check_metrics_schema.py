#!/usr/bin/env python
"""Lint a ``metrics.jsonl`` against the documented schema (README
"Observability").

Checks, per line:

- parses as a JSON object (``NaN``/``Infinity`` literals allowed — a
  diverging loss is data, not corruption);
- carries the required keys: ``step`` (non-negative int) and ``time``
  (unix seconds, float);
- every other value is a finite-or-not *number* (the writer coerces via
  ``float()`` and skips everything it can't), never a string/list/object;
- with ``--strict-monotonic``: ``step`` is non-decreasing across rows.
  Off by default because a ``recoverable_fit`` restart legitimately
  appends rows from the restored (earlier) step after the crash-era
  rows — a healthy recovered run is not a lint failure;

- resilience counters (``restarts``, ``rollbacks``, ``skipped_batches``
  — README "Robustness"): injected as a full set, each non-negative
  (not checked monotonic: a recoverable_fit restart resets the per-run
  counters mid-file, legally);

- fleet gauges (``fleet/peers_alive``, ``fleet/step_lag``,
  ``fleet/heartbeat_age_s`` — the chief's FleetHook under a supervising
  launcher, README "Robustness" → "Multi-host"): injected as a full
  set, each non-negative; ``fleet/peers_alive`` additionally at most
  the fleet size is not checkable here (the file does not carry the
  topology), so only non-negativity is enforced;

- chaos keys (``chaos/*`` — e.g. ``chaos/armed_unfired``): any present
  value must be a non-negative number;

- checkpoint keys (``checkpoint/*`` — today ``checkpoint/fence_s``, the
  overlapped-save durability-fence share of ``checkpoint_s``): any
  present value must be a non-negative number;

- startup/MTTR gauges (``startup/restore_s``, ``startup/aot_compile_s``,
  ``startup/time_to_first_step_s`` — README "Performance", restart
  MTTR): injected as a full set by TelemetryHook, each non-negative;

- input-work keys (``assemble_s``, ``shard_s`` — the per-batch means
  of ``pipeline/assemble`` and ``pipeline/shard``): injected together,
  non-negative seconds;

- expert-routing keys (``moe_load_max_over_mean``, ``moe_aux_loss``,
  ``moe_z_loss`` — a top-k expert model's routing statistics, means
  over layers and over the interval's steps): written together,
  non-negative, and the load statistic at least 1;

- tracer accounting (``trace/*`` — ``trace/events``, ``trace/dropped``
  in telemetry.json snapshots): any present value must be a
  non-negative number;

- serving keys (``serve/*`` — TTFT/TPOT/occupancy etc., README
  "Serving"): any present value must be a non-negative number, except
  the ``serve/slo_margin/*`` gauges, which are legitimately negative
  while an SLO is out of budget;

and, across the file with ``--require-telemetry``: at least one row
carries the full telemetry key set (``data_wait_s``, ``step_time_s``,
``mfu``) — the TelemetryHook injects them together, so a partial set on
any row is always an error.

With ``--declared-coverage REGISTRY_PY`` the path is validated as a
``telemetry.json`` goodput report instead: its ``startup`` section (the
start-up timeline, README "Observability") has to carry the whole of
``STARTUP_REPORT_KEYS``, each an explicit number even where nothing
happened, none negative, with the exclusive phases adding up to
``time_to_first_step_s`` but for ``unattributed_s`` and no more cache
hits than requests; and every metric key constant
declared in the registry module (the same UPPERCASE-constant extraction
``analysis/dtmlint``'s metric-key-registry rule uses) must appear in the
report's ``metrics`` snapshot, exactly or as a ``key/...`` timer/family
expansion.  This closes the declared-vs-emitted gap from the other
side: the lint rule stops ad-hoc keys that the schema never heard of,
this mode catches declared keys that no code path ever emits (dead
constants, or a metric whose emission silently regressed).  Keys whose
emission is legitimately load- or topology-dependent are excused with
``--allow-missing PREFIX`` (repeatable); ``--only-prefix PREFIX``
restricts the declared set instead, for reports that own exactly one
subsystem's keys (a serving stats report covers the ``serve/``
constants and nothing else — together the training run's coverage
check and the serving report's ``--only-prefix serve/`` check tile the
whole registry without a blanket allow on either side).

With ``--serving-report`` the path is validated as a serving stats
report (``<workdir>/serving_stats_p<i>.json``, serving/server.py)
instead: required top-level keys, a numbers-only ``metrics`` snapshot
carrying the FULL serving key set (every counter, every serving timer's
``/count`` AND ``/p99_s`` expansions — snapshot() flattens p99 for all
timers — the server writes the full set even when idle, so an absence
is a writer regression, not light load), every ``serve/*`` value
non-negative (``serve/slo_margin/*`` excepted).  ``serve/spec_*`` and
``serve/slo_*`` are full-set-or-absent: speculation keys exist only on
a spec-on engine, SLO keys only with a monitor attached, and in both
cases one key present implies the whole family (for SLOs: a matching
``serve/slo_margin/<name>`` for every ``serve/slo_breach/<name>`` and
vice versa).

With ``--timeseries`` the path is validated as a metric time-series
(``<workdir>/timeseries_p<i>.jsonl``, telemetry/timeseries.py) instead:
every row a JSON object carrying numeric ``ts_wall``/``ts_mono``/
``offered``/``served``, ``ts_mono`` non-decreasing across rows (the
writer stamps perf_counter, single-writer), ``offered >= served >= 0``,
numbers-only rows, the serve/ non-negativity sweep, and — unless
``--no-declared`` — every non-timestamp key must be a key constant
declared in the registry module (exactly, or as a ``key/...``
expansion): a time-series carrying keys the registry never heard of is
the same drift the metric-key lint rule stops at the source.

With ``--flight-recorder`` the path is validated as a flight-recorder
dump (``<workdir>/flight_recorder_p<i>.json``, telemetry/trace.py)
instead of a metrics file: required keys (``version``, ``reason``,
``pid``, ``process_index``, ``capacity``, ``events``, ``registry``),
event count bounded by the declared ring capacity, per-event required
keys and phases, ``ts_mono`` non-decreasing per thread (the tracer's
per-thread ordering invariant), non-negative durations, and a
numbers-only registry snapshot.

Exit 0 on a clean file, 1 with one line per violation on stderr.
Wired into tier-1 via ``tests/test_telemetry.py``'s smoke run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Iterable

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

REQUIRED_KEYS = ("step", "time")
TELEMETRY_KEYS = ("data_wait_s", "step_time_s", "mfu")
# Resilience counters TelemetryHook injects alongside the telemetry keys
# (README "Robustness").  Cumulative non-negative counts within one fit
# attempt — a restart resets rollbacks/skipped_batches and bumps
# restarts, so only non-negativity (not monotonicity) is checkable
# across a whole file.  Injected as a full set, like TELEMETRY_KEYS.
RESILIENCE_KEYS = ("restarts", "rollbacks", "skipped_batches")
# Fleet-health gauges the chief's FleetHook injects together (README
# "Robustness" → "Multi-host"); like the sets above, a partial set on a
# row is always a writer bug.  Only present under a supervising launcher
# (heartbeats on), so absence across the whole file is fine.
FLEET_KEYS = ("fleet/peers_alive", "fleet/step_lag", "fleet/heartbeat_age_s")
# Prefix for chaos-drill accounting keys (chaos/armed_unfired today):
# values must be non-negative numbers wherever they appear.
CHAOS_PREFIX = "chaos/"
# Checkpoint-accounting keys (checkpoint/fence_s today): wall-time
# shares, non-negative wherever they appear.
CHECKPOINT_PREFIX = "checkpoint/"
# Tracer accounting (trace/events, trace/dropped): counts, non-negative
# wherever they appear.
TRACE_PREFIX = "trace/"
# Serving keys (serve/ttft_s etc.): latencies, counts and fractions —
# non-negative wherever they appear.  The one exception:
# serve/slo_margin/<name> gauges are threshold − observed, NEGATIVE by
# design while the SLO is out of budget.
SERVE_PREFIX = "serve/"
SLO_MARGIN_PREFIX = "serve/slo_margin/"


def _serve_negative_ok(key: str) -> bool:
    # Margins go negative on breach; the canary gauge idles at -1
    # (deploy.NO_CANARY) between canaries by contract.
    return key.startswith(SLO_MARGIN_PREFIX) or key == "serve/version/canary"
# Restart-MTTR gauges TelemetryHook injects together (README
# "Performance"); a partial set on a row is a writer bug, like the sets
# above.  Values are overlapped wall readings — non-negative seconds.
STARTUP_KEYS = (
    "startup/restore_s",
    "startup/aot_compile_s",
    "startup/time_to_first_step_s",
)


# The start-up timeline in a telemetry.json report's "startup" section
# (telemetry/registry.py STARTUP_*; harness/startup.py::Timeline): fit
# creates every one at entry, so the section is this set or the writer
# is broken.  The first is outside fit; the next six are the exclusive
# phases that add up to time_to_first_step_s but for unattributed_s.
STARTUP_PHASE_KEYS = (
    "build_state_s",
    "build_step_s",
    "restore_s",
    "dataset_s",
    "pipeline_open_s",
    "first_chunk_s",
)
STARTUP_REPORT_KEYS = (
    "process_to_fit_s",
    *STARTUP_PHASE_KEYS,
    "aot_join_s",
    "first_data_wait_s",
    "unattributed_s",
    "time_to_first_step_s",
    "first_loss_row_s",
    "aot_lower_s",
    "aot_compile_s",
    "compile_requests",
    "cache_hits",
    "modules_at_fit",
    "cloud_logging_imported",
)
# What the phases' sum may miss of time_to_first_step_s beyond the
# reported remainder, and how far below zero the remainder may read:
# float rounding of a dozen perf_counter differences.
STARTUP_ROUNDING_S = 1e-3


# The input path's work per batch (``pipeline/assemble`` and
# ``pipeline/shard`` interval means) TelemetryHook injects together
# beside ``data_wait_s``: non-negative seconds, and a partial set on a
# row is a writer bug.
INPUT_WORK_KEYS = ("assemble_s", "shard_s")
# What a model with top-k routed experts reports on its log rows
# (core/train_loop.py::lm_loss_fn from the ``moe_stats`` collection;
# TelemetryHook averages them over the interval): always the three
# together; the fullest expert holds at least the mean.
MOE_KEYS = ("moe_load_max_over_mean", "moe_aux_loss", "moe_z_loss")
# Beside them where the expert layers hold a share of the router's
# experts: the share of the assignments that fell on it, in [0, 1], and
# never without the three; with it since PR 45 the slabs of rows a layer
# worked through them in, 1 at least, and never without the share.
MOE_HELD_KEY = "moe_held_share"
MOE_HELD_SLABS_KEY = "moe_held_slabs"


def _is_number(v) -> bool:
    return isinstance(v, (int, float)) and not isinstance(v, bool)


def check_lines(
    lines: Iterable[str], *, strict_monotonic: bool = False
) -> tuple[list[str], int, int]:
    """Returns ``(errors, row_count, telemetry_row_count)``."""
    errors: list[str] = []
    prev_step = None
    rows = 0
    telemetry_rows = 0
    for i, line in enumerate(lines, 1):
        if not line.strip():
            errors.append(f"line {i}: blank line")
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"line {i}: unparseable JSON ({e})")
            continue
        if not isinstance(row, dict):
            errors.append(f"line {i}: not a JSON object")
            continue
        rows += 1
        for key in REQUIRED_KEYS:
            if key not in row:
                errors.append(f"line {i}: missing required key {key!r}")
        step = row.get("step")
        if step is not None:
            if not isinstance(step, int) or isinstance(step, bool) or step < 0:
                errors.append(
                    f"line {i}: 'step' must be a non-negative int, "
                    f"got {step!r}"
                )
            else:
                if (
                    strict_monotonic
                    and prev_step is not None
                    and step < prev_step
                ):
                    errors.append(
                        f"line {i}: step went backwards "
                        f"({prev_step} -> {step})"
                    )
                prev_step = step
        for key, value in row.items():
            if key == "step":
                continue
            if not _is_number(value):
                errors.append(
                    f"line {i}: value for {key!r} is not a number: "
                    f"{value!r}"
                )
        present = [k for k in TELEMETRY_KEYS if k in row]
        if len(present) == len(TELEMETRY_KEYS):
            telemetry_rows += 1
        elif present:
            errors.append(
                f"line {i}: partial telemetry key set {present} "
                f"(expected all of {list(TELEMETRY_KEYS)} together)"
            )
        res_present = [k for k in RESILIENCE_KEYS if k in row]
        if res_present and len(res_present) != len(RESILIENCE_KEYS):
            errors.append(
                f"line {i}: partial resilience key set {res_present} "
                f"(expected all of {list(RESILIENCE_KEYS)} together)"
            )
        for key in res_present:
            value = row[key]
            if _is_number(value) and value < 0:
                errors.append(
                    f"line {i}: resilience counter {key!r} is negative: "
                    f"{value!r}"
                )
        fleet_present = [k for k in FLEET_KEYS if k in row]
        if fleet_present and len(fleet_present) != len(FLEET_KEYS):
            errors.append(
                f"line {i}: partial fleet key set {fleet_present} "
                f"(expected all of {list(FLEET_KEYS)} together)"
            )
        for key in fleet_present:
            value = row[key]
            if _is_number(value) and value < 0:
                errors.append(
                    f"line {i}: fleet gauge {key!r} is negative: {value!r}"
                )
        startup_present = [k for k in STARTUP_KEYS if k in row]
        if startup_present and len(startup_present) != len(STARTUP_KEYS):
            errors.append(
                f"line {i}: partial startup key set {startup_present} "
                f"(expected all of {list(STARTUP_KEYS)} together)"
            )
        for key in startup_present:
            value = row[key]
            if _is_number(value) and value < 0:
                errors.append(
                    f"line {i}: startup gauge {key!r} is negative: {value!r}"
                )
        work_present = [k for k in INPUT_WORK_KEYS if k in row]
        if work_present and len(work_present) != len(INPUT_WORK_KEYS):
            errors.append(
                f"line {i}: partial input-work key set {work_present} "
                f"(expected all of {list(INPUT_WORK_KEYS)} together)"
            )
        for key in work_present:
            value = row[key]
            if _is_number(value) and value < 0:
                errors.append(
                    f"line {i}: input-work timer {key!r} is negative: "
                    f"{value!r}"
                )
        moe_present = [k for k in MOE_KEYS if k in row]
        if moe_present and len(moe_present) != len(MOE_KEYS):
            errors.append(
                f"line {i}: partial expert-routing key set {moe_present} "
                f"(expected all of {list(MOE_KEYS)} together)"
            )
        if MOE_HELD_KEY in row:
            value = row[MOE_HELD_KEY]
            if not moe_present:
                errors.append(
                    f"line {i}: {MOE_HELD_KEY!r} without the expert-routing "
                    f"keys {list(MOE_KEYS)}"
                )
            if _is_number(value) and not 0.0 <= value <= 1.0:
                errors.append(
                    f"line {i}: {MOE_HELD_KEY!r} is outside [0, 1]: {value!r}"
                )
        if MOE_HELD_SLABS_KEY in row:
            value = row[MOE_HELD_SLABS_KEY]
            if MOE_HELD_KEY not in row:
                errors.append(
                    f"line {i}: {MOE_HELD_SLABS_KEY!r} without {MOE_HELD_KEY!r}"
                )
            if _is_number(value) and value < 1.0:
                errors.append(
                    f"line {i}: {MOE_HELD_SLABS_KEY!r} is below 1: {value!r}"
                )
        for key in moe_present:
            value = row[key]
            low = 1.0 if key == "moe_load_max_over_mean" else 0.0
            if _is_number(value) and value < low:
                errors.append(
                    f"line {i}: expert-routing key {key!r} is below "
                    f"{low}: {value!r}"
                )
        for key, value in row.items():
            if not (_is_number(value) and value < 0):
                continue
            if key.startswith(CHAOS_PREFIX):
                errors.append(
                    f"line {i}: chaos key {key!r} is negative: {value!r}"
                )
            elif key.startswith(CHECKPOINT_PREFIX):
                errors.append(
                    f"line {i}: checkpoint key {key!r} is negative: "
                    f"{value!r}"
                )
            elif key.startswith(TRACE_PREFIX):
                errors.append(
                    f"line {i}: trace key {key!r} is negative: {value!r}"
                )
            elif key.startswith(SERVE_PREFIX) and not _serve_negative_ok(key):
                errors.append(
                    f"line {i}: serving key {key!r} is negative: {value!r}"
                )
    return errors, rows, telemetry_rows


# --------------------------------------------------------------------------
# Serving stats reports (serving/server.py serving_stats_p<i>.json)
# --------------------------------------------------------------------------

SERVING_REQUIRED = ("version", "process_index", "draining", "metrics")
SERVING_COUNTERS = (
    "serve/requests", "serve/tokens", "serve/completed",
    "serve/prefix_cache_hits", "serve/prefix_cache_misses",
    "serve/prefix_cache_evictions",
)
SERVING_TIMERS = (
    "serve/ttft_s", "serve/tpot_s", "serve/prefill", "serve/decode",
    "serve/queue_depth", "serve/slot_occupancy",
)
# Paged-arena gauges + the computed cache-effectiveness key; flat
# values in the snapshot, exactly like counters.
SERVING_GAUGES = (
    "serve/blocks_free", "serve/blocks_resident",
    "serve/block_fragmentation", "serve/prefix_cache_hit_rate",
)
# Tail-latency expansions — snapshot() flattens p99 beside p50/p95 for
# EVERY timer, so the serving SLO surface covers all of them.
SERVING_P99 = SERVING_TIMERS
# SLO families (telemetry/slo.py): serve/slo_breach/<name> counters and
# serve/slo_margin/<name> gauges, pre-created together per configured
# spec — so every breach name must have a margin twin and vice versa
# (full-set-or-absent, name-wise).
SLO_BREACH_PREFIX = "serve/slo_breach/"
# Speculative decoding keys: present ONLY when the engine ran spec-on
# (spec_tokens > 0 pre-creates all of them; spec-off creates none), so
# the contract is full-set-or-absent — a partial set means a writer
# regression, never light load.
SERVING_SPEC_COUNTERS = ("serve/spec_drafted", "serve/spec_accepted")
SERVING_SPEC_TIMERS = (
    "serve/spec_acceptance_rate", "serve/spec_tokens_per_dispatch",
)
SERVING_SPEC_P99 = SERVING_SPEC_TIMERS
# Disaggregated-serving keys: the server pre-creates the WHOLE family
# when it runs as a prefill or decode replica and none of it when
# monolithic, so — like speculation — the contract is
# full-set-or-absent, keyed off the report's ``role`` field when it
# carries one (reports from this version always do) and off any
# serve/ship_* key otherwise.
SERVING_SHIP_COUNTERS = (
    "serve/ship_requests", "serve/ship_bytes", "serve/ship_pages",
    "serve/fleet_prefix_hits", "serve/fleet_prefix_misses",
)
SERVING_SHIP_TIMERS = ("serve/ship",)
SERVING_SHIP_P99 = SERVING_SHIP_TIMERS
SERVING_ROLES = ("monolithic", "prefill", "decode")
# Compiled-program pins: stats() publishes the engine's compile-cache
# sizes for EVERY role (the disagg acceptance gate — a prefill replica
# must pin (n, 0), a decode replica (0, n)), so both gauges are part of
# the unconditional full set.
SERVING_COMPILED_GAUGES = ("serve/compiled_prefill", "serve/compiled_decode")
# Admission / overload keys (serving/admission.py + the scheduler):
# present ONLY when the scheduler ran with an AdmissionPolicy, which
# pre-creates serve/submitted/<class> AND serve/shed/<class> for every
# configured class — so the contract is name-paired full-set-or-absent,
# exactly like the SLO family.  The backpressure gauge and its engage
# counter are likewise a pair, and only ever appear on an
# admission-enabled report (the gate rides on the admission scheduler).
SERVING_SUBMITTED_PREFIX = "serve/submitted/"
SERVING_SHED_PREFIX = "serve/shed/"
SERVING_BACKPRESSURE_GAUGE = "serve/backpressure"
SERVING_BACKPRESSURE_ENGAGED = "serve/backpressure_engaged"
# Autoscale keys: a replica started with --fleet-file pre-creates the
# whole trio and mirrors the controller's fleet_size.json transitions
# into it; fleets without a scale controller report none of them.
SERVING_SCALE_KEYS = (
    "serve/fleet_size", "serve/scale_up", "serve/scale_down",
)
# Continuous-deployment keys (serving/deploy.py): a replica started
# with --follow-checkpoints pre-creates the swap/rollback/reject
# counters and both version gauges at follower construction — full set
# or none.  Per-version splits (serve/version/<stat>/<vid>) are created
# five-at-a-time at a version's first routing, so every sighted vid
# must carry the whole five-stat set; serve/version/acceptance_rate/
# <vid> is speculation-conditional (like serve/spec_*) and deliberately
# outside the set.
SERVING_DEPLOY_COUNTERS = (
    "serve/deploy_swaps", "serve/deploy_rollbacks",
    "serve/deploy_rejected_candidates",
)
SERVING_DEPLOY_GAUGES = ("serve/version/active", "serve/version/canary")
SERVING_VERSION_COUNTER_PREFIXES = (
    "serve/version/requests/", "serve/version/tokens/",
    "serve/version/shed/",
)
SERVING_VERSION_TIMER_PREFIXES = (
    "serve/version/ttft_s/", "serve/version/tpot_s/",
)


def check_serving_report(report) -> list[str]:
    """Violations in one serving stats report (empty list = clean)."""
    errors: list[str] = []
    if not isinstance(report, dict):
        return ["serving report is not a JSON object"]
    for key in SERVING_REQUIRED:
        if key not in report:
            errors.append(f"missing required key {key!r}")
    if errors:
        return errors
    pi = report["process_index"]
    if not isinstance(pi, int) or isinstance(pi, bool) or pi < 0:
        errors.append(
            f"'process_index' must be a non-negative int, got {pi!r}"
        )
    if not isinstance(report["draining"], bool):
        errors.append(
            f"'draining' must be a bool, got {report['draining']!r}"
        )
    snap = report["metrics"]
    if not isinstance(snap, dict):
        return errors + ["'metrics' is not an object"]
    for key, value in snap.items():
        if not _is_number(value):
            errors.append(
                f"metrics value for {key!r} is not a number: {value!r}"
            )
        elif (
            value < 0
            and key.startswith(SERVE_PREFIX)
            and not _serve_negative_ok(key)
        ):
            errors.append(f"serving key {key!r} is negative: {value!r}")
    # Full-set requirement: the server touches every serving key before
    # snapshotting, so absence = writer regression (never light load).
    for key in SERVING_COUNTERS:
        if key not in snap:
            errors.append(f"serving counter {key!r} missing")
    for key in SERVING_GAUGES:
        if key not in snap:
            errors.append(f"serving gauge {key!r} missing")
    for key in SERVING_TIMERS:
        if f"{key}/count" not in snap:
            errors.append(f"serving timer {key!r} missing (no /count)")
    for key in SERVING_P99:
        if f"{key}/p99_s" not in snap:
            errors.append(f"serving p99 expansion {key!r}/p99_s missing")
    for key in SERVING_COMPILED_GAUGES:
        if key not in snap:
            errors.append(f"compiled-program gauge {key!r} missing")
    # Disaggregation section: role field (when present) must be valid,
    # and the ship/fleet family is full-set on a disagg replica, fully
    # absent on a monolithic one.
    role = report.get("role")
    if role is not None and role not in SERVING_ROLES:
        errors.append(f"'role' must be one of {list(SERVING_ROLES)}, "
                      f"got {role!r}")
    has_ship = any(
        k.startswith(("serve/ship", "serve/fleet_prefix_")) for k in snap
    )
    disagg = role in ("prefill", "decode") if role is not None else has_ship
    if disagg:
        for key in SERVING_SHIP_COUNTERS:
            if key not in snap:
                errors.append(f"ship counter {key!r} missing")
        for key in SERVING_SHIP_TIMERS:
            if f"{key}/count" not in snap:
                errors.append(f"ship timer {key!r} missing (no /count)")
        for key in SERVING_SHIP_P99:
            if f"{key}/p99_s" not in snap:
                errors.append(f"ship p99 expansion {key!r}/p99_s missing")
    elif has_ship:
        leaked = sorted(
            k for k in snap
            if k.startswith(("serve/ship", "serve/fleet_prefix_"))
        )
        errors.append(
            f"monolithic report leaks disaggregation keys: {leaked}"
        )
    # Speculation section: any serve/spec_* key present implies the
    # whole set (counters, timers, p99 expansions); values already
    # passed the non-negativity sweep above via the serve/ prefix.
    if any(k.startswith("serve/spec_") for k in snap):
        for key in SERVING_SPEC_COUNTERS:
            if key not in snap:
                errors.append(f"speculation counter {key!r} missing")
        for key in SERVING_SPEC_TIMERS:
            if f"{key}/count" not in snap:
                errors.append(
                    f"speculation timer {key!r} missing (no /count)"
                )
        for key in SERVING_SPEC_P99:
            if f"{key}/p99_s" not in snap:
                errors.append(
                    f"speculation p99 expansion {key!r}/p99_s missing"
                )
    # SLO section: any serve/slo_* key present implies a breach counter
    # AND a margin gauge per SLO name (the monitor pre-creates them as
    # a pair; a widowed key is a writer regression).
    if any(k.startswith("serve/slo_") for k in snap):
        breach_names = {
            k[len(SLO_BREACH_PREFIX):]
            for k in snap
            if k.startswith(SLO_BREACH_PREFIX)
        }
        margin_names = {
            k[len(SLO_MARGIN_PREFIX):]
            for k in snap
            if k.startswith(SLO_MARGIN_PREFIX)
        }
        if not breach_names and not margin_names:
            errors.append(
                "serve/slo_* key present but no serve/slo_breach/<name> "
                "or serve/slo_margin/<name> family members"
            )
        for name in sorted(breach_names - margin_names):
            errors.append(
                f"SLO {name!r} has a breach counter but no "
                f"serve/slo_margin/{name} gauge"
            )
        for name in sorted(margin_names - breach_names):
            errors.append(
                f"SLO {name!r} has a margin gauge but no "
                f"serve/slo_breach/{name} counter"
            )
    # Admission section: submitted/shed class names must pair up (the
    # policy pre-creates both counters per configured class; a widowed
    # class key is a writer regression, never light load).
    sub_names = {
        k[len(SERVING_SUBMITTED_PREFIX):]
        for k in snap
        if k.startswith(SERVING_SUBMITTED_PREFIX)
    }
    shed_names = {
        k[len(SERVING_SHED_PREFIX):]
        for k in snap
        if k.startswith(SERVING_SHED_PREFIX)
    }
    for name in sorted(sub_names - shed_names):
        errors.append(
            f"priority class {name!r} has a submitted counter but no "
            f"{SERVING_SHED_PREFIX}{name} counter"
        )
    for name in sorted(shed_names - sub_names):
        errors.append(
            f"priority class {name!r} has a shed counter but no "
            f"{SERVING_SUBMITTED_PREFIX}{name} counter"
        )
    # Backpressure: gauge + engage counter together, and only on an
    # admission-enabled report; the gauge is binary.
    has_bp_gauge = SERVING_BACKPRESSURE_GAUGE in snap
    has_bp_counter = SERVING_BACKPRESSURE_ENGAGED in snap
    if has_bp_gauge != has_bp_counter:
        errors.append(
            f"backpressure keys must appear together: "
            f"{SERVING_BACKPRESSURE_GAUGE!r} "
            f"{'present' if has_bp_gauge else 'missing'}, "
            f"{SERVING_BACKPRESSURE_ENGAGED!r} "
            f"{'present' if has_bp_counter else 'missing'}"
        )
    if has_bp_gauge and not sub_names:
        errors.append(
            "backpressure keys present without any "
            "serve/submitted/<class> counters (the gate rides on an "
            "admission-enabled scheduler)"
        )
    if has_bp_gauge and snap.get(SERVING_BACKPRESSURE_GAUGE) not in (
        0, 0.0, 1, 1.0
    ):
        errors.append(
            f"backpressure gauge must be 0 or 1, got "
            f"{snap.get(SERVING_BACKPRESSURE_GAUGE)!r}"
        )
    # Autoscale section: the fleet_size gauge and both scale counters
    # are pre-created together by --fleet-file — full trio or none.
    scale_present = [k for k in SERVING_SCALE_KEYS if k in snap]
    if scale_present and len(scale_present) != len(SERVING_SCALE_KEYS):
        errors.append(
            f"partial autoscale key set {scale_present} "
            f"(expected all of {list(SERVING_SCALE_KEYS)} together)"
        )
    # Deploy section: counters + version gauges pre-created together by
    # --follow-checkpoints — full set or none (the canary gauge's -1
    # idle value already passed the negativity sweep by allowlist).
    deploy_keys = SERVING_DEPLOY_COUNTERS + SERVING_DEPLOY_GAUGES
    deploy_present = [k for k in deploy_keys if k in snap]
    if deploy_present and len(deploy_present) != len(deploy_keys):
        errors.append(
            f"partial deploy key set {deploy_present} "
            f"(expected all of {list(deploy_keys)} together)"
        )
    # Per-version splits: every sighted vid carries the whole five-stat
    # set (requests/tokens/shed counters + ttft/tpot timers) — the
    # scheduler creates them five-at-a-time at first routing, so a
    # widowed vid key is a writer regression, never light load.
    vids: set = set()
    for prefix in SERVING_VERSION_COUNTER_PREFIXES:
        vids |= {k[len(prefix):] for k in snap if k.startswith(prefix)}
    for prefix in SERVING_VERSION_TIMER_PREFIXES:
        vids |= {
            k[len(prefix):-len("/count")]
            for k in snap
            if k.startswith(prefix) and k.endswith("/count")
        }
    if vids and not deploy_present:
        errors.append(
            f"per-version keys for versions {sorted(vids)} without the "
            "deploy counter/gauge family"
        )
    for vid in sorted(vids):
        for prefix in SERVING_VERSION_COUNTER_PREFIXES:
            if f"{prefix}{vid}" not in snap:
                errors.append(
                    f"version {vid}: counter {prefix}{vid} missing"
                )
        for prefix in SERVING_VERSION_TIMER_PREFIXES:
            if f"{prefix}{vid}/count" not in snap:
                errors.append(
                    f"version {vid}: timer {prefix}{vid} missing "
                    "(no /count)"
                )
            if f"{prefix}{vid}/p99_s" not in snap:
                errors.append(
                    f"version {vid}: p99 expansion {prefix}{vid}/p99_s "
                    "missing"
                )
    return errors


def speculation_summary(snap: dict) -> str:
    """One-line speculation section for the --serving-report output:
    acceptance p50/p99 and mean tokens-per-dispatch, or the spec-off
    marker when the engine never ran with spec_tokens > 0."""
    if not any(k.startswith("serve/spec_") for k in snap):
        return "speculation off"
    drafted = int(snap.get("serve/spec_drafted", 0))
    accepted = int(snap.get("serve/spec_accepted", 0))
    return (
        f"speculation: {drafted} drafted, {accepted} accepted, "
        f"acceptance p50 "
        f"{snap.get('serve/spec_acceptance_rate/p50_s', 0.0):.3f} "
        f"p99 {snap.get('serve/spec_acceptance_rate/p99_s', 0.0):.3f}, "
        f"tokens/dispatch mean "
        f"{snap.get('serve/spec_tokens_per_dispatch/mean_s', 0.0):.2f}"
    )


# --------------------------------------------------------------------------
# Metric time-series (telemetry/timeseries.py timeseries_p<i>.jsonl)
# --------------------------------------------------------------------------

TIMESERIES_REQUIRED = ("ts_wall", "ts_mono", "offered", "served")


def check_timeseries(
    lines: Iterable[str], declared: "dict[str, str] | None" = None
) -> tuple[list[str], int]:
    """Violations in a timeseries.jsonl (``(errors, row_count)``).

    ``declared`` (key → constant name, from ``declared_metric_keys``)
    enables the declared-keys check: every non-timestamp key must be a
    declared registry key, exactly or as a ``key/...`` expansion.
    """
    errors: list[str] = []
    rows = 0
    prev_mono = None
    declared_keys = tuple(declared) if declared else ()
    for i, line in enumerate(lines, 1):
        if not line.strip():
            errors.append(f"line {i}: blank line")
            continue
        try:
            row = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"line {i}: unparseable JSON ({e})")
            continue
        if not isinstance(row, dict):
            errors.append(f"line {i}: not a JSON object")
            continue
        rows += 1
        for key in TIMESERIES_REQUIRED:
            if key not in row:
                errors.append(f"line {i}: missing required key {key!r}")
            elif not _is_number(row[key]):
                errors.append(
                    f"line {i}: {key!r} is not a number: {row[key]!r}"
                )
        mono = row.get("ts_mono")
        if _is_number(mono):
            if prev_mono is not None and mono < prev_mono:
                errors.append(
                    f"line {i}: ts_mono went backwards "
                    f"({prev_mono} -> {mono})"
                )
            prev_mono = mono
        offered, served = row.get("offered"), row.get("served")
        if _is_number(offered) and _is_number(served):
            if served < 0 or offered < 0:
                errors.append(
                    f"line {i}: offered/served negative "
                    f"({offered!r}/{served!r})"
                )
            elif served > offered:
                errors.append(
                    f"line {i}: served ({served!r}) exceeds offered "
                    f"({offered!r})"
                )
        for key, value in row.items():
            if not _is_number(value):
                errors.append(
                    f"line {i}: value for {key!r} is not a number: "
                    f"{value!r}"
                )
                continue
            if (
                value < 0
                and key.startswith(SERVE_PREFIX)
                and not _serve_negative_ok(key)
            ):
                errors.append(
                    f"line {i}: serving key {key!r} is negative: {value!r}"
                )
            if key in TIMESERIES_REQUIRED or not declared:
                continue
            if key in declared or any(
                key.startswith(d + "/") for d in declared_keys
            ):
                continue
            errors.append(
                f"line {i}: key {key!r} is not declared in the registry "
                "(nor a declared key's /... expansion)"
            )
    return errors, rows


# --------------------------------------------------------------------------
# Flight-recorder dumps (telemetry/trace.py flight_record schema)
# --------------------------------------------------------------------------

FLIGHT_REQUIRED = (
    "version", "reason", "ts_wall", "pid", "process_index", "capacity",
    "events", "registry",
)
FLIGHT_EVENT_REQUIRED = ("ts_wall", "ts_mono", "tid", "name", "ph")
FLIGHT_PHASES = ("X", "i")


def check_flight_record(record) -> list[str]:
    """Violations in one flight-recorder dump (empty list = clean)."""
    errors: list[str] = []
    if not isinstance(record, dict):
        return ["flight record is not a JSON object"]
    for key in FLIGHT_REQUIRED:
        if key not in record:
            errors.append(f"missing required key {key!r}")
    if errors:
        return errors
    if not isinstance(record["reason"], str) or not record["reason"]:
        errors.append(f"'reason' must be a non-empty string: {record['reason']!r}")
    for key in ("pid", "process_index", "capacity"):
        v = record[key]
        if not isinstance(v, int) or isinstance(v, bool) or v < 0:
            errors.append(f"{key!r} must be a non-negative int, got {v!r}")
    if isinstance(record["capacity"], int) and record["capacity"] < 1:
        errors.append("'capacity' must be >= 1")
    events = record["events"]
    if not isinstance(events, list):
        return errors + ["'events' is not a list"]
    cap = record["capacity"]
    if isinstance(cap, int) and cap >= 1 and len(events) > cap:
        errors.append(
            f"{len(events)} events exceed the declared ring capacity {cap}"
        )
    last_mono: dict = {}
    for i, e in enumerate(events):
        if not isinstance(e, dict):
            errors.append(f"event {i}: not a JSON object")
            continue
        missing = [k for k in FLIGHT_EVENT_REQUIRED if k not in e]
        if missing:
            errors.append(f"event {i}: missing keys {missing}")
            continue
        if e["ph"] not in FLIGHT_PHASES:
            errors.append(
                f"event {i}: phase {e['ph']!r} not in {list(FLIGHT_PHASES)}"
            )
        if e["ph"] == "X":
            dur = e.get("dur_s")
            if not _is_number(dur) or dur < 0:
                errors.append(
                    f"event {i}: complete event needs non-negative dur_s, "
                    f"got {dur!r}"
                )
        for key in ("ts_wall", "ts_mono"):
            if not _is_number(e[key]):
                errors.append(f"event {i}: {key!r} is not a number")
        # Per-thread monotonicity: perf_counter is monotonic and each
        # thread appends in order, so a regression means a corrupted or
        # hand-edited dump.
        tid = e["tid"]
        if _is_number(e["ts_mono"]):
            prev = last_mono.get(tid)
            if prev is not None and e["ts_mono"] < prev:
                errors.append(
                    f"event {i}: ts_mono went backwards for tid {tid} "
                    f"({prev} -> {e['ts_mono']})"
                )
            last_mono[tid] = e["ts_mono"]
    registry = record["registry"]
    if not isinstance(registry, dict):
        errors.append("'registry' is not an object")
    else:
        for key, value in registry.items():
            if not _is_number(value):
                errors.append(
                    f"registry value for {key!r} is not a number: {value!r}"
                )
            elif value < 0 and key.startswith(TRACE_PREFIX):
                errors.append(f"registry trace key {key!r} is negative")
    return errors


# --------------------------------------------------------------------------
# Declared-vs-emitted coverage (telemetry.json goodput reports)
# --------------------------------------------------------------------------


def declared_metric_keys(registry_path: str) -> dict[str, str]:
    """``{key: CONSTANT_NAME}`` declared in the registry module, via the
    same extraction dtm-lint's metric-key-registry rule trusts."""
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    from analysis.dtmlint.rules.metric_keys import declared_keys_from_source

    with open(registry_path, encoding="utf-8") as f:
        return declared_keys_from_source(f.read())


def check_startup_section(report: dict) -> list[str]:
    """The ``startup`` section of a telemetry.json report against
    ``STARTUP_REPORT_KEYS``: the whole set, numbers, none negative (the
    remainder down to rounding), phases + remainder = time to the first
    step once there was one, hits <= requests."""
    section = report.get("startup") if isinstance(report, dict) else None
    if not isinstance(section, dict):
        return ["report carries no 'startup' section object"]
    errors = [
        f"startup section lacks {key!r}"
        for key in STARTUP_REPORT_KEYS
        if key not in section
    ]
    for key, value in section.items():
        if not _is_number(value):
            errors.append(f"startup {key!r} is not a number: {value!r}")
        elif value < (-STARTUP_ROUNDING_S if key == "unattributed_s" else 0):
            errors.append(f"startup {key!r} is negative: {value!r}")
    if errors:
        return errors
    first_step = section["time_to_first_step_s"]
    covered = sum(section[k] for k in STARTUP_PHASE_KEYS)
    if first_step and abs(
        first_step - covered - section["unattributed_s"]
    ) > STARTUP_ROUNDING_S:
        errors.append(
            f"startup phases ({covered!r}) + unattributed_s "
            f"({section['unattributed_s']!r}) do not add up to "
            f"time_to_first_step_s ({first_step!r})"
        )
    if section["cache_hits"] > section["compile_requests"]:
        errors.append(
            f"startup cache_hits ({section['cache_hits']!r}) exceed "
            f"compile_requests ({section['compile_requests']!r})"
        )
    return errors


def check_declared_coverage(
    report: dict,
    declared: dict[str, str],
    allow_missing: Iterable[str] = (),
    only_prefix: Iterable[str] = (),
) -> list[str]:
    """Declared keys absent from the report's ``metrics`` snapshot.

    A key counts as emitted when it appears exactly (counters, gauges)
    or as a ``key/...`` expansion (timer stats, gauge families).
    ``only_prefix`` restricts the declared set to keys under the given
    prefixes — the positive-scope twin of ``allow_missing``, for
    reports that own one subsystem's keys (a serving stats report
    covers ``serve/`` and nothing else).
    """
    errors: list[str] = []
    snap = report.get("metrics") if isinstance(report, dict) else None
    if not isinstance(snap, dict):
        return ["report carries no 'metrics' snapshot object"]
    prefixes = tuple(allow_missing)
    only = tuple(only_prefix)
    for key in sorted(declared):
        if only and not key.startswith(only):
            continue
        if key in snap or any(k.startswith(key + "/") for k in snap):
            continue
        if prefixes and key.startswith(prefixes):
            continue
        errors.append(
            f"declared metric key {key!r} ({declared[key]}) never "
            "emitted: dead constant, or its emission regressed"
        )
    return errors


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument(
        "path", help="path to metrics.jsonl (or, with --flight-recorder, "
        "a flight_recorder_p<i>.json dump)",
    )
    p.add_argument(
        "--require-telemetry",
        action="store_true",
        help="additionally require >= 1 row with the full telemetry key "
        "set (data_wait_s, step_time_s, mfu)",
    )
    p.add_argument(
        "--strict-monotonic",
        action="store_true",
        help="flag step regressions as errors (off by default: a "
        "recoverable_fit restart legitimately rewinds the step)",
    )
    p.add_argument(
        "--flight-recorder",
        action="store_true",
        help="validate the path as a flight-recorder dump "
        "(telemetry/trace.py schema) instead of a metrics file",
    )
    p.add_argument(
        "--serving-report",
        action="store_true",
        help="validate the path as a serving stats report "
        "(serving/server.py serving_stats_p<i>.json schema) instead of "
        "a metrics file",
    )
    p.add_argument(
        "--timeseries",
        action="store_true",
        help="validate the path as a metric time-series "
        "(telemetry/timeseries.py timeseries_p<i>.jsonl schema) instead "
        "of a metrics file",
    )
    p.add_argument(
        "--registry",
        metavar="REGISTRY_PY",
        default=os.path.join(
            _REPO_ROOT, "distributed_tensorflow_models_tpu", "telemetry",
            "registry.py",
        ),
        help="with --timeseries: registry module whose declared key "
        "constants bound the row keys (default: the repo's registry.py)",
    )
    p.add_argument(
        "--no-declared",
        action="store_true",
        help="with --timeseries: skip the declared-keys check (rows from "
        "a registry with out-of-tree keys)",
    )
    p.add_argument(
        "--declared-coverage",
        metavar="REGISTRY_PY",
        help="validate the path as a telemetry.json report instead: "
        "every key constant declared in REGISTRY_PY must appear in its "
        "'metrics' snapshot",
    )
    p.add_argument(
        "--allow-missing",
        action="append",
        default=[],
        metavar="PREFIX",
        help="with --declared-coverage: excuse declared keys matching "
        "this prefix (load/topology-dependent emission); repeatable",
    )
    p.add_argument(
        "--only-prefix",
        action="append",
        default=[],
        metavar="PREFIX",
        help="with --declared-coverage: check only declared keys under "
        "this prefix (a report that owns one subsystem's keys, e.g. "
        "a serving stats report with serve/); repeatable",
    )
    args = p.parse_args(argv)
    if args.timeseries:
        try:
            with open(args.path) as f:
                lines = f.read().splitlines()
            declared = (
                None if args.no_declared
                else declared_metric_keys(args.registry)
            )
        except (OSError, ValueError, SyntaxError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        errors, rows = check_timeseries(lines, declared)
        if rows == 0:
            errors.append("no time-series rows found")
        if errors:
            for e in errors:
                print(f"{args.path}: {e}", file=sys.stderr)
            return 1
        print(
            f"{args.path}: OK ({rows} rows, ts_mono monotonic"
            + (
                ", declared-keys checked" if declared is not None
                else ""
            )
            + ")"
        )
        return 0
    if args.declared_coverage:
        try:
            with open(args.path) as f:
                report = json.load(f)
            declared = declared_metric_keys(args.declared_coverage)
        except (OSError, ValueError, SyntaxError) as e:
            print(f"error: {e}", file=sys.stderr)
            return 1
        errors = check_declared_coverage(
            report, declared, allow_missing=args.allow_missing,
            only_prefix=args.only_prefix,
        )
        if not args.only_prefix:
            # A report scoped to one subsystem's keys (a serving stats
            # file) is not a fit's and has no start-up timeline.
            errors += check_startup_section(report)
        if errors:
            for e in errors:
                print(f"{args.path}: {e}", file=sys.stderr)
            return 1
        only = tuple(args.only_prefix)
        checked = sum(
            1 for k in declared if not only or k.startswith(only)
        )
        print(
            f"{args.path}: OK ({checked} declared keys all emitted"
            + (
                f", scoped to {', '.join(only)}" if only else ""
            )
            + (
                f", {len(args.allow_missing)} allowed-missing prefixes"
                if args.allow_missing
                else ""
            )
            + ")"
        )
        return 0
    if args.serving_report:
        try:
            with open(args.path) as f:
                report = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
            return 1
        errors = check_serving_report(report)
        if errors:
            for e in errors:
                print(f"{args.path}: {e}", file=sys.stderr)
            return 1
        m = report["metrics"]
        role = report.get("role", "monolithic")
        print(
            f"{args.path}: OK (role {role}, "
            f"{int(m['serve/requests'])} requests, "
            f"{int(m['serve/tokens'])} tokens, "
            f"ttft p99 {m['serve/ttft_s/p99_s']:.4f}s, "
            f"compiled {int(m.get('serve/compiled_prefill', 0))}p/"
            f"{int(m.get('serve/compiled_decode', 0))}d; "
            f"{speculation_summary(m)})"
        )
        return 0
    if args.flight_recorder:
        try:
            with open(args.path) as f:
                record = json.load(f)
        except (OSError, ValueError) as e:
            print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
            return 1
        errors = check_flight_record(record)
        if errors:
            for e in errors:
                print(f"{args.path}: {e}", file=sys.stderr)
            return 1
        print(
            f"{args.path}: OK (reason {record['reason']!r}, "
            f"{len(record['events'])} events, "
            f"{len(record['registry'])} registry keys)"
        )
        return 0
    try:
        with open(args.path) as f:
            lines = f.read().splitlines()
    except OSError as e:
        print(f"error: cannot read {args.path}: {e}", file=sys.stderr)
        return 1
    errors, rows, telemetry_rows = check_lines(
        lines, strict_monotonic=args.strict_monotonic
    )
    if rows == 0:
        errors.append("no metric rows found")
    if args.require_telemetry and telemetry_rows == 0 and rows:
        errors.append(
            "no row carries the full telemetry key set "
            f"{list(TELEMETRY_KEYS)}"
        )
    if errors:
        for e in errors:
            print(f"{args.path}: {e}", file=sys.stderr)
        return 1
    print(
        f"{args.path}: OK ({rows} rows, {telemetry_rows} with telemetry)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
