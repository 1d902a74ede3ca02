"""From a profiler trace (``.xplane.pb``) to numbers.

The one reduction every PR's traced run goes through, so that no PR
that claims a gain can change how its numbers are computed.  Read with
nothing but jax (``jax.profiler.ProfileData``).

What a TPU trace looks like (recorded on a v5e, PR 22): one plane per
chip named ``/device:TPU:<n>``, whose line ``XLA Ops`` holds one event
per executed HLO instruction (the core runs them one after another),
``XLA Modules`` one event per executed program, and ``Async XLA Ops``
the spans from the start to the completion of asynchronous
instructions (copies, collectives).  Host threads are lines of the
plane ``/host:CPU``; a ``jax.profiler.TraceAnnotation`` shows up there
under its own name.  All stamps are nanoseconds from the start of the
profile.  Device and host stamps are aligned by the profiler only to
within a millisecond or two (the toy trace shows a program on the
device 1.2 ms before the host call that launched it), so idle gaps
are attributed to host spans only by overlap, and short gaps are not
attributed at all.

Definitions:

- busy: the union of the ``XLA Ops`` intervals of one chip;
- window: from the first device event to the end of the last, over
  all chips;
- idle share: 1 - (busy averaged over chips) / window;
- collective time: the union of the intervals of collective
  instructions (all-reduce, all-gather, reduce-scatter, all-to-all,
  collective-permute, on either line) on chip 0;
- exposed collective time: the part of that union during which no
  other instruction runs on chip 0.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Iterable, Optional

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
)
# Gaps shorter than this are inside the profiler's own alignment error
# between device and host stamps; they are listed but not attributed.
MIN_ATTRIBUTED_GAP_S = 2e-3

Intervals = np.ndarray  # shape [n, 2], seconds, start < end


def find_xplane(trace_dir: str) -> Optional[str]:
    paths = sorted(
        glob.glob(
            os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
        )
    )
    return paths[-1] if paths else None


def op_name(event_name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``fusion.12``."""
    name = event_name.split(" = ", 1)[0].strip()
    return name[1:] if name.startswith("%") else name


def op_kind(name: str) -> str:
    """``fusion.12`` -> ``fusion``: instructions numbered apart are one
    row of the breakdown, so that it survives a recompilation."""
    return re.sub(r"[.\d]+$", "", name) or name


def union(intervals: Intervals) -> Intervals:
    """Merged, sorted, non-overlapping intervals."""
    if len(intervals) == 0:
        return np.zeros((0, 2))
    xs = intervals[np.argsort(intervals[:, 0])]
    ends = np.maximum.accumulate(xs[:, 1])
    # A new run starts where a start lies beyond every earlier end.
    new = np.concatenate([[True], xs[1:, 0] > ends[:-1]])
    starts = xs[new, 0]
    last = np.concatenate([np.nonzero(new)[0][1:] - 1, [len(xs) - 1]])
    return np.stack([starts, ends[last]], axis=1)


def total(intervals: Intervals) -> float:
    return float(np.sum(intervals[:, 1] - intervals[:, 0])) if len(intervals) else 0.0


def subtract(a: Intervals, b: Intervals) -> Intervals:
    """The part of the merged intervals ``a`` not covered by the merged
    intervals ``b``."""
    out = []
    j = 0
    for start, end in a:
        cur = start
        while j < len(b) and b[j, 1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k, 0] < end:
            if b[k, 0] > cur:
                out.append((cur, b[k, 0]))
            cur = max(cur, b[k, 1])
            k += 1
        if cur < end:
            out.append((cur, end))
    return np.array(out).reshape(-1, 2)


def self_times(spans: Intervals) -> np.ndarray:
    """Each interval's duration less that of the intervals nested
    directly inside it.  A ``while`` or ``conditional`` instruction is
    on the line for as long as its body runs, and the body's
    instructions are on the same line under their own names: without
    this the loop's time would be counted twice."""
    n = len(spans)
    out = spans[:, 1] - spans[:, 0] if n else np.zeros((0,))
    out = out.copy()
    order = np.lexsort((-spans[:, 1], spans[:, 0])) if n else []
    stack: list = []  # indices of the open enclosing intervals
    for i in order:
        while stack and spans[stack[-1], 1] <= spans[i, 0]:
            stack.pop()
        if stack and spans[i, 1] <= spans[stack[-1], 1]:
            out[stack[-1]] -= spans[i, 1] - spans[i, 0]
        stack.append(i)
    return np.maximum(out, 0.0)


def gaps(busy: Intervals) -> Intervals:
    """The idle intervals between merged busy intervals."""
    if len(busy) < 2:
        return np.zeros((0, 2))
    return np.stack([busy[:-1, 1], busy[1:, 0]], axis=1)


def _line_events(line) -> tuple[list[str], Intervals]:
    names, spans = [], []
    for e in line.events:
        names.append(e.name)
        spans.append((e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9))
    return names, np.array(spans).reshape(-1, 2)


def read_planes(data, host_names: Optional[set] = None) -> dict:
    """``{"devices": {index: {line name: (names, intervals)}},
    "host": [(name, start_s, end_s)]}`` from a ``ProfileData``.  With
    ``host_names`` only host events of those names are kept (a traced
    window holds hundreds of thousands the reduction never reads)."""
    devices: dict = {}
    host: list = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE):
                    lines[line.name] = _line_events(line)
            devices[int(m.group(1))] = lines
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("$"):
                        continue  # the Python tracer's function events
                    if host_names is not None and e.name not in host_names:
                        continue
                    host.append(
                        (
                            e.name,
                            e.start_ns * 1e-9,
                            (e.start_ns + e.duration_ns) * 1e-9,
                        )
                    )
    return {"devices": devices, "host": host}


def attribute_gap(
    gap: tuple[float, float], spans: Iterable[tuple[str, float, float]]
) -> str:
    """The name of the host span that covers most of ``gap``."""
    if gap[1] - gap[0] < MIN_ATTRIBUTED_GAP_S:
        return "unattributed"
    best, best_overlap = "unattributed", 0.0
    for name, start, end in spans:
        overlap = min(end, gap[1]) - max(start, gap[0])
        if overlap > best_overlap:
            best, best_overlap = name, overlap
    return best


def clip_devices(devices: dict, start_s: float) -> dict:
    """The device events that start at or after ``start_s``."""
    out = {}
    for index, lines in devices.items():
        out[index] = {}
        for line, (names, spans) in lines.items():
            keep = spans[:, 0] >= start_s if len(spans) else np.zeros((0,), bool)
            out[index][line] = (
                [n for n, k in zip(names, keep) if k], spans[keep]
            )
    return out


def reduce_planes(
    planes: dict,
    *,
    host_spans: Iterable[tuple[str, float, float]] = (),
    start_s: Optional[float] = None,
    top_ops: int = 10,
    top_gaps: int = 5,
) -> Optional[dict]:
    """The summary every per-layer reader of the trace works from, or
    None when no operation ran on a device.  ``host_spans`` are
    ``(name, start_s, end_s)`` on the trace's own clock; with
    ``start_s`` only device events from that instant on count."""
    devices = planes["devices"]
    if start_s is not None:
        devices = clip_devices(devices, start_s)
    per_chip_busy = {}
    for index, lines in devices.items():
        if OPS_LINE in lines and len(lines[OPS_LINE][1]):
            per_chip_busy[index] = union(lines[OPS_LINE][1])
    if not per_chip_busy:
        return None
    first = min(b[0, 0] for b in per_chip_busy.values())
    last = max(b[-1, 1] for b in per_chip_busy.values())
    window_s = last - first
    busy_s = float(np.mean([total(b) for b in per_chip_busy.values()]))

    chip0 = min(per_chip_busy)
    names, spans = devices[chip0][OPS_LINE]
    short = [op_name(n) for n in names]
    is_coll = np.array([bool(COLLECTIVE.match(n)) for n in short])
    coll_spans = [spans[is_coll]]
    if ASYNC_LINE in devices[chip0]:
        a_names, a_spans = devices[chip0][ASYNC_LINE]
        a_coll = np.array(
            [bool(COLLECTIVE.match(op_name(n))) for n in a_names], bool
        )
        if len(a_spans):
            coll_spans.append(a_spans[a_coll])
    collective = union(np.concatenate(coll_spans).reshape(-1, 2))
    compute = union(spans[~is_coll])
    exposed = subtract(collective, compute)

    by_kind: dict = {}
    for n, own in zip(short, self_times(spans)):
        k = op_kind(n)
        by_kind[k] = by_kind.get(k, 0.0) + float(own)
    device_ops = sorted(by_kind.items(), key=lambda kv: -kv[1])[:top_ops]

    idle = gaps(per_chip_busy[chip0])
    order = np.argsort(-(idle[:, 1] - idle[:, 0]))[:top_gaps] if len(idle) else []
    host_spans = list(host_spans)
    idle_gaps = [
        [attribute_gap((idle[i, 0], idle[i, 1]), host_spans),
         float(idle[i, 1] - idle[i, 0])]
        for i in order
    ]
    modules = {}
    if MODULES_LINE in devices[chip0]:
        m_names, m_spans = devices[chip0][MODULES_LINE]
        for n, (s, e) in zip(m_names, m_spans):
            key = re.sub(r"\(\d+\)$", "", n)
            count, secs = modules.get(key, (0, 0.0))
            modules[key] = (count + 1, secs + (e - s))
    return {
        "chips": len(per_chip_busy),
        "window_s": float(window_s),
        "busy_s": busy_s,
        "busy_s_chip0": total(per_chip_busy[chip0]),
        "window_start_s": float(first),
        "collective_s": total(collective),
        "collective_exposed_s": total(exposed),
        "collective_ops": int(is_coll.sum()),
        "device_ops": [[k, float(v)] for k, v in device_ops],
        "idle_gaps": idle_gaps,
        "modules": {k: [c, float(s)] for k, (c, s) in modules.items()},
    }


def marker_start_s(planes: dict, marker: str) -> Optional[float]:
    """Where the (last) annotation named ``marker`` starts on the
    trace's clock."""
    starts = [start for name, start, _ in planes["host"] if name == marker]
    return max(starts) if starts else None


def reduce_trace(
    trace_dir: str,
    *,
    marker: Optional[str] = None,
    marker_stamp_s: Optional[float] = None,
    program_spans: Iterable[tuple[str, float, float]] = (),
    clip_after_marker_s: Optional[float] = None,
) -> Optional[dict]:
    """Reduce the newest trace under ``trace_dir``.  ``program_spans``
    are the program's own spans ``(name, start_s, end_s)`` on the clock
    ``marker_stamp_s`` was read from; they are moved onto the trace's
    clock through the marker and used to name the idle gaps.  Without a
    marker the gaps stay ``unattributed``.

    Starting the profiler stalls the host for seconds, so the benchmark
    lets the program settle under the running profiler, syncs the
    device, and only then stamps the marker: with
    ``clip_after_marker_s`` the device events before the marker's start
    plus that many seconds are left out (the benchmark idles a little
    longer than that inside the marker, which covers the profiler's own
    alignment error between device and host stamps)."""
    from jax.profiler import ProfileData

    path = find_xplane(trace_dir)
    if path is None:
        return None
    planes = read_planes(
        ProfileData.from_file(path), None if marker is None else {marker}
    )
    spans: list = []
    offset = start = None
    if marker is not None:
        start = marker_start_s(planes, marker)
    if start is not None and marker_stamp_s is not None:
        offset = start - marker_stamp_s
        spans = [(n, s + offset, e + offset) for n, s, e in program_spans]
    clip = None
    if start is not None and clip_after_marker_s is not None:
        clip = start + clip_after_marker_s
    summary = reduce_planes(planes, host_spans=spans, start_s=clip)
    if summary is not None:
        summary["marker_found"] = start is not None
    return summary
