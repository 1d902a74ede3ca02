"""The device the run is on: refusal without a TPU, the result line's
``device`` object, and the table of peaks (``benchmark/peaks.json``)."""

from __future__ import annotations

import os

from benchmark.lib.cells import BENCH_DIR, read_json


class NoAccelerator(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


class UnknownDevice(RuntimeError):
    """The device kind is not in ``peaks.json``: an error, not a default."""


def require_tpu(chips: int) -> list:
    """The ``chips`` devices the cell runs on.  Never falls back."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        raise NoAccelerator(
            f"needs {chips} TPU chip(s); jax.devices() is {devices}. "
            "The benchmark does not run on the CPU."
        )
    return list(devices[:chips])


def load_peaks(device_kind: str, path: str | None = None) -> dict:
    table = read_json(path or os.path.join(BENCH_DIR, "peaks.json"))
    if device_kind not in table or device_kind == "source":
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(have {sorted(k for k in table if k != 'source')})"
        )
    return table[device_kind]


def program_temp_bytes(client) -> int:
    """The largest scratch (HLO temporaries) any program loaded in this
    process needs while it runs, as its compiler states it."""
    temp = 0
    for executable in client.live_executables():
        try:
            stats = executable.get_compiled_memory_stats()
        except Exception:  # noqa: BLE001 - a backend without the statistic
            continue
        temp = max(temp, int(stats.temp_size_in_bytes))
    return temp


def memory_peak_parts(devices, temp_bytes: int = 0) -> dict:
    """The two readings ``memory_peak_bytes`` is derived from: the
    allocator's ``peak_bytes_in_use`` on the fullest of ``devices`` and
    the largest program's temporaries (``temp_bytes``, which the runner
    reads with :func:`program_temp_bytes` while its programs are still
    loaded).  On the TPU the allocator counts buffers (parameters,
    optimizer state, caches, batches in flight) but not the scratch a
    running program holds (measured, PR 22: ResNet-50 at batch 256 reads
    1.5 GB there while its step needs 9 GB of temporaries)."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {
        "allocator_peak_bytes": peak,
        "largest_program_temp_bytes": int(temp_bytes) if peak else 0,
    }


def device_object(devices, temp_bytes: int = 0) -> dict:
    """The result line's ``device``.  ``memory_peak_bytes`` is derived,
    not read: the sum of the two parts beside it, an upper estimate of
    the peak (the allocator's peak and the largest program's scratch
    need not fall at the same instant; the step or decode program that
    owns the scratch runs while the buffers are live, so the sum is
    close).  0 where the backend reports no memory statistics, as the
    CPU does."""
    parts = memory_peak_parts(devices, temp_bytes)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": sum(parts.values()),
        "memory_peak_derived_from": parts,
    }
