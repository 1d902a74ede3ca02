"""Record a small profiler trace of a toy program on the attached chip(s).

A development tool, never a cell: it exists so that the trace reduction
(``benchmark/lib/trace_reduce.py``) can be checked against a real
``.xplane.pb`` kept beside its test.  Writes under ``chiprun_out/``.

    chiprun -- python benchmark/tools/record_trace.py
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time


def main() -> int:
    import jax
    import jax.numpy as jnp

    out = os.path.join("chiprun_out", "record_trace")
    shutil.rmtree(out, ignore_errors=True)
    os.makedirs(out)
    devices = jax.devices()
    info = {
        "devices": [str(d) for d in devices],
        "kind": devices[0].device_kind,
        "env_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR"),
        "memory_stats_keys": sorted((devices[0].memory_stats() or {}).keys()),
        "cwd": os.getcwd(),
        "tmpdir": os.environ.get("TMPDIR"),
        "home": os.environ.get("HOME"),
    }

    n = len(devices)
    mesh = jax.sharding.Mesh(devices, ("data",))
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    @jax.jit
    def step(w, x):
        y = jnp.tanh(x @ w)
        g = jnp.mean(y.T @ x, axis=0)  # reduces over the sharded batch
        return w - 1e-3 * g[None, :] * jnp.ones_like(w)

    w = jax.device_put(jnp.ones((512, 512), jnp.bfloat16), rep)
    x = jax.device_put(jnp.ones((256 * n, 512), jnp.bfloat16), spec)
    w = step(w, x)
    jax.block_until_ready(w)

    jax.profiler.start_trace(out)
    t_marker = time.perf_counter()
    t_wall = time.time()
    with jax.profiler.TraceAnnotation("bench_sync_marker"):
        time.sleep(0.001)
    for i in range(4):
        with jax.profiler.TraceAnnotation("bench_host_span", i=i):
            w = step(w, x)
        if i == 1:
            jax.block_until_ready(w)
            time.sleep(0.005)  # an idle gap the reduction must find
    jax.block_until_ready(w)
    jax.profiler.stop_trace()
    info["marker_perf_counter"] = t_marker
    info["marker_time"] = t_wall

    path = glob.glob(os.path.join(out, "plugins", "profile", "*", "*.xplane.pb"))[0]
    info["xplane_bytes"] = os.path.getsize(path)
    shutil.copy(path, os.path.join(out, "toy.xplane.pb"))
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = list(line.events)
            lines.append(
                {
                    "name": line.name,
                    "n_events": len(events),
                    "sample": [
                        {
                            "name": e.name,
                            "start_ns": e.start_ns,
                            "duration_ns": e.duration_ns,
                            "stats": {k: str(v)[:80] for k, v in list(e.stats)[:8]},
                        }
                        for e in events[:6]
                    ],
                }
            )
        planes.append({"name": plane.name, "lines": lines})
    info["planes"] = planes

    # Disk write speed where the benchmark keeps its work directory.
    blob = os.urandom(1 << 20) * 256
    t0 = time.perf_counter()
    with open(os.path.join(out, "blob"), "wb") as f:
        f.write(blob)
        f.flush()
        os.fsync(f.fileno())
    info["disk_write_MBps"] = 256 / (time.perf_counter() - t0)
    os.remove(os.path.join(out, "blob"))
    shutil.rmtree(os.path.join(out, "plugins"), ignore_errors=True)
    info["memory_stats"] = {k: int(v) for k, v in (devices[0].memory_stats() or {}).items()}
    with open(os.path.join(out, "info.json"), "w") as f:
        json.dump(info, f, indent=1)
    print(json.dumps({k: v for k, v in info.items() if k != "planes"}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
