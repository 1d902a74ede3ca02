"""Test bootstrap: fake 8-device CPU mesh.

SURVEY.md §4.3: `--xla_force_host_platform_device_count=8` gives 8 fake CPU
devices so the real Mesh/collective code paths run in CI with no TPU — the
direct analogue of the reference's in-process fake clusters
(TF server_lib.py:216-239 `create_local_server`).

Must run before the first `import jax` anywhere in the test process.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

from distributed_tensorflow_models_tpu.harness import (  # noqa: E402
    startup as startuplib,
)

# The suite runs on the CPU: the tier-1 command sets JAX_PLATFORMS=cpu,
# and a bare `pytest` must not grab an attached accelerator either.
jax.config.update("jax_platforms", "cpu")

# Persistent XLA compilation cache: the suite compiles the same tiny
# train-step programs dozens of times (every fit/test builds a fresh jit
# object, so in-memory caches never hit across tests).  With the on-disk
# cache, identical programs deserialize (~45% cheaper than compiling on
# this box) — a large win for the many-fit harness/resilience/telemetry
# suites.  Semantics are unchanged: compiled artifacts are bit-identical,
# and a cache hit still runs the compile path (InstrumentedStep's
# compile-event detection keeps working).  Placed by the same helper as
# every other entry point: JAX_COMPILATION_CACHE_DIR if set, else the
# fixed <checkout>/.xla_cache, so back-to-back runs reuse it.
startuplib.apply_compile_cache()

import pytest  # noqa: E402


@pytest.fixture(scope="session")
def mesh8():
    from distributed_tensorflow_models_tpu.core import mesh as meshlib

    assert len(jax.devices()) == 8, jax.devices()
    return meshlib.data_parallel_mesh()


@pytest.fixture(autouse=True)
def _serialize_two_proc_tests(request):
    """Machine-wide serialization of ``two_proc``-marked tests.

    Each such test spawns a 2-process jax cluster (≈3 heavyweight
    processes with this one).  Two of them overlapping — parallel pytest
    sessions, a driver verify run racing a manual run — oversubscribes
    the 1–2 cores this box has and turns a ~60 s test into a 300 s
    timeout flake.  An exclusive flock on a fixed path means concurrent
    runs queue instead of thrashing; within one pytest session the lock
    is uncontended and costs nothing."""
    if request.node.get_closest_marker("two_proc") is None:
        yield
        return
    import fcntl

    path = os.environ.get("DTM_TWO_PROC_LOCK", "/tmp/dtm-two-proc.lock")
    with open(path, "a+") as f:
        fcntl.flock(f, fcntl.LOCK_EX)
        try:
            yield
        finally:
            fcntl.flock(f, fcntl.LOCK_UN)
