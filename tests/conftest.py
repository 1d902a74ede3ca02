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

# The files whose longest case takes over 30 s from an empty compile cache
# beside five other workers, the longest first, in the order they run:
# after everything else, their cases handed out one by one, so that the
# run's last minutes are shared out by the case and not by the file.  Read
# from the tier-1 command's own ``--junitxml`` with ``--durations=80`` added
# (ISSUE 41; CHANGES.md has the table).  A file stays out of the list, and
# on one worker, where its cases share what the process holds: a module's
# fixture (the references under ``tests/benchmark/``, ``test_serving.py``,
# ``test_resilience.py``) or a module's cache of compiled oracles
# (``test_kimi_linear.py``, ``test_olmo_hybrid.py``).
_LONG_FILES_LAST = (
    "tests/test_chip_compile.py",
    "tests/benchmark/test_bench_run_files.py",
    "tests/benchmark/test_bench_rehearse.py",
    "tests/benchmark/test_bench_startup.py",
    "tests/test_harness.py",
    "tests/test_lm_fit_smoke.py",
    "tests/test_transformer.py",
)


def _long_place(nodeid: str) -> int:
    """0 for a case of any other file, else 1 + the file's place above."""
    file = nodeid.split("::", 1)[0]
    return next((1 + i for i, name in enumerate(_LONG_FILES_LAST) if file.endswith(name)), 0)


def pytest_collection_modifyitems(session, config, items):
    items.sort(key=lambda item: _long_place(item.nodeid))  # stable: collection order within a place


def pytest_xdist_make_scheduler(config, log):
    """Under xdist a file's cases stay together on one worker, in their
    order (several files hold one expensive module-scoped fixture, a model
    and its plain reference with every gradient, which ``--dist load``
    made two or three workers pay for, up to 350 s each; some cases lean
    on what an earlier case of their file left in the process); the cases
    of the long files above are handed out one by one as workers run dry,
    which is what balances the run's second half."""
    from xdist.scheduler import LoadScopeScheduling

    class _FilesThenLongCases(LoadScopeScheduling):
        def _split_scope(self, nodeid):
            return nodeid if _long_place(nodeid) else nodeid.split("::", 1)[0]

    return _FilesThenLongCases(config, log)


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
