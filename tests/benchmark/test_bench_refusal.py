"""Without a TPU the command exits non-zero and prints no result; so it
does in a directory that holds only BENCHMARK.json and the benchmark's
own directories."""

import json
import os
import shutil
import subprocess
import sys

import bench_testlib

REPO = bench_testlib.REPO


def _run(cwd, *extra):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "resnet50_train",
         "--seed", "1", "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_refuses_to_run_on_the_cpu():
    done = _run(REPO)
    assert done.returncode == 2, done.stderr[-2000:]
    assert done.stdout.strip() == ""
    assert "TPU" in done.stderr


def test_refuses_without_the_program(tmp_path):
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        paths = json.load(f)["paths"]
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    for p in paths:
        shutil.copytree(
            os.path.join(REPO, p), tmp_path / p,
            ignore=shutil.ignore_patterns("__pycache__"),
        )
    done = _run(str(tmp_path))
    assert done.returncode != 0
    assert done.stdout.strip() == ""


def test_unknown_workload_is_refused():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "no_such_cell"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 2 and done.stdout.strip() == ""


def test_compile_counter_sees_small_programs():
    """A program that compiles in under the persistent cache's threshold
    raises neither a hit nor a miss; the window's counter has to see it
    all the same."""
    code = (
        "import sys, os; sys.path.insert(0, os.getcwd())\n"
        "import jax, jax.numpy as jnp\n"
        "from distributed_tensorflow_models_tpu.harness import startup\n"
        "from benchmark.lib.compile_events import CompileCounter\n"
        "startup.apply_compile_cache()\n"
        "x7, x9 = jnp.ones(7), jnp.ones(9)\n"
        "c = CompileCounter()\n"
        "f = jax.jit(lambda x: x * 3 + 1)\n"
        "f(x7).block_until_ready(); a = c.total()\n"
        "f(x7).block_until_ready(); b = c.total()\n"
        "f(x9).block_until_ready(); d = c.total()\n"
        "print(a, b, d, c.hits + c.misses)\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=120,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    a, b, d, hits_and_writes = (int(x) for x in done.stdout.split())
    assert a == 1 and b == a and d == a + 1
    assert hits_and_writes < d
