#!/usr/bin/env python
"""Chip smoke: the trainer and the serving engine, once, on the TPU.

The quickest proof that the system still starts on the chip.  One
process, which owns the chip for its whole life and starts no child.
It refuses to run unless ``jax.devices()[0].platform == "tpu"`` and
never pins or falls back to the CPU.  Each phase prints one JSON line
when it ends; any phase that raises ends the run with a non-zero exit.
The last stdout line is the driver's contract line.

    python chip_smoke.py            # one chip: train, serve, kernels
    python chip_smoke.py --chips 4  # four chips: data-parallel fit only

Default phases (one chip):

- ``train``   — ``recoverable_fit`` on ``resnet50_synthetic`` at the
  published shape (ResNet-50 v1, 224x224, global batch 256, library
  default conv lowering, default donation), ``TRAIN_STEPS`` steps with
  one checkpoint save inside the run.  These are the two calls ``cli
  train`` makes (``harness/cli.py``); the CLI has no log-cadence flag,
  so the smoke sets ``log_every_steps`` on the config to get loss rows.
- ``serve``   — an ``LMServer`` over the widest LM the repo serves
  (2 layers, d_model 640, d_ff 8192), mixed prompt lengths and sampling
  modes; streams byte-identical whatever they were batched with, and
  byte-identical to solo ``generate`` at matmul precision "highest"
  (see ``phase_serve`` for what the chip's default precision allows).
- ``kernels`` — the Pallas kernels compiled by Mosaic (never interpret)
  against their plain references at real shapes, and the fused LM head
  at the token cells' shapes against f32 autodiff of the two-stage head.

``--chips 4`` runs only the data-parallel comparison: the same ``fit``
on a one-device mesh and on all four, in this one process.

Steps/s and compile seconds printed here are smoke readings (one run,
compile included in the wall), not benchmark numbers.

Everything the run writes goes under ``chip_smoke_out/`` (git-ignored),
emptied at start so auto-resume never skips the steps.  The compile
cache is wherever ``harness/startup.py::apply_compile_cache`` puts it
(``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.xla_cache``);
each phase line carries the persistent-cache hits and misses it saw.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import logging
import math
import os
import shutil
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chip_smoke_out")

TRAIN_CONFIG = "resnet50_synthetic"
TRAIN_STEPS = 60
TRAIN_LOG_EVERY = 10
TRAIN_CKPT_EVERY = 30

# The serve phase's LM: two layers at d_model 640, d_ff 8192.
SERVE_MODEL = dict(
    vocab_size=256, num_layers=2, num_heads=4, d_model=640, d_ff=8192
)
SERVE_MAX_LEN = 64
SERVE_MAX_NEW = 24
SERVE_SEED = 7
# (prompt length, temperature, top_k, top_p): mixed lengths, greedy and
# the three sampling modes.  Eight requests over four slots, so half are
# admitted mid-flight into recycled slots.
SERVE_REQUESTS = (
    (4, 0.0, 0, 1.0),
    (9, 0.8, 20, 1.0),
    (17, 0.7, 0, 0.9),
    (30, 0.0, 0, 1.0),
    (9, 1.0, 0, 1.0),
    (4, 0.8, 20, 0.95),
    (17, 0.0, 0, 1.0),
    (30, 0.8, 20, 1.0),
)

# (B, T, H, D) flash-attention shapes; one ResNet-50 3x3 conv class at
# the published per-chip batch.
# The last two are the token cells' attention shapes (``gpt2m_train``;
# ``olmoe_train`` at one sequence of its four, so that the materialized
# f32 reference and its gradients fit the chip).
FLASH_SHAPES = (
    (16, 512, 8, 64), (4, 2048, 8, 64), (8, 1024, 16, 64), (1, 4096, 16, 128),
)
CONV_SHAPE = dict(batch=256, size=56, cin=64, cout=64)
# (B, T, d, V, bias) of the fused LM head: ``gpt2m_train``'s and
# ``olmoe_train``'s.
HEAD_SHAPES = ((8, 1024, 1024, 50257, True), (4, 4096, 2048, 50304, False))
# Vocabulary-sized products (2 n d V FLOPs each) the compiled head may
# hold per row: logits, dlogits . W^T and x^T . dlogits, and no fourth.
HEAD_PRODUCTS = (2.95, 3.10)
# bf16 inputs and outputs against an f32 reference: errors are compared
# to the reference's largest magnitude.
KERNEL_TOL = 2e-2

DP_STEPS = 10
# Relative per-step loss agreement between the 1-device and the 4-device
# run of the same global batch.  The first step is the same function of
# the same parameters and batch (only the reduction order differs), so it
# is held tightly; after that bf16 rounding differences compound through
# lr-0.1 updates, so the trajectories are only asked to stay together.
DP_FIRST_LOSS_RTOL = 2e-3
DP_LOSS_RTOL = 1e-2


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


class CacheCounter:
    """Counts jax's persistent-compilation-cache hit and miss events."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self):
        import jax.monitoring

        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_) -> None:
        if event == self._HIT:
            self.hits += 1
        elif event == self._MISS:
            self.misses += 1

    def read(self) -> tuple[int, int]:
        return self.hits, self.misses


def _read_json(path: str):
    with open(path) as f:
        return json.load(f)


def _losses(workdir: str) -> list[tuple[int, float]]:
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [(int(r["step"]), float(r["loss"])) for r in rows]


def _lower_step(cfg, state, mesh):
    """The very step program ``fit`` builds for ``cfg``, lowered against
    the batch spec ``fit`` compiles for (trace-only)."""
    import jax

    from distributed_tensorflow_models_tpu.harness import startup as startuplib
    from distributed_tensorflow_models_tpu.harness import train as trainlib

    return trainlib.build_step(cfg, state).lower(
        state,
        startuplib.abstract_batch(cfg, mesh),
        jax.random.key(cfg.seed + 1),
    )


# --------------------------------------------------------------------------
# train
# --------------------------------------------------------------------------


def phase_train(out_dir: str, steps: int = TRAIN_STEPS, **overrides) -> dict:
    import jax
    import numpy as np

    from distributed_tensorflow_models_tpu.core import train_loop
    from distributed_tensorflow_models_tpu.harness import (
        checkpoint as ckptlib,
    )
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config
    from distributed_tensorflow_models_tpu.ops import conv as convlib

    workdir = os.path.join(out_dir, "train")
    cfg = get_config(
        TRAIN_CONFIG,
        **{
            "train_steps": steps,
            "log_every_steps": TRAIN_LOG_EVERY,
            "checkpoint_every_steps": TRAIN_CKPT_EVERY,
            "trace_export": True,
            **overrides,
        },
    )
    t0 = time.perf_counter()
    result = trainlib.recoverable_fit(cfg, workdir)
    wall_s = time.perf_counter() - t0
    if result.preempted or int(result.state.step) != steps:
        raise AssertionError(
            f"fit stopped at step {int(result.state.step)} of {steps} "
            f"(preempted={result.preempted})"
        )

    losses = _losses(workdir)
    if not losses or losses[-1][0] != steps:
        raise AssertionError(f"loss rows do not reach step {steps}: {losses}")
    bad = [(s, v) for s, v in losses if not math.isfinite(v)]
    if bad:
        raise AssertionError(f"non-finite loss rows: {bad}")
    if not losses[-1][1] < losses[0][1]:
        raise AssertionError(f"loss did not go down: {losses}")

    # The checkpoint restores to the step and the values fit ended on.
    mesh = trainlib.mesh_from_config(cfg)
    manager = ckptlib.CheckpointManager(workdir, keep=cfg.keep_checkpoints)
    try:
        saved_steps = sorted(manager.all_steps())
        restored_state, _, restored = ckptlib.restore_or_init(
            manager, trainlib.build_state(cfg, mesh)
        )
    finally:
        manager.close()
    if not restored or int(restored_state.step) != steps:
        raise AssertionError(
            f"restore_or_init gave step {int(restored_state.step)} "
            f"(restored={restored}), want {steps}; saved {saved_steps}"
        )
    if not any(s < steps for s in saved_steps):
        raise AssertionError(f"no save inside the run: {saved_steps}")
    for a, b in zip(
        jax.tree.leaves(restored_state.params),
        jax.tree.leaves(result.state.params),
    ):
        if not np.array_equal(np.asarray(a), np.asarray(b)):
            raise AssertionError("restored params differ from fit's")

    kind = jax.devices()[0].device_kind
    report = _read_json(os.path.join(workdir, "telemetry.json"))
    metrics = report["metrics"]
    checks = {
        "train/compile/count >= 1": metrics.get("train/compile/count", 0) >= 1,
        "train/flops_per_step > 0": metrics.get("train/flops_per_step", 0) > 0,
        "mfu > 0": report["mfu"] > 0,
        "device_kind": report["device_kind"] == kind,
    }
    if not all(checks.values()):
        raise AssertionError(f"telemetry.json checks failed: {checks}")

    # What fit compiled, read off the very step program it builds: the
    # conv lowering (convolution ops in the module) and donation (the
    # state argument aliased to the output).
    text = _lower_step(cfg, result.state, mesh).as_text()
    conv_ops = text.count("stablehlo.convolution")
    donated = "tf.aliasing_output" in text or "jax.buffer_donor" in text
    conv_impl = convlib.get_default_conv_impl()
    if (conv_ops > 0) != (conv_impl == "xla"):
        raise AssertionError(
            f"conv lowering {conv_impl!r} but {conv_ops} convolution ops"
        )
    if donated != train_loop.default_donate():
        raise AssertionError(
            f"step program donation {donated} != default "
            f"{train_loop.default_donate()}"
        )

    trace = _read_json(os.path.join(workdir, "trace_p0.json"))
    aot_used = any(
        e["name"] in ("train/compile", "train/dispatch")
        and e.get("args", {}).get("aot")
        for e in trace["traceEvents"]
    )
    return {
        "config": cfg.name,
        "global_batch": cfg.global_batch_size,
        "image_size": cfg.image_size,
        "steps": steps,
        "loss_first": losses[0][1],
        "loss_last": losses[-1][1],
        "conv_impl": conv_impl,
        "convolution_ops": conv_ops,
        "donation": donated,
        "aot_executable_used": aot_used,
        "checkpoint_steps": saved_steps,
        "restored_step": int(restored_state.step),
        "compile_events": report["compile_events"],
        "compile_s": report["seconds"]["compile"],
        "aot_compile_s": report["startup"]["aot_compile_s"],
        "flops_per_step": report["flops_per_step"],
        "device_kind": report["device_kind"],
        # Smoke readings: one run, wall includes compile and saves.
        "smoke_steps_per_sec_whole_run": report["steps_per_sec"],
        "smoke_mfu_whole_run": report["mfu"],
        "wall_s": round(wall_s, 2),
    }


# --------------------------------------------------------------------------
# serve
# --------------------------------------------------------------------------


def _load_schema_lint():
    path = os.path.join(REPO, "scripts", "check_metrics_schema.py")
    spec = importlib.util.spec_from_file_location("check_metrics_schema", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@contextlib.contextmanager
def _matmul_precision(value):
    """Process-wide default matmul precision (the config, not the
    thread-local context manager: the server's worker thread must see
    it too)."""
    import jax

    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", value)
    try:
        yield
    finally:
        jax.config.update("jax_default_matmul_precision", old)


def _serve(workdir, model, params, prompts, requests, keys, max_new, waves):
    """One LMServer over a fresh engine; ``waves`` names how the request
    set is offered, in order: ``"together"`` submits all at once (eight
    requests over four slots, so half are admitted mid-flight into
    recycled slots), ``"alone"`` submits one at a time, each waiting for
    the one before.  Returns the streams of each wave; checks the
    two-program pin, the arena audit and the drain artifact's schema."""
    from distributed_tensorflow_models_tpu.serving.engine import (
        InferenceEngine,
    )
    from distributed_tensorflow_models_tpu.serving.server import (
        LMServer,
        serving_stats_path,
    )

    built = {}

    def factory():
        built["engine"] = InferenceEngine(
            model, params, max_slots=4, prefill_chunk=16, decode_burst=8,
        )
        return built["engine"]

    def submit(server, i):
        _, temperature, top_k, top_p = requests[i]
        return server.submit(
            prompts[i], max_new, temperature=temperature, top_k=top_k,
            top_p=top_p, rng=keys[i],
        )

    server = LMServer(factory, workdir=workdir, process_index=0)
    server.start()
    streams = []
    for wave in waves:
        if wave == "together":
            handles = [submit(server, i) for i in range(len(requests))]
            done = [h.result(timeout=600) for h in handles]
        else:
            done = [
                submit(server, i).result(timeout=600)
                for i in range(len(requests))
            ]
        streams.append([list(c.tokens) for c in done])
    server.drain()

    compile_counts = built["engine"].compile_counts()
    if compile_counts != (1, 1):
        raise AssertionError(f"compile_counts {compile_counts} != (1, 1)")
    stats_path = serving_stats_path(workdir, 0)
    lint_rc = _load_schema_lint().main([stats_path, "--serving-report"])
    if lint_rc != 0:
        raise AssertionError(
            f"check_metrics_schema --serving-report exited {lint_rc}"
        )
    stats = _read_json(stats_path)
    if stats.get("fsck_errors"):
        raise AssertionError(f"arena fsck: {stats['fsck_errors']}")
    return streams, stats["metrics"]


def _differing(a: list, b: list) -> list[int]:
    return [i for i, (x, y) in enumerate(zip(a, b)) if x != y]


def phase_serve(
    out_dir: str,
    model_dims: dict = SERVE_MODEL,
    requests: tuple = SERVE_REQUESTS,
    max_new: int = SERVE_MAX_NEW,
) -> dict:
    """Served streams against solo ``generate()``.

    On the CPU every served stream is byte-identical to solo
    ``generate()``.  On the TPU that holds only at
    ``jax_default_matmul_precision="highest"``: at the default precision
    an f32 matmul is computed in bf16 passes whose rounding depends on
    the batch shape (a solo row is M=1, an engine row one of M=4 lanes),
    so near-tied tokens of a random-weight model flip in some streams
    (PERF.md, PR 21 findings; ROADMAP D5).  So the phase asserts the
    strongest properties that do hold and prints the rest:

    - at the default precision (what a user gets): a request's stream
      does not depend on what it was batched with — the same requests
      served together, and then again one at a time over the now-warm
      prefix cache, give byte-identical streams; the count of streams
      that leave solo ``generate()`` is printed, not asserted;
    - at ``"highest"``: every served stream is byte-identical to solo
      ``generate()``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_models_tpu.harness.generate import generate
    from distributed_tensorflow_models_tpu.models import get_model

    model = get_model(
        "transformer_lm", **model_dims, max_len=SERVE_MAX_LEN,
        dropout_rate=0.0, dtype=jnp.float32,
    )
    params = model.init(
        jax.random.key(SERVE_SEED), jnp.zeros((1, 4), jnp.int32)
    )["params"]
    prompt_rng = np.random.RandomState(SERVE_SEED)
    prompts = [
        prompt_rng.randint(0, model_dims["vocab_size"], plen).astype(np.int32)
        for plen, *_ in requests
    ]
    keys = [
        jax.random.fold_in(jax.random.key(SERVE_SEED), i)
        for i in range(len(requests))
    ]

    def solo():
        return [
            np.asarray(
                generate(
                    model, params, jnp.asarray(prompt)[None], max_new,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    rng=key,
                )
            )[0, len(prompt):].tolist()
            for prompt, (_, temperature, top_k, top_p), key in zip(
                prompts, requests, keys
            )
        ]

    t0 = time.perf_counter()
    (together, alone), metrics = _serve(
        os.path.join(out_dir, "serve"), model, params, prompts, requests,
        keys, max_new, ("together", "alone"),
    )
    wall_s = time.perf_counter() - t0
    moved = _differing(together, alone)
    if moved:
        raise AssertionError(
            f"streams depend on what they were batched with: requests "
            f"{moved} differ between the two waves"
        )
    if not metrics["serve/prefix_cache_hits"] > 0:
        raise AssertionError("the replayed wave never hit the prefix cache")
    off_solo_default = _differing(together, solo())

    with _matmul_precision("highest"):
        (exact,), _ = _serve(
            os.path.join(out_dir, "serve_highest"), model, params, prompts,
            requests, keys, max_new, ("together",),
        )
        off_solo_highest = _differing(exact, solo())
    if off_solo_highest:
        raise AssertionError(
            f"at precision=highest, streams differ from solo generate(): "
            f"requests {off_solo_highest} of {len(requests)}"
        )
    return {
        "model": {**model_dims, "max_len": SERVE_MAX_LEN, "dtype": "float32"},
        "requests": len(requests),
        "prompt_lengths": sorted({r[0] for r in requests}),
        "sampled_requests": sum(1 for r in requests if r[1] > 0),
        "tokens_per_wave": sum(len(t) for t in together),
        "compile_counts": [1, 1],
        "serving_report_lint": "ok",
        "streams_independent_of_batching": True,
        "prefix_cache_hits": metrics["serve/prefix_cache_hits"],
        "mismatched_vs_solo_at_highest_precision": 0,
        "mismatched_vs_solo_at_default_precision": len(off_solo_default),
        # Smoke reading: both waves, both programs' compiles included.
        "default_precision_waves_wall_s": round(wall_s, 2),
    }


# --------------------------------------------------------------------------
# kernels
# --------------------------------------------------------------------------


def _normalized_err(got, want) -> float:
    import jax.numpy as jnp

    got = jnp.asarray(got, jnp.float32)
    want = jnp.asarray(want, jnp.float32)
    if not bool(jnp.all(jnp.isfinite(got))):
        return float("inf")
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def _check_kernel(name, fn, ref_fn, args, report):
    """Run ``fn`` (value and grads, Mosaic-compiled) and ``ref_fn`` (the
    plain reference on f32 copies, highest precision) and record the
    errors; raise when the kernel is missing or wrong."""
    import jax
    import jax.numpy as jnp

    def with_grads(f):
        def run(*xs):
            out = f(*xs)
            # A fixed non-uniform cotangent, so the backward kernels see
            # more than a constant.
            weight = jnp.cos(
                jnp.arange(out.size, dtype=jnp.float32).reshape(out.shape)
            )
            return jnp.sum(out.astype(jnp.float32) * weight), out

        return jax.value_and_grad(
            run, argnums=tuple(range(len(args))), has_aux=True
        )

    jitted = jax.jit(with_grads(fn))
    if "tpu_custom_call" not in jitted.lower(*args).as_text():
        raise AssertionError(f"{name}: no tpu_custom_call in the program")
    (_, out), grads = jitted(*args)
    f32 = [a.astype(jnp.float32) for a in args]
    with jax.default_matmul_precision("highest"):
        (_, ref_out), ref_grads = jax.jit(with_grads(ref_fn))(*f32)
    errs = {"out": _normalized_err(out, ref_out)}
    for i, (g, rg) in enumerate(zip(grads, ref_grads)):
        errs[f"grad{i}"] = _normalized_err(g, rg)
    report[name] = {k: round(v, 5) for k, v in errs.items()}
    worst = max(errs.values())
    if not worst <= KERNEL_TOL:
        raise AssertionError(
            f"{name}: normalized error {errs} exceeds {KERNEL_TOL}"
        )


def _check_head(shape, report):
    """The fused LM head (``ops/losses.py::fused_unembed_mean_xent``, bf16
    products) and the per-token op under ``jnp.mean`` that it replaced in
    ``fit``, both against f32 autodiff of the two-stage head at highest
    precision; and the products the new op's compiled program holds."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_models_tpu.core import train_loop
    from distributed_tensorflow_models_tpu.ops import losses as losslib

    B, T, d, V, with_bias = shape
    rng = np.random.RandomState(0)
    hidden = jnp.asarray(rng.randn(B, T, d).astype(np.float32), jnp.bfloat16)
    kernel = jnp.asarray(rng.randn(d, V).astype(np.float32) * 0.02)
    targets = jnp.asarray(rng.randint(0, V, (B, T)), jnp.int32)
    args = [hidden, kernel]
    names = ["dhidden", "dkernel"]
    if with_bias:
        args.append(jnp.asarray(rng.randn(V).astype(np.float32) * 0.02))
        names.append("dbias")

    def two_stage(h, k, b=None):
        logits = h.reshape(-1, d) @ k
        return losslib.mean_softmax_cross_entropy(
            logits if b is None else logits + b, targets.reshape(-1)
        )

    heads = {
        "autodiff_head": lambda h, k, b=None: jnp.mean(
            losslib.chunked_unembed_xent(h, k, b, targets)
        ),
        "fused_head": lambda h, k, b=None: losslib.fused_unembed_mean_xent(
            h, k, b, targets
        ),
    }

    def with_grads(head):
        return jax.jit(
            jax.value_and_grad(head, argnums=tuple(range(len(args))))
        )

    with jax.default_matmul_precision("highest"):
        want = with_grads(two_stage)(*[a.astype(jnp.float32) for a in args])
    tag = f"_{B}x{T}x{d}_v{V}"
    worst = {}
    for which, head in heads.items():
        lowered = with_grads(head).lower(*args)
        compiled = lowered.compile()
        loss, grads = compiled(*args)
        errs = {"loss": _normalized_err(loss, want[0])}
        for name, g, wg in zip(names, grads, want[1]):
            errs[name] = _normalized_err(g, wg)
        worst[which] = max(errs.values())
        report[which + tag] = {k: round(v, 5) for k, v in errs.items()}
        if not worst[which] <= KERNEL_TOL:
            raise AssertionError(
                f"{which}{tag}: normalized error {errs} exceeds {KERNEL_TOL}"
            )
    if worst["fused_head"] > 1.25 * worst["autodiff_head"]:
        raise AssertionError(
            f"fused_head{tag} is less exact than the op it replaced: {worst}"
        )
    # ``compiled`` is the fused head's: the loop's last.
    products = train_loop.program_flops(lowered, compiled) / (
        2.0 * B * T * d * V
    )
    report["fused_head" + tag]["vocab_products_per_row"] = round(products, 3)
    if not HEAD_PRODUCTS[0] <= products <= HEAD_PRODUCTS[1]:
        raise AssertionError(
            f"fused_head{tag}: {products:.3f} vocabulary-sized products a "
            f"row, not within {HEAD_PRODUCTS}"
        )


def phase_kernels(
    flash_shapes: tuple = FLASH_SHAPES,
    conv_shape: dict = CONV_SHAPE,
    head_shapes: tuple = HEAD_SHAPES,
) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from distributed_tensorflow_models_tpu.ops import attention as attnlib
    from distributed_tensorflow_models_tpu.ops.conv_mxu import conv2d_mxu

    rng = np.random.RandomState(0)

    def bf16(*shape, scale=1.0):
        return jnp.asarray(
            rng.randn(*shape).astype(np.float32) * scale, jnp.bfloat16
        )

    report: dict = {}
    for shape in flash_shapes:
        # The ring's chunk kernels at offsets 0 (self-attention).
        # Positional: (q, k, v, q_offset, kv_offset, causal, scale,
        # block_q, block_kv, interpret) — interpret=False is the point.
        _check_kernel(
            "flash_attention_chunk_" + "x".join(map(str, shape)),
            lambda q, k, v: attnlib.flash_attention_chunk(
                q, k, v, 0, 0, True, None, None, None, False
            )[0],
            lambda q, k, v: attnlib.reference_attention(q, k, v, causal=True),
            [bf16(*shape, scale=0.5) for _ in range(3)],
            report,
        )
        # What ``attention(impl="auto")`` runs here: the fused kernels.
        qkv = [bf16(*shape, scale=0.5) for _ in range(3)]
        if attnlib.auto_route(*qkv) != "fused":
            raise AssertionError(f"auto does not choose fused at {shape}")
        _check_kernel(
            "auto_attention_" + "x".join(map(str, shape)),
            lambda q, k, v: attnlib.attention(q, k, v, causal=True),
            lambda q, k, v: attnlib.reference_attention(q, k, v, causal=True),
            qkv,
            report,
        )
    b, s = conv_shape["batch"], conv_shape["size"]
    cin, cout = conv_shape["cin"], conv_shape["cout"]
    _check_kernel(
        f"conv2d_mxu_{b}x{s}x{s}x{cin}_3x3x{cout}",
        lambda x, k: conv2d_mxu(x, k, (1, 1), "SAME", interpret=False),
        lambda x, k: lax.conv_general_dilated(
            x, k, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        ),
        [bf16(b, s, s, cin), bf16(3, 3, cin, cout, scale=0.05)],
        report,
    )
    for shape in head_shapes:
        _check_head(shape, report)
    return {
        "compiled_by": "mosaic (interpret=False, tpu_custom_call present)",
        "tolerance": KERNEL_TOL,
        "normalized_errors": report,
    }


# --------------------------------------------------------------------------
# --chips 4: data-parallel fit on one device and on all four
# --------------------------------------------------------------------------


def phase_data_parallel(
    out_dir: str, n_devices: int = 4, steps: int = DP_STEPS, **overrides
) -> dict:
    import jax

    from distributed_tensorflow_models_tpu.core import mesh as meshlib
    from distributed_tensorflow_models_tpu.data import pipeline as pipelib
    from distributed_tensorflow_models_tpu.harness import hooks as hooklib
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    cfg = get_config(
        TRAIN_CONFIG,
        **{"train_steps": steps, "log_every_steps": 1, **overrides},
    )

    class ParamPlacement(hooklib.Hook):
        """Records how many devices hold each parameter, and whether it
        is replicated, on the state fit actually trains."""

        def __init__(self):
            self.spans: set = set()
            self.replicated = True

        def wants_step(self, step):
            return step == 1

        def after_step(self, state, metrics, step):
            if step != 1:
                return
            for leaf in jax.tree.leaves(state.params):
                self.spans.add(len(leaf.sharding.device_set))
                self.replicated &= leaf.sharding.is_fully_replicated

    runs = {}
    for n in (1, n_devices):
        mesh = meshlib.data_parallel_mesh(jax.devices()[:n])
        workdir = os.path.join(out_dir, f"dp{n}")
        placement = ParamPlacement()
        result = trainlib.fit(
            cfg, workdir, mesh=mesh, extra_hooks=[placement]
        )
        if int(result.state.step) != steps:
            raise AssertionError(
                f"{n}-device fit stopped at step {int(result.state.step)}"
            )
        losses = _losses(workdir)
        if [s for s, _ in losses] != list(range(1, steps + 1)):
            raise AssertionError(f"{n}-device loss rows: {losses}")
        # The batch, through the same pipeline stages fit builds.
        host = pipelib.HostPipeline(trainlib.build_dataset(cfg, "train"))
        try:
            batch = next(iter(pipelib.DevicePrefetcher(host, mesh, depth=1)))
        finally:
            host.stop()
        batch_span = {
            len(leaf.sharding.device_set) for leaf in jax.tree.leaves(batch)
        }
        shard_rows = {
            leaf.addressable_shards[0].data.shape[0]
            for leaf in jax.tree.leaves(batch)
        }
        runs[n] = {
            "losses": [v for _, v in losses],
            "param_device_span": sorted(placement.spans),
            "params_replicated": placement.replicated,
            "batch_device_span": sorted(batch_span),
            "batch_rows_per_device": sorted(shard_rows),
            # GLOBAL FLOPs of one step as fit's telemetry priced it: a
            # compiled SPMD program is one device's partition, scaled
            # back by train_loop.program_flops.
            "flops_per_step": _read_json(
                os.path.join(workdir, "telemetry.json")
            )["flops_per_step"],
        }

    # The wide run's compiled step (a cache hit: fit compiled the same
    # program) must hold the gradient all-reduce.
    all_reduce_ops = (
        _lower_step(cfg, result.state, mesh).compile().as_text()
        .count("all-reduce(")
    )
    wide = runs[n_devices]
    if wide["batch_device_span"] != [n_devices]:
        raise AssertionError(f"batch does not span {n_devices}: {wide}")
    if wide["batch_rows_per_device"] != [cfg.global_batch_size // n_devices]:
        raise AssertionError(f"batch is not split evenly: {wide}")
    if wide["param_device_span"] != [n_devices] or not wide["params_replicated"]:
        raise AssertionError(f"params are not replicated on all: {wide}")
    if all_reduce_ops < 1:
        raise AssertionError("no all-reduce in the compiled step")
    if runs[1]["param_device_span"] != [1]:
        raise AssertionError(f"one-device run is not on one device: {runs[1]}")
    flops_ratio = wide["flops_per_step"] / runs[1]["flops_per_step"]
    if not 0.98 <= flops_ratio <= 1.02:
        raise AssertionError(
            f"global FLOPs per step differ between the runs: "
            f"{runs[1]['flops_per_step']:.4e} on 1 device, "
            f"{wide['flops_per_step']:.4e} on {n_devices}"
        )
    rel = [
        abs(a - b) / max(abs(a), 1e-9)
        for a, b in zip(runs[1]["losses"], wide["losses"])
    ]
    if not all(math.isfinite(v) for v in wide["losses"]):
        raise AssertionError(f"non-finite losses: {wide['losses']}")
    if rel[0] > DP_FIRST_LOSS_RTOL or max(rel) > DP_LOSS_RTOL:
        raise AssertionError(
            f"per-step losses differ by {rel[0]:.5f} at step 1 "
            f"(> {DP_FIRST_LOSS_RTOL}) or by up to {max(rel):.4f} "
            f"(> {DP_LOSS_RTOL}): {runs[1]['losses']} vs {wide['losses']}"
        )
    return {
        "config": cfg.name,
        "global_batch": cfg.global_batch_size,
        "steps": steps,
        "devices": [1, n_devices],
        "batch_device_span": wide["batch_device_span"],
        "batch_rows_per_device": wide["batch_rows_per_device"],
        "param_device_span": wide["param_device_span"],
        "params_replicated": wide["params_replicated"],
        "all_reduce_ops": all_reduce_ops,
        "flops_per_step_1": runs[1]["flops_per_step"],
        f"flops_per_step_{n_devices}": wide["flops_per_step"],
        "losses_1": runs[1]["losses"],
        f"losses_{n_devices}": wide["losses"],
        "first_step_rel_loss_diff": round(rel[0], 6),
        "max_rel_loss_diff": round(max(rel), 6),
        "loss_rtol": {"first_step": DP_FIRST_LOSS_RTOL, "all": DP_LOSS_RTOL},
    }


# --------------------------------------------------------------------------


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): train, serve and kernels on one chip; "
        "4: only the data-parallel fit on one device and on all four",
    )
    args = parser.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < args.chips:
        print(
            f"chip_smoke: needs {args.chips} TPU chip(s); jax.devices() is "
            f"{devices}. It does not run on the CPU.",
            file=sys.stderr,
        )
        return 2

    from distributed_tensorflow_models_tpu.harness import startup as startuplib

    logging.basicConfig(
        level=logging.INFO, stream=sys.stderr,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    shutil.rmtree(OUT_DIR, ignore_errors=True)
    os.makedirs(OUT_DIR)
    cache_dir = startuplib.apply_compile_cache()
    cache = CacheCounter()
    emit(
        {
            "phase": "start",
            "chips": args.chips,
            "devices": [str(d) for d in devices],
            "compile_cache_dir": cache_dir,
            "compile_cache_entries": startuplib.cache_entry_count(cache_dir),
        }
    )
    if args.chips == 4:
        phases = [("data_parallel", lambda: phase_data_parallel(OUT_DIR))]
    else:
        phases = [
            ("train", lambda: phase_train(OUT_DIR)),
            ("serve", lambda: phase_serve(OUT_DIR)),
            ("kernels", phase_kernels),
        ]
    for name, run in phases:
        hits0, misses0 = cache.read()
        t0 = time.perf_counter()
        result = run()
        hits, misses = cache.read()
        emit(
            {
                "phase": name,
                "ok": True,
                "seconds": round(time.perf_counter() - t0, 2),
                "compile_cache_hits": hits - hits0,
                "compile_cache_misses": misses - misses0,
                **result,
            }
        )
    emit(
        {
            "ok": True,
            "device": {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            },
        }
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
