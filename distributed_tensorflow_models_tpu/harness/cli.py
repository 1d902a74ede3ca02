"""Command-line entry point: train / eval / list for every config.

The L6+L5 replacement (SURVEY.md §1): the reference launches each model
with a shell script exporting host lists and ``--job_name/--task_index``
flags into a per-model ``main()``.  Here one CLI covers the zoo, and there
is no job/task topology to configure — multi-host SPMD needs only
``--multihost`` (coordinator autodetected on managed TPU slices, SURVEY.md
§5.8).

    python -m distributed_tensorflow_models_tpu.harness.cli train \\
        --config lenet_mnist --workdir /tmp/lenet --train-steps 2000
    python -m distributed_tensorflow_models_tpu.harness.cli eval \\
        --config lenet_mnist --workdir /tmp/lenet
    python -m distributed_tensorflow_models_tpu.harness.cli list
"""

from __future__ import annotations

import argparse
import json
import logging
import sys


def _parse_chaos(text: str) -> dict:
    """argparse ``type=`` for --chaos: a ValueError here becomes a clean
    usage error naming the bad key/value (lazy import keeps CLI startup
    light)."""
    from distributed_tensorflow_models_tpu.resilience import chaos

    return chaos.parse_chaos_spec(text)


def _add_common(p: argparse.ArgumentParser) -> None:
    p.add_argument("--config", required=True, help="config name (see `list`)")
    p.add_argument("--workdir", required=True, help="checkpoint/metrics dir")
    p.add_argument("--train-steps", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--seed", type=int, default=None)
    p.add_argument(
        "--steps-per-loop", type=int, default=None,
        help="fused multi-step dispatch: train steps per jitted call "
        "(lax.scan over stacked batches; 1 = per-step dispatch).  Raise "
        "for small models where host dispatch, not the chip, bounds step "
        "rate — trajectory and hook cadences are unchanged (README "
        "'Performance')",
    )
    p.add_argument(
        "--data-workers", type=int, default=None,
        help="parallel host input pipeline: worker threads decoding/"
        "augmenting batches behind ordered reassembly (1 = single "
        "producer thread).  Deterministic — the batch stream is "
        "bit-identical for any value; raise it for decode-bound inputs "
        "(README 'Performance')",
    )
    p.add_argument(
        "--mesh-model", type=int, default=None,
        help="tensor-parallel axis size (default 1)",
    )
    p.add_argument(
        "--mesh-seq", type=int, default=None,
        help="sequence-parallel axis size (default 1)",
    )
    p.add_argument(
        "--mesh-pipe", type=int, default=None,
        help="pipeline axis size (default 1)",
    )
    p.add_argument(
        "--mesh-expert", type=int, default=None,
        help="expert-parallel axis size (default 1)",
    )
    p.add_argument(
        "--seq-impl", choices=("ring", "ulysses"), default=None,
        help="sequence-parallelism strategy over the seq axis",
    )
    p.add_argument(
        "--attn-impl",
        choices=("auto", "reference", "blockwise"),
        default=None,
        help="attention kernel (auto = fused kernels on a TPU, else blockwise)",
    )
    p.add_argument(
        "--fused-unembed", action=argparse.BooleanOptionalAction,
        default=None,
        help="fuse the LM head projection + cross entropy (chunked bf16 "
        "matmul, no [B*T, V] f32 logits tensor — ops/losses.py); "
        "--no-fused-unembed forces the two-stage f32 head on configs "
        "that default fused",
    )
    p.add_argument(
        "--nan-policy", choices=("abort", "rollback"), default=None,
        help="divergence policy: abort (default — non-finite loss kills "
        "the run) or rollback (restore the last finite checkpoint, skip "
        "exactly the offending chunk's batches, retry under "
        "--rollback-budget; README 'Robustness')",
    )
    p.add_argument(
        "--rollback-budget", type=int, default=None,
        help="max nan_policy=rollback rewinds per run (default 3)",
    )
    p.add_argument(
        "--watchdog-timeout-s", type=float, default=None,
        help="step-progress watchdog: warn (ERROR log + "
        "train/watchdog_last_progress_s gauge) when no chunk completes "
        "within this many seconds — a hung collective or pipeline "
        "deadlock produces a diagnosis instead of a silent stall",
    )
    p.add_argument(
        "--watchdog-abort", action=argparse.BooleanOptionalAction,
        default=None,
        help="escalate a persistent stall (2+ watchdog timeout "
        "intervals, after at least one chunk has completed) to an "
        "abort attempt instead of warnings only",
    )
    p.add_argument(
        "--checkpoint-every-steps", type=int, default=None,
        help="additionally checkpoint every N steps (step cadence is "
        "deterministic — needed for reproducible drills and exact "
        "multi-host restart points; the 600s clock cadence stays "
        "active alongside)",
    )
    p.add_argument(
        "--xla-cache-dir", type=str, default=None,
        help="persistent XLA compilation cache dir for relaunch-to-"
        "first-step MTTR (default <checkout>/.xla_cache; "
        "JAX_COMPILATION_CACHE_DIR, when set, wins; '' disables) — "
        "README 'Performance'",
    )
    p.add_argument(
        "--aot-compile", action=argparse.BooleanOptionalAction,
        default=None,
        help="AOT-compile the train step concurrently with the "
        "checkpoint restore (default on; bit-identical to the jit "
        "path).  --no-aot-compile reverts to lazy first-step "
        "compilation",
    )
    p.add_argument(
        "--trace-ring-events", type=int, default=None,
        help="structured event tracer ring size (flight recorder / "
        "Chrome-trace export; default 4096, 0 disables tracing) — "
        "README 'Observability'",
    )
    p.add_argument(
        "--trace-export", action=argparse.BooleanOptionalAction,
        default=None,
        help="write the event ring as Perfetto-loadable Chrome-trace "
        "JSON (<workdir>/trace_p<i>.json) at every fit exit; merge "
        "hosts with scripts/fleet_report.py (default off)",
    )
    p.add_argument(
        "--flight-recorder", action=argparse.BooleanOptionalAction,
        default=None,
        help="dump <workdir>/flight_recorder_p<i>.json (last trace "
        "events + registry snapshot) on abnormal exits — rollback, "
        "preemption, crash, chaos kill (default on); "
        "--no-flight-recorder disables",
    )
    p.add_argument(
        "--preempt-poll-steps", type=int, default=None,
        help="multi-host preemption-notice poll cadence in steps (the "
        "poll is a collective; default 20).  Keep poll_steps x step_time "
        "inside the fleet's SIGTERM grace window or the emergency "
        "checkpoint never runs; single-process runs poll every chunk "
        "boundary and ignore this",
    )
    p.add_argument(
        "--chaos", type=_parse_chaos, default=None, metavar="K=V[,K=V...]",
        help="deterministic fault injection (testing/drills; off by "
        "default): pipeline_fail_at_batch, nan_at_step, "
        "torn_checkpoint_at_step, sigterm_at_step — e.g. "
        "--chaos 'nan_at_step=50' (resilience/chaos.py)",
    )
    p.add_argument(
        "--multihost", action="store_true",
        help="initialize jax.distributed (multi-host SPMD)",
    )


def _overrides(args) -> dict:
    out = {}
    if args.train_steps is not None:
        out["train_steps"] = args.train_steps
    if args.batch_size is not None:
        out["global_batch_size"] = args.batch_size
    if args.seed is not None:
        out["seed"] = args.seed
    if getattr(args, "steps_per_loop", None) is not None:
        out["steps_per_loop"] = args.steps_per_loop
    if getattr(args, "data_workers", None) is not None:
        out["data_workers"] = args.data_workers
    if getattr(args, "nan_policy", None) is not None:
        out["nan_policy"] = args.nan_policy
    if getattr(args, "rollback_budget", None) is not None:
        out["rollback_budget"] = args.rollback_budget
    if getattr(args, "watchdog_timeout_s", None) is not None:
        out["watchdog_timeout_s"] = args.watchdog_timeout_s
    if getattr(args, "watchdog_abort", None) is not None:
        out["watchdog_abort"] = args.watchdog_abort
    if getattr(args, "checkpoint_every_steps", None) is not None:
        out["checkpoint_every_steps"] = args.checkpoint_every_steps
    if getattr(args, "xla_cache_dir", None) is not None:
        out["xla_cache_dir"] = args.xla_cache_dir
    if getattr(args, "aot_compile", None) is not None:
        out["aot_compile"] = args.aot_compile
    if getattr(args, "preempt_poll_steps", None) is not None:
        out["preempt_poll_steps"] = args.preempt_poll_steps
    if getattr(args, "trace_ring_events", None) is not None:
        out["trace_ring_events"] = args.trace_ring_events
    if getattr(args, "trace_export", None) is not None:
        out["trace_export"] = args.trace_export
    if getattr(args, "flight_recorder", None) is not None:
        out["flight_recorder"] = args.flight_recorder
    if getattr(args, "chaos", None) is not None:
        out["chaos"] = args.chaos
    for attr, key in (
        ("mesh_model", "mesh_model"),
        ("mesh_seq", "mesh_seq"),
        ("mesh_pipe", "mesh_pipe"),
        ("mesh_expert", "mesh_expert"),
        ("seq_impl", "seq_impl"),
        ("attn_impl", "attn_impl"),
        ("fused_unembed", "fused_unembed"),
    ):
        if getattr(args, attr, None) is not None:
            out[key] = getattr(args, attr)
    return out


def main(argv: list[str] | None = None) -> int:
    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    parser = argparse.ArgumentParser(prog="dtm")
    sub = parser.add_subparsers(dest="cmd", required=True)
    p_train = sub.add_parser("train", help="train a config (auto-resumes)")
    _add_common(p_train)
    p_eval = sub.add_parser("eval", help="evaluate the latest checkpoint")
    _add_common(p_eval)
    p_eval.add_argument(
        "--continuous", action="store_true",
        help="re-evaluate as new checkpoints appear",
    )
    p_eval.add_argument("--max-batches", type=int, default=None)
    p_ab = sub.add_parser(
        "ab",
        help="async-PS vs sync-replica comparison (the reference's "
        "flagship experiment)",
    )
    p_ab.add_argument("--config", required=True)
    p_ab.add_argument("--steps", type=int, default=50)
    p_ab.add_argument("--async-workers", type=int, default=4)
    p_ab.add_argument(
        "--schedule", choices=("round_robin", "random"), default="round_robin"
    )
    p_ab.add_argument("--staleness-limit", type=int, default=None)
    p_ab.add_argument("--batch-size", type=int, default=None)
    p_ab.add_argument("--seed", type=int, default=None)
    p_ab.add_argument("--mesh-model", type=int, default=None)
    p_ab.add_argument(
        "--fused-unembed", action=argparse.BooleanOptionalAction,
        default=None,
        help="fused chunked LM head in both arms (LM configs)",
    )
    p_ab.add_argument("--multihost", action="store_true")
    # Shared override plumbing (_overrides) expects these attributes.
    p_ab.set_defaults(train_steps=None, workdir=None)
    p_gen = sub.add_parser(
        "generate",
        help="sample from a trained transformer LM checkpoint (KV-cache "
        "decode)",
    )
    p_gen.add_argument("--config", required=True)
    p_gen.add_argument("--workdir", required=True)
    p_gen.add_argument(
        "--prompt",
        default="",
        help="comma-separated token ids (empty = BOS-style token 0)",
    )
    p_gen.add_argument("--max-new-tokens", type=int, default=64)
    p_gen.add_argument("--temperature", type=float, default=0.0)
    p_gen.add_argument("--top-k", type=int, default=0)
    p_gen.add_argument("--top-p", type=float, default=1.0)
    # Default None so _overrides doesn't clobber cfg.seed; the sampling
    # key falls back to 0 below.
    p_gen.add_argument("--seed", type=int, default=None)
    p_gen.add_argument("--eos-id", type=int, default=None)
    p_gen.set_defaults(
        train_steps=None, batch_size=None, multihost=False
    )
    sub.add_parser("list", help="list available configs")
    args = parser.parse_args(argv)

    from distributed_tensorflow_models_tpu.harness.config import (
        get_config,
        list_configs,
    )

    if args.cmd == "list":
        for name in list_configs():
            print(name)
        return 0

    # Cluster facts from the launcher (DTM_* env, launch.py) take priority;
    # --multihost without them falls back to managed-slice auto-detection.
    from distributed_tensorflow_models_tpu import launch as launchlib

    in_cluster = launchlib.initialize_from_env()
    if args.multihost and not in_cluster:
        from distributed_tensorflow_models_tpu.core import mesh as meshlib

        meshlib.initialize_multihost()

    cfg = get_config(args.config, **_overrides(args))

    if args.cmd == "ab":
        from distributed_tensorflow_models_tpu.harness import experiment

        result = experiment.async_vs_sync(
            cfg,
            args.steps,
            num_workers=args.async_workers,
            schedule=args.schedule,
            staleness_limit=args.staleness_limit,
        )
        print(json.dumps(result.to_json()))
        return 0

    if args.cmd == "train":
        from distributed_tensorflow_models_tpu.harness import train as trainlib

        result = trainlib.recoverable_fit(cfg, args.workdir)
        print(
            json.dumps(
                {
                    "final_metrics": result.final_metrics,
                    "preempted": result.preempted,
                }
            )
        )
        if result.preempted:
            # Preemption grace: the run checkpointed and stopped early.
            # Exit with the resumable code (EX_TEMPFAIL) so wrappers —
            # including launch.py — distinguish "rerun me" from failure.
            return launchlib.RESUMABLE_EXIT_CODE
        return 0

    if args.cmd == "generate":
        import jax
        import jax.numpy as jnp

        from distributed_tensorflow_models_tpu.harness import (
            checkpoint as ckptlib,
        )
        from distributed_tensorflow_models_tpu.harness import (
            generate as genlib,
        )
        from distributed_tensorflow_models_tpu.harness import train as trainlib

        if cfg.task != "lm" or cfg.model != "transformer_lm":
            raise SystemExit(
                "generate requires a transformer_lm config "
                f"(got model={cfg.model!r})"
            )
        if cfg.mesh_pipe > 1:
            raise SystemExit(
                "generate does not support pipelined checkpoints "
                "(stacked parameter layout)"
            )
        from distributed_tensorflow_models_tpu.models import get_model

        mesh = trainlib.mesh_from_config(cfg)
        template = trainlib.build_state(cfg, mesh)
        manager = ckptlib.CheckpointManager(
            args.workdir, keep=cfg.keep_checkpoints
        )
        try:
            state, _ = manager.restore(template)
        except FileNotFoundError as e:
            raise SystemExit(
                f"no checkpoint in {args.workdir!r}: {e}"
            ) from e
        model = get_model(cfg.model, **cfg.model_kwargs)
        try:
            tokens = [
                int(t) for t in args.prompt.split(",") if t.strip()
            ]
        except ValueError as e:
            raise SystemExit(
                f"--prompt must be comma-separated ints: {e}"
            ) from e
        if not tokens:
            tokens = [0]
        prompt = jnp.asarray([tokens], jnp.int32)
        out = genlib.generate(
            model,
            state.params,
            prompt,
            args.max_new_tokens,
            temperature=args.temperature,
            top_k=args.top_k,
            top_p=args.top_p,
            rng=jax.random.key(args.seed or 0),
            eos_id=args.eos_id,
        )
        print(
            json.dumps(
                {
                    "step": int(state.step),
                    "prompt": tokens,
                    "tokens": [int(t) for t in out[0]],
                }
            )
        )
        return 0

    from distributed_tensorflow_models_tpu.harness import evaluate as evallib

    if args.continuous:
        for res in evallib.continuous_eval(
            cfg, args.workdir, max_batches=args.max_batches
        ):
            print(json.dumps({"step": res.step, **res.metrics}))
        return 0
    fn = evallib.evaluate_lm if cfg.task == "lm" else evallib.evaluate_classification
    res = fn(cfg, args.workdir, max_batches=args.max_batches)
    print(json.dumps({"step": res.step, **res.metrics}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
