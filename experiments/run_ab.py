#!/usr/bin/env python
"""Produce the committed async-PS vs sync-replica A/B artifact.

The reference repo exists to compare asynchronous parameter-server training
against synchronous replica training on the same model (SURVEY.md §2.1 R6,
§2.4, BASELINE.json config 4).  This script runs that A/B through
``harness.experiment.async_vs_sync`` at ResNet-32/CIFAR-10 scale-down and
writes ``ab_resnet32.json`` (full trajectories + staleness distribution)
and ``AB_REPORT.md`` (human-readable summary) next to itself.

Usage: python experiments/run_ab.py [--steps N] [--batch N] [--workers K]
"""

# Runnable from anywhere.
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import platform
from datetime import datetime, timezone


def main():
    p = argparse.ArgumentParser()
    p.add_argument(
        "--config",
        default="resnet32_cifar10",
        help="experiment config name; resnet50_synthetic runs the "
        "BASELINE config-4 flagship A/B at its named model (synthetic "
        "ImageNet input, documented in the artifact)",
    )
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--batch", type=int, default=64)
    p.add_argument("--workers", type=int, default=4)
    p.add_argument(
        "--conv-impl",
        default=None,
        choices=["xla", "patches", "mxu"],
        help="conv lowering override (patches = matmul-only im2col; "
        "mxu = Pallas implicit-GEMM kernel)",
    )
    p.add_argument("--staleness-limit", type=int, default=None)
    p.add_argument(
        "--lr",
        type=float,
        default=None,
        help="override learning rate (the sync-tuned 0.1 destabilizes the "
        "async arm at staleness K-1; a lower lr shows the "
        "staleness-slowed-but-converging regime)",
    )
    p.add_argument("--tag", default="", help="artifact filename suffix")
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")

    from distributed_tensorflow_models_tpu.harness import experiment
    from distributed_tensorflow_models_tpu.harness.config import get_config

    cfg = get_config(
        args.config,
        global_batch_size=args.batch,
        seed=0,
    )
    if args.conv_impl:
        cfg = cfg.replace(
            model_kwargs={**cfg.model_kwargs, "conv_impl": args.conv_impl}
        )
    if args.lr is not None:
        import dataclasses

        cfg = cfg.replace(
            optimizer=dataclasses.replace(
                cfg.optimizer, learning_rate=args.lr
            )
        )
    res = experiment.async_vs_sync(
        cfg,
        args.steps,
        num_workers=args.workers,
        schedule="round_robin",
        staleness_limit=args.staleness_limit,
    )

    dev = jax.devices()[0]
    payload = {
        "experiment": "async_ps_vs_sync_replicas",
        "config": args.config,
        "conv_impl": args.conv_impl or "xla",
        "steps": args.steps,
        "global_batch_size": args.batch,
        "async_workers": args.workers,
        "staleness_limit": args.staleness_limit,
        "platform": dev.platform,
        "device": dev.device_kind,
        "n_devices": len(jax.devices()),
        "timestamp": datetime.now(timezone.utc).isoformat(),
        "host": platform.node(),
        **res.to_json(),
    }
    payload["learning_rate"] = (
        args.lr if args.lr is not None else cfg.optimizer.learning_rate
    )
    suffix = f"_{args.tag}" if args.tag else ""
    stem = "ab_" + args.config
    if args.config == "resnet32_cifar10":
        stem = "ab_resnet32"  # keep the round-2 artifact names stable
    here = os.path.dirname(os.path.abspath(__file__))
    with open(os.path.join(here, f"{stem}{suffix}.json"), "w") as f:
        json.dump(payload, f, indent=1)

    sync, async_ = payload["sync"], payload["async"]

    def curve(losses, k=10):
        rows = []
        for i in range(0, len(losses), k):
            rows.append(f"| {i} | {losses[i]:.4f} |")
        if (len(losses) - 1) % k:
            rows.append(f"| {len(losses) - 1} | {losses[-1]:.4f} |")
        return "\n".join(rows)

    hist = "\n".join(
        f"| {k} | {v} |" for k, v in async_["staleness_hist"].items()
    )
    report = f"""# Async-PS vs sync-replica A/B — {args.config}

The reference's headline experiment (SURVEY.md §2.1 R6, BASELINE.json
config 4), run through `harness.experiment.async_vs_sync` (`cli.py ab`):
identical init and batch stream; sync = the compiled SPMD psum step,
async = the deterministic event-driven PS emulator with {args.workers}
virtual workers (SURVEY.md §7.6).

Generated by `experiments/run_ab.py --steps {args.steps} --batch \
{args.batch} --workers {args.workers}` on {payload['device']} \
({payload['n_devices']} device(s)), {payload['timestamp']}.

## Headline

| | sync | async ({args.workers} workers) |
|---|---|---|
| final loss (step {args.steps - 1}) | {sync['final_loss']:.4f} | \
{async_['final_loss']:.4f} |
| model updates applied | {args.steps} | {args.steps} |
| mean staleness | 0 (by construction) | {async_['mean_staleness']} |
| dropped gradients | 0 | {async_['dropped']} |
| wall time (s) — **not comparable across columns**: fused compiled \
SPMD step vs un-jitted emulator bookkeeping | {sync['seconds']} | \
{async_['seconds']} |

The comparison this experiment is built for is **loss at equal update
counts** (both columns apply exactly {args.steps} updates); the wall-time
row is recorded only for provenance and says nothing about async-vs-sync
speed.  Interpretation: with round-robin scheduling the async emulator's
staleness settles at workers-1 = {args.workers - 1}; every update applies
gradients computed against parameters {args.workers - 1} steps old, which
slows convergence per step relative to sync — the effect the reference
was built to measure.  (On a real PS deployment the async side's selling
point was hiding stragglers, which an SPMD TPU slice does not have —
SURVEY.md §2.4.)

## Staleness distribution (async)

| staleness | events |
|---|---|
{hist}

## Convergence (loss every 10 steps)

Sync:

| step | loss |
|---|---|
{curve(sync['losses'])}

Async:

| step | loss |
|---|---|
{curve(async_['losses'])}

Full series: `{stem}{suffix}.json`.
"""
    report_name = (
        f"AB_REPORT{suffix}.md"
        if args.config == "resnet32_cifar10"
        else f"AB_REPORT_{args.config}{suffix}.md"
    )
    with open(os.path.join(here, report_name), "w") as f:
        f.write(report)
    print(json.dumps({k: payload[k] for k in ("sync", "async")})[:400])
    print(f"wrote experiments/{stem}{suffix}.json and {report_name}")


if __name__ == "__main__":
    main()
