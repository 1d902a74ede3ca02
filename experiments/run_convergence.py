#!/usr/bin/env python
"""Produce committed convergence artifacts (VERDICT r1 item 8).

Trains a config through the real harness (``fit`` with its full hook set)
and copies the resulting ``metrics.jsonl`` trend — loss/accuracy (or
nll/perplexity) every ``log_every_steps`` — into
``experiments/convergence_<config>.json`` plus a markdown summary table.
This is accuracy-*trend* evidence (SURVEY.md §4.4's integration tier), not
a full training run; datasets fall back to the synthetic substitutes in
this offline environment and the artifact records which was used.

Usage: python experiments/run_convergence.py --config ptb_small \
           --steps 2000 [--force-cpu]
"""

# Runnable from anywhere.
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import json
import platform
import tempfile
from datetime import datetime, timezone


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--config", required=True)
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--batch", type=int, default=None)
    p.add_argument("--log-every", type=int, default=25)
    p.add_argument("--force-cpu", action="store_true")
    args = p.parse_args()

    import jax

    if args.force_cpu:
        jax.config.update("jax_platforms", "cpu")

    from distributed_tensorflow_models_tpu.data import datasets
    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config

    overrides = dict(
        train_steps=args.steps,
        log_every_steps=args.log_every,
        checkpoint_every_secs=1e9,
    )
    if args.batch:
        overrides["global_batch_size"] = args.batch
    cfg = get_config(args.config, **overrides)

    # Record data provenance: real files under DATA_DIR or synthetic.
    real_markers = {
        "mnist": "mnist.npz",
        "cifar10": "cifar10.npz",
        "ptb": "ptb.train.txt",
    }
    marker = real_markers.get(cfg.dataset)
    data_kind = (
        "real"
        if marker and os.path.exists(os.path.join(datasets.DATA_DIR, marker))
        else "synthetic"
    )

    workdir = tempfile.mkdtemp(prefix=f"conv_{cfg.name}_")
    res = trainlib.fit(cfg, workdir)

    rows = []
    with open(os.path.join(workdir, "metrics.jsonl")) as f:
        for line in f:
            rows.append(json.loads(line))

    dev = jax.devices()[0]
    is_lm = cfg.task == "lm"
    payload = {
        "experiment": "convergence_trend",
        "config": cfg.name,
        "steps": args.steps,
        "global_batch_size": cfg.global_batch_size,
        "dataset": cfg.dataset,
        "data_kind": data_kind,
        "platform": dev.platform,
        "device": dev.device_kind,
        "n_devices": len(jax.devices()),
        "timestamp": datetime.now(timezone.utc).isoformat(),
        "host": platform.node(),
        "final_metrics": res.final_metrics,
        "series": rows,
    }
    here = os.path.dirname(os.path.abspath(__file__))
    out_json = os.path.join(here, f"convergence_{cfg.name}.json")
    with open(out_json, "w") as f:
        json.dump(payload, f, indent=1)

    import math

    def fmt(r):
        step = r.get("step")
        loss = r.get("loss", float("nan"))
        if is_lm:
            nll = r.get("nll", loss)
            return f"| {step} | {loss:.4f} | {math.exp(min(nll, 20)):.1f} |"
        acc = r.get("accuracy", float("nan"))
        return f"| {step} | {loss:.4f} | {acc:.4f} |"

    head = (
        "| step | loss | perplexity |" if is_lm else "| step | loss | top-1 |"
    )
    stride = max(1, len(rows) // 40)
    table = "\n".join(fmt(r) for r in rows[::stride])
    caveat = ""
    if data_kind == "synthetic" and is_lm:
        caveat = (
            "\n> **Synthetic-data caveat:** these perplexities are over a "
            "synthetic Zipfian token stream, an easier distribution than "
            "the real corpus.  They demonstrate the training loop "
            "converges; they are **not comparable** to literature "
            "perplexity corridors (e.g. PTB valid ~86) and must not be "
            "read as accuracy parity.  See "
            "experiments/DATA_AVAILABILITY.md for why no real corpus is "
            "available here.\n"
        )
    report = f"""# Convergence trend — {cfg.name}

`{args.steps}` steps of `fit` (full hook set) on {payload['device']} \
({payload['n_devices']} device(s)), dataset `{cfg.dataset}` \
(**{data_kind}** data), batch {cfg.global_batch_size}, \
{payload['timestamp']}.
{caveat}
{head}
|---|---|---|
{table}

Final: `{json.dumps(res.final_metrics)}`

Full series: `convergence_{cfg.name}.json`.  Generated by
`experiments/run_convergence.py --config {cfg.name} --steps {args.steps}`.
"""
    out_md = os.path.join(here, f"CONVERGENCE_{cfg.name}.md")
    with open(out_md, "w") as f:
        f.write(report)
    print(f"wrote {out_json} and {out_md}; final={res.final_metrics}")


if __name__ == "__main__":
    main()
