"""Eval drivers: checkpoint-restoring top-1/top-5 (and perplexity) loops.

Reference semantics (SURVEY.md §3.5): the eval process restores the newest
checkpoint — EMA *shadow* variables when the model maintains them (TF
moving_averages.py:638) — runs top-1/top-5 counts over the validation set,
and optionally repeats every N minutes on the newest checkpoint
(``--run_once`` flag in the inception eval driver).
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Optional

import jax
import numpy as np

from distributed_tensorflow_models_tpu.core import sharding
from distributed_tensorflow_models_tpu.core import train_loop
from distributed_tensorflow_models_tpu.harness import checkpoint as ckptlib
from distributed_tensorflow_models_tpu.harness import train as trainlib
from distributed_tensorflow_models_tpu.harness.config import ExperimentConfig
from distributed_tensorflow_models_tpu.ops import losses as losslib

log = logging.getLogger("dtm")


@dataclasses.dataclass
class EvalResult:
    step: int
    metrics: dict


def evaluate_classification(
    cfg: ExperimentConfig,
    workdir: str,
    *,
    mesh=None,
    max_batches: Optional[int] = None,
    use_ema: bool = True,
) -> EvalResult:
    """One eval pass at the latest checkpoint: top-1/top-5 over the
    validation split (counting scheme of the reference's eval loop)."""
    if mesh is None:
        mesh = trainlib.mesh_from_config(cfg)
    template = trainlib.build_state(cfg, mesh)
    manager = ckptlib.CheckpointManager(workdir, keep=cfg.keep_checkpoints)
    state, _ = manager.restore(template)
    state = trainlib.place(cfg, state, mesh)
    eval_step = train_loop.make_eval_step(
        state.apply_fn, use_ema=use_ema and state.ema_params is not None
    )

    dataset = trainlib.build_dataset(cfg, "test")
    max_batches = max_batches or cfg.eval_batches
    if max_batches is None:
        # One pass over the validation set.  Epoch-looping datasets
        # (ArrayDataset) expose batches_per_epoch; one-pass datasets
        # (eval TFRecord) terminate on their own.
        max_batches = getattr(dataset, "batches_per_epoch", None)
    top1 = top5 = count = xent = 0.0
    for i, batch in enumerate(dataset):
        if max_batches is not None and i >= max_batches:
            break
        if len(batch["label"]) % mesh.devices.size:
            # Partial final batch: pad to the mesh and mask via counts.
            batch = _pad_batch(batch, mesh.devices.size)
        out = eval_step(state, sharding.shard_batch(mesh, batch))
        top1 += float(out["top1_count"])
        top5 += float(out["top5_count"])
        count += float(out["count"])
        xent += float(out["xent_sum"])
    manager.close()
    metrics = {
        "top1": top1 / max(count, 1),
        "top5": top5 / max(count, 1),
        "xent": xent / max(count, 1),
        "count": count,
    }
    log.info(
        "eval @ step %d: top1=%.4f top5=%.4f over %d examples",
        int(state.step), metrics["top1"], metrics["top5"], int(count),
    )
    return EvalResult(step=int(state.step), metrics=metrics)


def _pad_batch(batch, multiple: int):
    """Pad with copies of row 0, tagging padding with label -1 so top-k
    counts ignore it (label -1 matches nothing)."""
    n = len(batch["label"])
    pad = (-n) % multiple
    if pad == 0:
        return batch
    out = {}
    for k, v in batch.items():
        pad_rows = np.repeat(v[:1], pad, axis=0)
        if k == "label":
            pad_rows = np.full((pad,), -1, v.dtype)
        out[k] = np.concatenate([v, pad_rows], axis=0)
    return out


def evaluate_lm(
    cfg: ExperimentConfig,
    workdir: str,
    *,
    mesh=None,
    max_batches: Optional[int] = None,
) -> EvalResult:
    """Perplexity over the validation stream (R8's ``run_epoch`` eval):
    fresh zero carry, threaded across the whole split, ppl = exp(mean nll)."""
    if mesh is None:
        mesh = trainlib.mesh_from_config(cfg)
    template = trainlib.build_state(cfg, mesh)
    manager = ckptlib.CheckpointManager(workdir, keep=cfg.keep_checkpoints)
    state, _ = manager.restore(template)
    state = trainlib.place(cfg, state, mesh)

    @jax.jit
    def lm_eval_step(state, carry, batch):
        logits, new_carry = state.apply_fn(
            {"params": state.eval_params}, batch["inputs"], carry=carry,
            train=False,
        )
        nll = losslib.softmax_cross_entropy(logits, batch["targets"])
        return new_carry, nll.sum(), np.prod(batch["targets"].shape).astype(
            np.float32
        )

    dataset = trainlib.build_dataset(cfg, "valid")
    carry = template.carry  # zero carry from the fresh template
    total_nll = total_tok = 0.0
    n_batches = dataset.batches_per_epoch
    if max_batches is not None:
        n_batches = min(n_batches, max_batches)
    it = iter(dataset)
    for _ in range(n_batches):
        batch = sharding.shard_batch(mesh, next(it))
        carry, nll_sum, n_tok = lm_eval_step(state, carry, batch)
        total_nll += float(nll_sum)
        total_tok += float(n_tok)
    manager.close()
    ppl = float(np.exp(total_nll / max(total_tok, 1)))
    metrics = {"perplexity": ppl, "nll": total_nll / max(total_tok, 1)}
    log.info("eval @ step %d: perplexity=%.2f", int(state.step), ppl)
    return EvalResult(step=int(state.step), metrics=metrics)


def continuous_eval(
    cfg: ExperimentConfig,
    workdir: str,
    *,
    interval_secs: float = 60.0,
    max_evals: Optional[int] = None,
    max_batches: Optional[int] = None,
):
    """Re-evaluate whenever a new checkpoint appears — the reference's
    repeat-every-N-minutes eval loop (SURVEY.md §3.5 last line).  Yields
    :class:`EvalResult` per new checkpoint."""
    seen: Optional[int] = None
    evals = 0
    manager = ckptlib.CheckpointManager(workdir, keep=cfg.keep_checkpoints)
    while max_evals is None or evals < max_evals:
        latest = manager.latest_step()
        if latest is not None and latest != seen:
            seen = latest
            fn = evaluate_lm if cfg.task == "lm" else evaluate_classification
            yield fn(cfg, workdir, max_batches=max_batches)
            evals += 1
        else:
            time.sleep(interval_secs)
