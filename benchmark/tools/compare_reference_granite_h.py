#!/usr/bin/env python3
"""The program's ``granite_h_micro`` model against its plain reference at
the configuration's own widths and the cell's length, outside any timed
window.

    chiprun -- python3 benchmark/tools/compare_reference_granite_h.py --seed <n>

Builds the configuration's model (``benchmark/configs/granite_h_micro.json``:
program config and overrides, so the same cut: ten layers, 12544 rows,
every head) with weights that ``--seed`` fixes (every norm scale,
``A_log``, ``dt_bias``, ``D`` and the convolution's bias moved off its
initial value, so that a dropped term would show: :func:`build`), takes
``--sequences`` sequences of the cell's stream, one at a time (the
reference walks 8,192 tokens one by one), and prints one JSON line per
comparison and a last line with ``ok``.  Three comparisons:

- ``bf16``: the loss the cell's step differentiates
  (``harness/train.py::build_loss`` of the cell's configuration: bf16
  compute over f32 parameters, per-half recomputation, the routes the
  chip takes, the fused head of ``ops/losses.py`` fed from the tied
  embedding matrix, its gradient finished in the forward pass) against
  the reference (f32, precision ``highest``); the logits, which that
  loss never forms, from a second apply of the same model;
- ``f32``: the same with the model in float32 under
  ``jax.default_matmul_precision("highest")`` and the unfused head
  (``fused_unembed=False``: the fused head multiplies in bfloat16
  whatever the model's dtype), which has to agree with the reference to
  rounding: this stack has no router, so no near-tie can send a token
  another way and the whole sequence is judged;
- ``reference_bf16``: the reference itself with everything in bfloat16
  (``dt``, the decay, the recurrent state, the norms, the softmax and
  the logits too: the nearest precision below what the configuration
  states), which has to come out as **not** correct under the ``bf16``
  tolerances.

Each prints the largest and the root-mean-square logit difference over
the spread (standard deviation) of the reference's logits, the loss of
both sides, and the relative error (norm of the difference over the norm
of the reference's) of the gradient of the loss for one leaf of each
kind (``LEAVES``; the tied embedding among them, whose gradient is the
sum of the gather's and the head's), the worst leaf and the mean over
the leaves.  Outside the comparison: Adam's update and the clip (the
cell's own ``correct`` reads the parameters' change over its window).

The tolerances (``TOLERANCES``) are what the chip runs of PR 38 support,
with the readings beside each.  ``--rehearse`` runs the cell's tiny size
on the CPU, to find wrong paths before chip time is spent; it holds the
``f32`` comparison to the tolerances and prints no verdict on ``bf16``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import types
import zlib

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)
CONFIG = "granite_h_micro"

# name -> (bf16 program, f32 program at "highest"); None: not judged.
# Set from PR 38's chip runs (one call, what git would commit, published
# widths, 8,192 positions, one sequence, seeds 2147485391, 2147485392 and
# 2147485393, ``ok: true`` three of three; PERF.md section 6), with the
# readings beside each.  A bf16 limit lies between the program's reading
# and the reference's one precision down (``reference_bf16``), which has
# to fall outside one bf16 limit, not each: it fell outside all three on
# every seed.  This stack has no router, so the float32 program is judged
# over the whole sequence and sits at rounding; its limits leave the
# readings some ten times of room, since fresh seeds read higher.  A fourth
# seed, run under these limits in a later call (2147485451, ``ok: true``,
# the reference in bf16 outside all three again), widened three of the
# bf16 ranges below: 0.01677 of ``logit_rms_over_spread``, 1.93e-4 of
# ``loss_abs`` against 3.3e-3, 0.01081 of ``grad_rel_mean``.
TOLERANCES = {
    # One logit of 8,192 x 12,544, an extreme value: bf16 0.098-0.102
    # against 0.148-0.181, too near each other for a limit between; float32
    # 2.4e-4 to 6.6e-4.
    "logit_max_over_spread": (None, 5e-3),
    # bf16 0.01663-0.01668 (a mean over 1e8 logits: steady to 0.3% over the
    # seeds) against 0.0198-0.0253; float32 2.8e-5 to 7.8e-5.
    "logit_rms_over_spread": (0.0182, 5e-4),
    # The fused head takes logits, softmax and cross entropy in float32 over
    # a bf16 product from the tied matrix: 6.6e-5 to 1.07e-4, against
    # 6.2e-3 to 2.3e-2 (the reference in bf16 rounds its logits and its
    # log-sum-exp too); float32 9.5e-7 to 1.9e-6.
    "loss_abs": (1e-3, 5e-5),
    # The mean over the leaves of ``LEAVES``: 0.01063-0.01076 against
    # 0.0151-0.0430.
    "grad_rel_mean": (0.013, None),
    # The worst leaf, in float32 7.8e-5 to 1.2e-3 (``dt_bias`` or ``A_log``
    # of the first state-space layer: 64 numbers).  Not judged in bf16, where
    # those 64 numbers or the convolution's taps are worst and by how much is
    # the seed's: 0.0137-0.0250 against 0.031-0.333; the mean is the steadier
    # reading.  The tied embedding: 0.0081-0.0085 in bf16, 2.3e-5 to 5.6e-5
    # in float32.
    "grad_rel": (None, 1e-2),
}

# (name, path below a block or the root, which block): one leaf of each kind.
LEAVES = (
    ("ssm_in_proj", ("ssm", "in_proj", "kernel"), "ssm"),
    ("ssm_conv", ("ssm", "conv"), "ssm"),
    ("ssm_conv_bias", ("ssm", "conv_bias"), "ssm"),
    ("ssm_a_log", ("ssm", "A_log"), "ssm"),
    ("ssm_dt_bias", ("ssm", "dt_bias"), "ssm"),
    ("ssm_d", ("ssm", "D"), "ssm"),
    ("ssm_norm", ("ssm", "norm", "scale"), "ssm"),
    ("ssm_out_proj", ("ssm", "out_proj", "kernel"), "ssm"),
    ("ssm_ln1", ("ln1", "scale"), "ssm"),
    ("attn_wq", ("attn", "query", "kernel"), "attention"),
    ("attn_wk", ("attn", "key", "kernel"), "attention"),
    ("attn_wv", ("attn", "value", "kernel"), "attention"),
    ("attn_wo", ("attn", "out", "kernel"), "attention"),
    ("mlp_gate", ("mlp", "gate", "kernel"), "attention"),
    ("mlp_down", ("mlp", "down", "kernel"), "ssm"),
    ("ln2", ("ln2", "scale"), "attention"),
    ("embedding", ("embedding", "embedding"), None),
    ("ln_f", ("ln_f", "scale"), None),
)


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--sequences", type=int, default=1)
    parser.add_argument("--rehearse", action="store_true")
    return parser.parse_args(argv)


def load_config(rehearse: bool) -> dict:
    """The configuration's file, through the cell that runs it (so that a
    rehearsal gets that cell's tiny size)."""
    from benchmark.lib import cells

    bench = cells.read_json(os.path.join(REPO_DIR, "BENCHMARK.json"))
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == CONFIG)
    return cells.load_cell(cell, rehearse=rehearse).config


def selected(tree: dict) -> dict:
    """The leaves whose gradients are compared: of the first state-space
    block, of the attention block, and of the root."""
    blocks = sorted((k for k in tree if k.startswith("blocks_")), key=lambda k: int(k[7:]))
    first = {
        "ssm": next(b for b in blocks if "ssm" in tree[b]),
        "attention": next(b for b in blocks if "attn" in tree[b]),
    }
    out = {}
    for name, path, kind in LEAVES:
        leaf = tree if kind is None else tree[first[kind]]
        for key in path:
            leaf = leaf[key]
        out[name] = leaf
    return out


def path_id(path) -> int:
    """A parameter's path as a number that is the same in every process
    (``hash`` of a string is not: Python salts it per process)."""
    return zlib.crc32("/".join(str(getattr(p, "key", p)) for p in path).encode())


def build(config: dict, seed: int, sequences: int):
    """``(cfg, make_model, params, tokens, targets)``: the cell's program
    configuration, a model factory by dtype, parameters and ``sequences``
    rows of the cell's stream, all fixed by ``seed``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config
    from distributed_tensorflow_models_tpu.models import get_model

    cfg = get_config(config["program_config"], **config["overrides"], global_batch_size=sequences)
    make_model = lambda dtype: get_model(cfg.model, **cfg.model_kwargs, dtype=dtype)
    batch = next(iter(trainlib.build_dataset(cfg, "train")))
    tokens = jnp.asarray(np.asarray(batch["inputs"]), jnp.int32)
    targets = jnp.asarray(np.asarray(batch["targets"]), jnp.int32)
    params = jax.jit(
        lambda key: make_model(jnp.float32).init(key, tokens[:1, :128])["params"]
    )(jax.random.key(seed))

    def move(path, leaf):
        if path[-1].key not in ("scale", "A_log", "dt_bias", "D", "conv_bias"):
            return leaf
        key = jax.random.fold_in(jax.random.key(seed + 1), path_id(path) % (2**31))
        return leaf + 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)

    return cfg, make_model, jax.tree_util.tree_map_with_path(move, params), tokens, targets


def program_side(cfg, model):
    """``params, tokens, targets -> (logits, loss, grads)`` of the
    program: the loss and the selected gradients from the loss ``fit``'s
    step differentiates for ``cfg`` (``build_loss``; this model sows
    nothing into ``losses``, so it is the mean cross entropy), the logits
    from a second apply."""
    import jax

    from distributed_tensorflow_models_tpu.harness import train as trainlib

    state = types.SimpleNamespace(apply_fn=model.apply, carry=None)
    loss_fn = trainlib.build_loss(cfg, state)

    def run(params, tokens, targets):
        batch = {"inputs": tokens, "targets": targets}
        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(params, state, batch, {})
        logits, _ = model.apply({"params": params}, tokens, train=False)
        return logits, loss, selected(grads)

    return jax.jit(run)


def reference_side(ref, kwargs: dict, dtype=None):
    """The same of the reference, in float32 or in ``dtype``."""
    import jax
    import jax.numpy as jnp

    kwargs = dict(kwargs, dtype=dtype or jnp.float32)

    def run(params, tokens, targets):
        (loss, _), grads = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, targets, **kwargs), has_aux=True
        )(params)
        return ref.forward(params, tokens, **kwargs), loss, selected(grads)

    return jax.jit(run)


def margins(got, want) -> dict:
    """One sequence's readings: ``got`` is the program's, ``want`` the
    reference's ``(logits, loss, grads)``, already on the host."""
    import numpy as np

    (g_logits, g_loss, g_grads), (w_logits, w_loss, w_grads) = got, want
    w64 = np.asarray(w_logits, np.float64)
    spread = float(np.std(w64))
    diff = np.asarray(g_logits, np.float64) - w64
    rel = lambda a, b: float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    return {
        "logit_spread": spread,
        "logit_max_over_spread": float(np.abs(diff).max()) / spread,
        "logit_rms_over_spread": float(np.sqrt(np.mean(diff**2))) / spread,
        "loss": [float(g_loss), float(w_loss)],
        "loss_abs": abs(float(g_loss) - float(w_loss)),
        "grad_rel_by_leaf": {
            k: rel(np.asarray(g_grads[k], np.float64), np.asarray(w_grads[k], np.float64))
            for k in w_grads
        },
    }


def worst(per_sequence: list) -> dict:
    """The worst reading of each margin over the sequences."""
    out = {}
    for key in TOLERANCES:
        if key == "grad_rel":
            out[key] = max(max(m["grad_rel_by_leaf"].values()) for m in per_sequence)
        elif key == "grad_rel_mean":
            out[key] = max(
                sum(m["grad_rel_by_leaf"].values()) / len(m["grad_rel_by_leaf"])
                for m in per_sequence
            )
        else:
            out[key] = max(m[key] for m in per_sequence)
    return out


def within(readings: dict, column: int) -> dict:
    return {
        key: readings[key] <= tol[column]
        for key, tol in TOLERANCES.items()
        if tol[column] is not None
    }


def main(argv=None) -> int:
    args = parse_args(argv)
    sys.path.insert(0, REPO_DIR)
    os.environ["DTM_DATA_DIR"] = os.path.join(REPO_DIR, ".benchmark_work", "no_data")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp

    from benchmark.lib import cells, device

    if args.rehearse:
        if jax.devices()[0].platform == "tpu":
            print("compare_reference_granite_h: --rehearse is for the CPU", file=sys.stderr)
            return 2
    else:
        try:
            device.require_tpu(1)
        except device.NoAccelerator as e:
            print(f"compare_reference_granite_h: {e}", file=sys.stderr)
            return 2
    config = load_config(args.rehearse)
    ref = cells.load_module("references", config["reference"])
    kwargs = dict(config["reference_kwargs"])
    if args.rehearse:
        sizes = config["overrides"]["model_kwargs"]
        kwargs.update(num_heads=sizes["num_heads"], num_kv_heads=sizes["num_kv_heads"])
    cfg, make_model, params, tokens, targets = build(config, args.seed, args.sequences)
    dev = jax.devices()[0]
    print(json.dumps({
        "config": CONFIG, "seed": args.seed, "tokens": list(tokens.shape),
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "parameters": sum(x.size for x in jax.tree.leaves(params)),
        "tolerances": TOLERANCES, "rehearsal": args.rehearse,
    }), flush=True)

    rows = [(tokens[i : i + 1], targets[i : i + 1]) for i in range(tokens.shape[0])]
    reference = reference_side(ref, kwargs)
    want = [jax.device_get(reference(params, t, y)) for t, y in rows]
    ok = True

    def report(name, column, per_sequence, expect_within):
        nonlocal ok
        readings = worst(per_sequence)
        line = {"program": name, "worst": readings, "per_sequence": per_sequence}
        if not (args.rehearse and column == 0):
            line["within"] = within(readings, column)
            ok = ok and all(line["within"].values()) == expect_within
        print(json.dumps(line), flush=True)

    for column, (name, dtype) in enumerate((("bf16", jnp.bfloat16), ("f32", jnp.float32))):
        # The fused head multiplies in bfloat16 whatever the model's dtype.
        program = program_side(cfg.replace(fused_unembed=name == "bf16"), make_model(dtype))
        per_sequence = []
        for (t, y), w in zip(rows, want):
            if name == "f32":
                with jax.default_matmul_precision("highest"):
                    got = jax.device_get(program(params, t, y))
            else:
                got = jax.device_get(program(params, t, y))
            per_sequence.append(margins(got, w))
        report(name, column, per_sequence, True)
    # The reference one precision down, held to the bf16 program's
    # tolerances: it has to fall outside them.
    low = reference_side(ref, kwargs, jnp.bfloat16)
    per_sequence = [margins(jax.device_get(low(params, t, y)), w) for (t, y), w in zip(rows, want)]
    report("reference_bf16", 0, per_sequence, False)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
