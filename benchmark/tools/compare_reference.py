#!/usr/bin/env python3
"""The program's model against its plain reference at the configuration's
own widths, outside any timed window.

    chiprun -- python3 benchmark/tools/compare_reference.py --config olmoe --seed <n>

Builds the configuration's model (``benchmark/configs/<config>.json``:
program config and overrides) with weights from ``--seed`` (norm scales
moved off 1, so that a dropped scale would show), takes ``--sequences``
sequences of the cell's stream, one at a time (the reference keeps a
``[heads, time, time]`` score tensor and a second float32 copy of every
weight), and prints one JSON line per comparison and a last line with
``ok``.  Three comparisons:

- ``bf16``: the program as the cell runs it (bf16 compute over f32
  parameters) against the reference (f32, precision ``highest``);
- ``f32``: the program in float32 under
  ``jax.default_matmul_precision("highest")``, which has to agree with
  the reference to rounding;
- ``reference_bf16``: the reference itself with everything in bfloat16
  (norms, router, softmaxes and logits too: the nearest precision below
  what the configuration states), which has to come out as **not**
  correct under the ``bf16`` tolerances: they tell a program that
  computes the router, the norms or the softmax in bf16 from one that
  does not.

Each prints: the share of tokens whose chosen experts are the same set
on both sides; over those tokens, the largest and the root-mean-square
logit difference over the spread (standard deviation) of the reference's
logits (a token whose near-tie went the other way is computed by another
expert: it counts against the share, not against the logits); the total
loss and the two router losses of both sides; and the relative error (norm of the
difference over the norm of the reference's) of the gradient of the
total loss for the router, the three matrices of the expert that got
most tokens, ``Wq`` and the two QK-norm weights.

The tolerances (``TOLERANCES``) are what the chip run of PR 25 supports,
with the reason beside each.  ``--rehearse`` runs the cell's tiny size
on the CPU, to find wrong paths before chip time is spent; it holds the
``f32`` comparison to the CPU tests' tolerance and prints no verdict on
``bf16``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO_DIR = os.path.dirname(BENCH_DIR)

# name -> (bf16 program, f32 program at "highest").  Each bf16 limit lies
# between two readings of PR 25's chip runs at published widths (three
# seeds, two sequences each; PERF.md section 6): the largest the program
# gave, and the smallest the reference gave when computed one precision
# down (``reference_bf16``).  Two of them tell the two apart and are what
# makes the lower precision come out as not correct: the total loss (the
# program takes logits, softmax and cross entropy in float32: 0.0007 to
# 0.0021 apart, the all-bf16 reference 0.011 to 0.024) and the router
# z-loss (a float32 router: 3e-5 to 4.5e-4, against 2.6e-3 to 3.8e-3).
# The others bound what bf16 products cost either way (logits rms 0.016
# to 0.021 against 0.021 to 0.026; gradients 1.0 to 1.5% against 1.6 to
# 1.7%).  The f32 limits allow for one thing beyond rounding (3.6e-6 of
# the spread on logits, 4.7e-7 on gradients): a genuine near-tie in the
# top-8 goes the other way for about one token in 8,192 (seed
# 2147483902), which moves that token's logits by 0.4 of the spread, the
# gradients by 6e-4 and the loss by 1.1e-5.  So logits are compared on
# the tokens whose experts are the same set, and the share of such tokens
# has a limit of its own.
TOLERANCES = {
    "logit_max_over_spread": (1.0, 1e-4),
    "logit_rms_over_spread": (0.03, 1e-5),
    "loss_abs": (0.005, 1e-4),
    "aux_loss_abs": (0.01, 1e-4),
    "z_loss_rel": (1.2e-3, 1e-5),
    "same_experts_share_min": (0.93, 0.999),
    "grad_rel": (0.03, 2e-3),
}


def parse_args(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--sequences", type=int, default=2)
    parser.add_argument("--rehearse", action="store_true")
    return parser.parse_args(argv)


def load_config(name: str, rehearse: bool):
    """The configuration's file, through a cell that runs it (so that a
    rehearsal gets that cell's tiny size)."""
    from benchmark.lib import cells

    bench = cells.read_json(os.path.join(REPO_DIR, "BENCHMARK.json"))
    cell = next(w["name"] for w in bench["workloads"] if w["config"] == name)
    return cells.load_cell(cell, rehearse=rehearse).config


def build(config: dict, seed: int, sequences: int):
    """``(make_model, params, tokens, targets)``: a model factory by
    dtype, seeded parameters and ``sequences`` rows of the stream."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributed_tensorflow_models_tpu.harness import train as trainlib
    from distributed_tensorflow_models_tpu.harness.config import get_config
    from distributed_tensorflow_models_tpu.models import get_model

    cfg = get_config(
        config["program_config"], **config["overrides"], global_batch_size=sequences
    )
    make_model = lambda dtype: get_model(cfg.model, **cfg.model_kwargs, dtype=dtype)
    batch = next(iter(trainlib.build_dataset(cfg, "train")))
    tokens = jnp.asarray(np.asarray(batch["inputs"]), jnp.int32)
    targets = jnp.asarray(np.asarray(batch["targets"]), jnp.int32)
    params = jax.jit(
        lambda key: make_model(jnp.float32).init(key, tokens[:1])["params"]
    )(jax.random.key(seed))

    def move_scales(path, leaf):
        if path[-1].key != "scale":
            return leaf
        key = jax.random.fold_in(jax.random.key(seed + 1), hash(str(path)) % (2**31))
        return leaf + 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)

    params = jax.tree_util.tree_map_with_path(move_scales, params)
    return make_model, params, tokens, targets


def selected(tree: dict, expert) -> dict:
    """The leaves whose gradients are compared (first layer); of the
    expert stacks, expert ``expert``'s matrices."""
    block = tree["blocks_0"]
    moe, attn = block["moe"], block["attn"]
    return {
        "router": moe["router"],
        "w_gate": moe["w_gate"][expert],
        "w_up": moe["w_up"][expert],
        "w_down": moe["w_down"][expert],
        "wq": attn["query"]["kernel"],
        "q_norm": attn["q_norm"]["scale"],
        "k_norm": attn["k_norm"]["scale"],
    }


def program_side(model, top_k: int):
    """``params, tokens, targets, expert -> (logits, parts, experts,
    grads)`` of the program's model: logits, the loss as ``lm_loss_fn``
    composes it (cross entropy + everything in ``losses``), the chosen experts per
    layer (the program's own ``route_topk`` on the input of each expert
    layer) and the selected gradients."""
    import jax
    import jax.numpy as jnp

    from distributed_tensorflow_models_tpu.parallel import moe as moelib

    def total(params, tokens, targets):
        (logits, _), updated = model.apply(
            {"params": params}, tokens, train=False,
            mutable=["losses", "moe_stats", "intermediates"],
            capture_intermediates=lambda mdl, _: mdl.name == "ln2",
        )
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.mean(jnp.take_along_axis(logp, targets[..., None], axis=-1))
        loss = nll + sum(jnp.sum(x) for x in jax.tree.leaves(updated["losses"]))
        stats = updated["moe_stats"]
        layers = sorted(stats)
        mean = lambda name: sum(stats[l]["moe"][name] for l in layers) / len(layers)
        experts = []
        for l in layers:
            h = updated["intermediates"][l]["ln2"]["__call__"][0]
            h = h.astype(model.dtype).reshape(-1, h.shape[-1])
            experts.append(moelib.route_topk(params[l]["moe"]["router"], h, top_k)[3])
        parts = {"total": loss, "nll": nll, "aux_loss": mean("aux_loss"), "z_loss": mean("z_loss")}
        return loss, (logits, parts, experts)

    def run(params, tokens, targets, expert):
        (_, (logits, parts, experts)), grads = jax.value_and_grad(total, has_aux=True)(
            params, tokens, targets
        )
        return logits, parts, experts, selected(grads, expert)

    return jax.jit(run)


def reference_side(ref, kwargs: dict, dtype=None):
    """The same of the reference, in float32 or in ``dtype``; its fifth
    result is the expert of the first layer that got most tokens."""
    import jax
    import jax.numpy as jnp

    kwargs = dict(kwargs, dtype=dtype or jnp.float32)
    fwd_kwargs = {k: kwargs[k] for k in ("num_heads", "top_k", "eps", "theta", "dtype")}

    def run(params, tokens, targets):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, targets, **kwargs), has_aux=True
        )(params)
        logits = ref.forward(params, tokens, **fwd_kwargs)
        chosen = ref.routing(params, tokens, **fwd_kwargs)
        busiest = jnp.argmax(jnp.sum(chosen[0], axis=0))
        return logits, {"total": loss, **parts}, chosen, selected(grads, busiest), busiest

    return jax.jit(run)


def margins(got, want) -> dict:
    """One sequence's readings: ``got`` is the program's, ``want`` the
    reference's ``(logits, parts, experts, grads)``, already on the host."""
    import numpy as np

    g_logits, g_parts, g_experts, g_grads = got[:4]
    w_logits, w_parts, w_chosen, w_grads = want[:4]
    spread = float(np.std(w_logits))
    same = []
    for experts, chosen in zip(g_experts, w_chosen):
        mine = np.asarray(experts)
        if mine.shape != chosen.shape:  # indices [tokens, top_k], not a mask
            mine = np.zeros(chosen.shape, bool)
            np.put_along_axis(mine, np.asarray(experts), True, axis=-1)
        same.append(np.all(mine == np.asarray(chosen), axis=-1))
    # Tokens routed alike in every layer, as rows of the [1, time, vocab] logits.
    alike = np.all(same, axis=0)
    diff = (np.asarray(g_logits, np.float64) - np.asarray(w_logits, np.float64))[0][alike]
    rel = lambda a, b: float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    return {
        "logit_spread": spread,
        "logit_max_over_spread": float(np.abs(diff).max()) / spread,
        "logit_rms_over_spread": float(np.sqrt(np.mean(diff**2))) / spread,
        "loss": [float(g_parts["total"]), float(w_parts["total"])],
        "loss_abs": abs(float(g_parts["total"]) - float(w_parts["total"])),
        "aux_loss": [float(g_parts["aux_loss"]), float(w_parts["aux_loss"])],
        "aux_loss_abs": abs(float(g_parts["aux_loss"]) - float(w_parts["aux_loss"])),
        "z_loss": [float(g_parts["z_loss"]), float(w_parts["z_loss"])],
        "z_loss_rel": abs(float(g_parts["z_loss"]) / float(w_parts["z_loss"]) - 1.0),
        "same_experts_share_min": float(min(np.mean(x) for x in same)),
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
        elif key.endswith("_min"):
            out[key] = min(m[key] for m in per_sequence)
        else:
            out[key] = max(m[key] for m in per_sequence)
    return out


def within(readings: dict, column: int) -> dict:
    return {
        key: (readings[key] >= tol[column]) if key.endswith("_min") else (readings[key] <= tol[column])
        for key, tol in TOLERANCES.items()
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
            print("compare_reference: --rehearse is for the CPU", file=sys.stderr)
            return 2
    else:
        try:
            device.require_tpu(1)
        except device.NoAccelerator as e:
            print(f"compare_reference: {e}", file=sys.stderr)
            return 2
    config = load_config(args.config, args.rehearse)
    ref = cells.load_module("references", config["reference"])
    kwargs = dict(config["reference_kwargs"])
    make_model, params, tokens, targets = build(config, args.seed, args.sequences)
    kwargs["top_k"] = config["overrides"]["model_kwargs"]["moe_top_k"]
    kwargs["num_heads"] = config["overrides"]["model_kwargs"]["num_heads"]
    dev = jax.devices()[0]
    print(json.dumps({
        "config": args.config, "seed": args.seed, "tokens": list(tokens.shape),
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
        program = program_side(make_model(dtype), kwargs["top_k"])
        per_sequence = []
        for (t, y), w in zip(rows, want):
            if name == "f32":
                with jax.default_matmul_precision("highest"):
                    got = jax.device_get(program(params, t, y, w[4]))
            else:
                got = jax.device_get(program(params, t, y, w[4]))
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
