#!/usr/bin/env python3
"""The program's ``nemotron3_nano`` model against its plain reference at
the configuration's own widths and the cell's length, outside any timed
window.

    chiprun -- python3 benchmark/tools/compare_reference_nemotron_h.py --seed <n>

Builds the configuration's model (``benchmark/configs/nemotron3_nano.json``:
program config and overrides) with weights from ``--seed`` (every norm
scale, ``A_log``, ``dt_bias``, ``D`` and the convolution's bias moved off
its initial value, so that a dropped term would show), takes ``--sequences`` sequences of the cell's
stream, one at a time (the reference walks 8,192 tokens one by one and
keeps a second float32 copy of every weight), and prints one JSON line
per comparison and a last line with ``ok``.  Three comparisons:

- ``bf16``: the program as the cell runs it (bf16 compute over f32
  parameters) against the reference (f32, precision ``highest``);
- ``f32``: the program in float32 under
  ``jax.default_matmul_precision("highest")``, which has to agree with
  the reference to rounding;
- ``reference_bf16``: the reference itself with everything in bfloat16
  (the decay, the recurrent state, the norms, the router and the logits
  too: the nearest precision below what the configuration states), which
  has to come out as **not** correct under the ``bf16`` tolerances.

Each prints: the share of tokens whose chosen experts are the same set on
both sides, the worst of the expert layers; over the tokens routed alike
in every layer, the largest and the root-mean-square logit difference
over the spread (standard deviation) of the reference's logits; the loss
of both sides; the share of assignments on held experts; and the relative
error (norm of the difference over the norm of the reference's) of the
gradient of the loss for: the first Mamba-2 layer's ``W_in``, ``A_log``,
``dt_bias``, ``D``, the convolution's taps and the grouped gated norm's
weight, the attention layer's ``W_q`` and ``W_k`` (sixteen query heads a
key/value head), and of the first expert layer the router, the shared
expert's first matrix and the two matrices of the held expert that got
most tokens.

The tolerances (``TOLERANCES``) are what the chip runs of PR 40 support,
with the reason beside each.  ``--rehearse`` runs the cell's tiny size on
the CPU, to find wrong paths before chip time is spent; it holds the
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
CONFIG = "nemotron3_nano"

# name -> (bf16 program, f32 program at "highest"); None: not judged.
# From the chip runs of PR 40 at published widths and 8,192 positions,
# seven seeds, one sequence each (PERF.md section 6).  Four bf16 limits lie
# between the largest reading the program gave and the smallest the
# reference gave one precision down (``reference_bf16``): the logits' rms,
# the loss, the held share and the share of tokens with the same experts
# tell the two apart on every seed.
#
# What "to rounding" can mean here (as for ``kimi_linear``: PERF.md section
# 7).  The top-6 of 128 sigmoid scores has near-ties: in float32 a token in
# a few hundred takes another expert in some layer on the two sides (the
# scores differ in the seventh digit), and this stack mixes the sequence
# after every expert layer, so that token's other hidden state reaches
# every later position.  So the float32 program is judged **before the
# first token that routes otherwise** (``early_logit_max_over_spread``:
# whole chunks of the scan, the attention, every layer), where it has to
# agree to rounding; after it, the limits allow for what a few re-routed
# tokens do to the rest.  In bf16 the first other choice comes within the
# first tokens, so that reading is not judged there; the share of tokens
# with the same experts is.
TOLERANCES = {
    # bf16 0.55-0.71, reference_bf16 0.62-0.68: bounds single outliers
    # only, and does not tell the two apart.  float32: 1.6e-4 to 3.5e-4 on
    # six seeds and 0.12 on the seventh, where a token re-routed at
    # position 1,080 moves what later tokens read of it.
    "logit_max_over_spread": (0.9, 0.3),
    # bf16 0.0160-0.0184 against 0.0234-0.0285; float32 1.9e-5 to 3.3e-5,
    # and 6.9e-4 on the seed with the early re-routed token.
    "logit_rms_over_spread": (0.021, 2e-3),
    # float32 9.9e-5 to 3.1e-4 over 481 to 1,372 tokens: the sharp reading.
    "early_logit_max_over_spread": (None, 1e-3),
    # The program takes logits, softmax and cross entropy in float32:
    # 3e-5 to 9.9e-4 against 0.0045-0.022; float32 0 to 1.5e-5.
    "loss_abs": (0.003, 1e-4),
    # A float32 router: 1e-5 to 1.5e-4 against 1.3e-3 to 2.2e-3; float32 0
    # to 5e-6.
    "held_share_abs": (4e-4, 5e-5),
    # The worst expert layer: 0.871-0.883 against 0.771-0.809 (float32:
    # 0.9991-0.9999).
    "same_experts_share_min": (0.84, 0.995),
    # The worst leaf, the router's: 0.089-0.198 against 0.185-0.310, which
    # overlap on one seed of seven: bounds a wrong backward pass, and does
    # not tell the two apart; the other leaves 0.02-0.04 against 0.03-0.08
    # (float32: 3e-4 to 1.2e-3, and 8.5e-3 with the early re-routed token).
    "grad_rel": (0.25, 0.02),
}


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
        lambda key: make_model(jnp.float32).init(key, tokens[:1, :128])["params"]
    )(jax.random.key(seed))

    def move(path, leaf):
        if path[-1].key not in ("scale", "A_log", "dt_bias", "D", "conv_bias"):
            return leaf
        key = jax.random.fold_in(jax.random.key(seed + 1), hash(str(path)) % (2**31))
        return leaf + 0.05 * jax.random.normal(key, leaf.shape, leaf.dtype)

    params = jax.tree_util.tree_map_with_path(move, params)
    return make_model, params, tokens, targets


def layout(params: dict) -> dict:
    """The first block of each kind, by name."""
    blocks = sorted((k for k in params if k.startswith("blocks_")), key=lambda k: int(k[7:]))
    first = lambda test: next(b for b in blocks if test(params[b]))
    return {
        "ssm": first(lambda p: "ssm" in p),
        "attn": first(lambda p: "attn" in p),
        "moe": first(lambda p: "moe" in p),
        "moe_layers": [b for b in blocks if "moe" in params[b]],
    }


def selected(tree: dict, names: dict, expert) -> dict:
    """The leaves whose gradients are compared; of the expert stacks,
    held expert ``expert``'s matrices."""
    ssm, attn, moe = (tree[names[k]][k] for k in ("ssm", "attn", "moe"))
    return {
        "ssm_w_in": ssm["in_proj"]["kernel"],
        "ssm_a_log": ssm["A_log"],
        "ssm_dt_bias": ssm["dt_bias"],
        "ssm_d": ssm["D"],
        "ssm_conv": ssm["conv"],
        "ssm_norm": ssm["norm"]["scale"],
        "attn_wq": attn["query"]["kernel"],
        "attn_wk": attn["key"]["kernel"],
        "router": moe["router"],
        "shared_up": moe["shared"]["up"]["kernel"],
        "w_up": moe["w_up"][expert],
        "w_down": moe["w_down"][expert],
    }


def program_side(model, names: dict, top_k: int, routing):
    """``params, tokens, targets, expert -> (logits, parts, experts,
    grads)`` of the program's model: logits, the loss as ``lm_loss_fn``
    composes it (cross entropy; this model sows nothing into ``losses``),
    the chosen experts per expert layer (the program's own ``route_topk``
    on the input of each) and the selected gradients."""
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
        loss = nll + sum(jnp.sum(x) for x in jax.tree.leaves(updated.get("losses", {})))
        stats = updated["moe_stats"]
        experts = []
        for layer in names["moe_layers"]:
            h = updated["intermediates"][layer]["ln2"]["__call__"][0]
            h = h.astype(model.dtype).reshape(-1, h.shape[-1])
            experts.append(
                moelib.route_topk(params[layer]["moe"]["router"], h, top_k, routing)[3]
            )
        held = sum(stats[l]["moe"]["held_share"] for l in names["moe_layers"])
        parts = {"total": loss, "nll": nll, "held_share": held / len(names["moe_layers"])}
        return loss, (logits, parts, experts)

    def run(params, tokens, targets, expert):
        (_, (logits, parts, experts)), grads = jax.value_and_grad(total, has_aux=True)(
            params, tokens, targets
        )
        return logits, parts, experts, selected(grads, names, expert)

    return jax.jit(run)


def reference_side(ref, names: dict, kwargs: dict, count: int, dtype=None):
    """The same of the reference, in float32 or in ``dtype``; its fifth
    result is the held expert of the first expert layer that got most
    tokens (an index into the expert stacks)."""
    import jax
    import jax.numpy as jnp

    kwargs = dict(kwargs, dtype=dtype or jnp.float32)
    first = kwargs["held_first"]

    def run(params, tokens, targets):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: ref.loss(p, tokens, targets, **kwargs), has_aux=True
        )(params)
        logits = ref.forward(params, tokens, **kwargs)
        chosen = ref.routing(params, tokens, **kwargs)
        busiest = jnp.argmax(jnp.sum(chosen[0][:, first : first + count], axis=0))
        return logits, {"total": loss, **parts}, chosen, selected(grads, names, busiest), busiest

    return jax.jit(run)


def margins(got, want) -> dict:
    """One sequence's readings: ``got`` is the program's, ``want`` the
    reference's ``(logits, parts, experts, grads)``, already on the host."""
    import numpy as np

    g_logits, g_parts, g_experts, g_grads = got[:4]
    w_logits, w_parts, w_chosen, w_grads = want[:4]
    spread = float(np.std(np.asarray(w_logits, np.float64)))
    same = []
    for experts, chosen in zip(g_experts, w_chosen):
        mine = np.asarray(experts)
        if mine.shape != chosen.shape:  # indices [tokens, top_k], not a mask
            mine = np.zeros(chosen.shape, bool)
            np.put_along_axis(mine, np.asarray(experts), True, axis=-1)
        same.append(np.all(mine == np.asarray(chosen), axis=-1))
    alike = np.all(same, axis=0)
    everywhere = (np.asarray(g_logits, np.float64) - np.asarray(w_logits, np.float64))[0]
    diff = everywhere[alike]
    # The model is causal: before the first token whose experts differ in
    # some layer, nothing a near-tie did has reached any logit.
    clean = int(np.argmin(alike)) if not alike.all() else len(alike)
    before = everywhere[: max(clean, 1)]
    rel = lambda a, b: float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))
    return {
        "logit_spread": spread,
        "logit_max_over_spread": float(np.abs(diff).max()) / spread,
        "logit_rms_over_spread": float(np.sqrt(np.mean(diff**2))) / spread,
        "tokens_before_the_first_other_choice": clean,
        "early_logit_max_over_spread": float(np.abs(before).max()) / spread,
        "loss": [float(g_parts["total"]), float(w_parts["total"])],
        "loss_abs": abs(float(g_parts["total"]) - float(w_parts["total"])),
        "held_share": [float(g_parts["held_share"]), float(w_parts["held_share"])],
        "held_share_abs": abs(float(g_parts["held_share"]) - float(w_parts["held_share"])),
        "same_experts_share_min": float(min(np.mean(x) for x in same)),
        "routed_alike_in_every_layer": float(np.mean(alike)),
        "grad_rel_by_leaf": {
            k: rel(np.asarray(g_grads[k], np.float64), np.asarray(w_grads[k], np.float64))
            for k in w_grads
        },
    }


def worst(per_sequence: list) -> dict:
    """The worst reading of each margin over the sequences."""
    out = {"tokens_before_the_first_other_choice": min(
        m["tokens_before_the_first_other_choice"] for m in per_sequence
    )}
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
    from distributed_tensorflow_models_tpu.parallel import moe as moelib

    if args.rehearse:
        if jax.devices()[0].platform == "tpu":
            print("compare_reference_nemotron_h: --rehearse is for the CPU", file=sys.stderr)
            return 2
    else:
        try:
            device.require_tpu(1)
        except device.NoAccelerator as e:
            print(f"compare_reference_nemotron_h: {e}", file=sys.stderr)
            return 2
    config = load_config(args.rehearse)
    ref = cells.load_module("references", config["reference"])
    model_kwargs = config["overrides"]["model_kwargs"]
    held_first, held_count = model_kwargs["moe_held"]
    kwargs = dict(
        config["reference_kwargs"], num_heads=model_kwargs["num_heads"],
        num_kv_heads=model_kwargs["num_kv_heads"],
        ssm_groups=model_kwargs["ssm_num_groups"],
        top_k=model_kwargs["moe_top_k"], held_first=held_first,
    )
    routing = moelib.Routing(
        model_kwargs["moe_scoring"], model_kwargs["moe_renormalize"],
        model_kwargs["moe_routed_scale"],
    )
    make_model, params, tokens, targets = build(config, args.seed, args.sequences)
    names = layout(params)
    dev = jax.devices()[0]
    print(json.dumps({
        "config": CONFIG, "seed": args.seed, "tokens": list(tokens.shape),
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "parameters": sum(x.size for x in jax.tree.leaves(params)),
        "tolerances": TOLERANCES, "rehearsal": args.rehearse,
    }), flush=True)

    rows = [(tokens[i : i + 1], targets[i : i + 1]) for i in range(tokens.shape[0])]
    reference = reference_side(ref, names, kwargs, held_count)
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
        program = program_side(make_model(dtype), names, kwargs["top_k"], routing)
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
    low = reference_side(ref, names, kwargs, held_count, jnp.bfloat16)
    per_sequence = [margins(jax.device_get(low(params, t, y)), w) for (t, y), w in zip(rows, want)]
    report("reference_bf16", 0, per_sequence, False)
    print(json.dumps({"ok": ok}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
