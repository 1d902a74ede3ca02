"""Micro-benchmark of the training attention core on one chip (PR 26).

One process, one call: every candidate for ``attention(impl="auto")`` at
the two benchmark cells' shapes, forward and forward + backward, bf16,
causal.  The numbers decide the route and go into PERF.md; the losers are
not kept as routes.

    chiprun -- python3 experiments/attention_core_bench.py

Candidates: today's ``blockwise_attention``; the tree's Pallas flash pair
as it is and at larger tiles; the fused kernels at several tiles; the two
kernels that ship with jax (``pallas.ops.tpu.flash_attention`` and
``splash_attention``), with the BTHD <-> BHTD copies they need inside the
timed function.  TFLOP/s by the full-square count (4 B H T^2 D forward,
three times that with the backward), the count PERF.md section 5 uses.
Each candidate's output and gradients are also compared with blockwise.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from distributed_tensorflow_models_tpu.ops import attention as A

SHAPES = {"gpt2m": (8, 1024, 16, 64), "olmoe": (4, 4096, 16, 128)}
REPEATS = 20


def _jax_flash(tile):
    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    def fn(q, k, v):
        T = q.shape[1]
        t = min(tile, T)
        bs = None if tile == 0 else fa.BlockSizes(
            block_q=t, block_k_major=t, block_k=t, block_b=1,
            block_q_major_dkv=t, block_k_major_dkv=t, block_k_dkv=t,
            block_q_dkv=t, block_k_major_dq=t, block_k_dq=t, block_q_dq=t,
        )
        sw = lambda x: jnp.swapaxes(x, 1, 2)
        out = fa.flash_attention(
            sw(q), sw(k), sw(v), causal=True,
            sm_scale=q.shape[-1] ** -0.5, block_sizes=bs,
        )
        return sw(out)

    return fn


def _jax_splash(tile, fused_bwd):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    def fn(q, k, v):
        B, T, H, D = q.shape
        t = min(tile, T)
        dq = {} if fused_bwd else dict(block_q_dq=t, block_kv_dq=t)
        bs = sk.BlockSizes(
            block_q=t, block_kv=t, block_kv_compute=t,
            block_q_dkv=t, block_kv_dkv=t, block_kv_dkv_compute=t,
            use_fused_bwd_kernel=fused_bwd, **dq,
        )
        # Built inside each trace: the kernel object holds the mask's
        # arrays, which are tracers under jit.
        kernel = sk.make_splash_mha(
            mask=sm.MultiHeadMask([sm.CausalMask((T, T))] * H),
            head_shards=1, q_seq_shards=1, block_sizes=bs,
        )
        sw = lambda x: jnp.swapaxes(x, 1, 2)
        # splash takes no scale: the query is scaled before (in bf16 —
        # exact at D=64, one rounding at D=128; a departure from the
        # route's mathematics, noted in PERF.md).
        qs = (q * (D ** -0.5)).astype(q.dtype)
        out = jax.vmap(kernel)(sw(qs), sw(k), sw(v))
        return sw(out)

    return fn


def candidates():
    c = {
        "blockwise": lambda q, k, v: A.blockwise_attention(q, k, v, causal=True),
        "tree_flash_asis": lambda q, k, v: A.flash_attention(q, k, v, True),
        "tree_flash_t512": lambda q, k, v: A.flash_attention(
            q, k, v, True, None, 512, 512
        ),
        "jax_flash_default": _jax_flash(0),
        "jax_flash_t512": _jax_flash(512),
        "jax_splash_t512": _jax_splash(512, False),
        "jax_splash_t512_fusedbwd": _jax_splash(512, True),
    }
    for bq, bkv in (
        (512, 512), (256, 256), (1024, 1024), (1024, 512), (512, 1024),
        (256, 512), (512, 256),
    ):
        c[f"fused_q{bq}_kv{bkv}"] = (
            lambda q, k, v, bq=bq, bkv=bkv: A.fused_attention(
                q, k, v, True, None, bq, bkv
            )
        )
    return c


def _time(f, args):
    out = f(*args)
    jax.block_until_ready(out)
    best = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(REPEATS):
            out = f(*args)
        jax.block_until_ready(out)
        best.append((time.perf_counter() - t0) / REPEATS)
    return float(np.median(best)) * 1e3


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("no TPU: this is a chip measurement", file=sys.stderr)
        return 2
    only = set(sys.argv[1:])
    rows = []
    for cell, shape in SHAPES.items():
        B, T, H, D = shape
        keys = jax.random.split(jax.random.key(2147483659), 4)
        q, k, v, w = (
            (jax.random.normal(kk, shape, jnp.float32) * 0.5).astype(jnp.bfloat16)
            for kk in keys
        )
        flops_fwd = 4.0 * B * H * T * T * D
        base = None
        for name, fn in candidates().items():
            if only and name not in only and name != "blockwise":
                continue
            row = {"cell": cell, "shape": list(shape), "candidate": name}
            try:
                fwd = jax.jit(fn)
                # The cotangent is an argument: closed over, it would be
                # a 67 MB constant in every executable.
                grad = jax.jit(jax.value_and_grad(
                    lambda q, k, v, w: jnp.sum(
                        (fn(q, k, v) * w).astype(jnp.float32)
                    ),
                    argnums=(0, 1, 2),
                ))
                out = fwd(q, k, v)
                _, gs = grad(q, k, v, w)
                got = [out, *gs]
                if base is None:
                    base = got
                else:
                    row["max_abs_diff_vs_blockwise"] = [
                        float(jnp.max(jnp.abs(
                            a.astype(jnp.float32) - b.astype(jnp.float32)
                        )))
                        for a, b in zip(got, base)
                    ]
                row["fwd_ms"] = _time(fwd, (q, k, v))
                row["fwd_bwd_ms"] = _time(grad, (q, k, v, w))
                row["fwd_tflops"] = flops_fwd / row["fwd_ms"] / 1e9
                row["fwd_bwd_tflops"] = 3 * flops_fwd / row["fwd_bwd_ms"] / 1e9
            except Exception as e:  # noqa: BLE001 — a refusal is a result
                row["error"] = f"{type(e).__name__}: {str(e)[-400:]}"
            rows.append(row)
            print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    name = "attention_core_bench" + ("_subset" if only else "")
    with open(f"chiprun_out/{name}.json", "w") as f:
        json.dump(
            {"device": dev.device_kind, "repeats": REPEATS, "rows": rows},
            f, indent=1,
        )
    return 0


if __name__ == "__main__":
    sys.exit(main())
