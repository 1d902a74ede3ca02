"""``fit`` through each language model's program config, once: the normal
path at the smallest size that has every kind of layer the model has, a
few steps on the CPU, and what the run wrote (``metrics.jsonl``,
``telemetry.json``, ``step_scopes_p0.json``) read against one table.  A
count of traced calls is the model's layers of that kind times two
(``model.init`` and the step).
"""

import dataclasses
import json
import os
import re
import subprocess
import sys
import types

import jax
import numpy as np
import pytest

from distributed_tensorflow_models_tpu.core import mesh as meshlib
from distributed_tensorflow_models_tpu.harness import train as trainlib
from distributed_tensorflow_models_tpu.harness.config import get_config
from distributed_tensorflow_models_tpu.telemetry import registry as reglib


def _fit(workdir, config, model_kwargs, *, devices, optimizer=None, **overrides):
    """Run ``config`` at ``model_kwargs`` over the first ``devices`` CPU
    devices, a row of the batch each and no fewer than two (``None``: the
    mesh ``fit`` makes of the config, all 8), and return what it gave and
    wrote."""
    cfg = get_config(
        config, model_kwargs={**get_config(config).model_kwargs, "vocab_size": 97, **model_kwargs},
        vocab_size=97, global_batch_size=max(2, devices or 8), log_every_steps=2, **overrides,
    )
    mesh = meshlib.data_parallel_mesh(jax.devices()[:devices]) if devices else None
    if optimizer:
        cfg = cfg.replace(optimizer=dataclasses.replace(cfg.optimizer, **optimizer))
    counters = reglib.get_registry().snapshot()
    result = trainlib.fit(cfg, workdir, mesh=mesh)

    def written(name):
        path = os.path.join(workdir, name)
        return open(path).read() if os.path.exists(path) else ""

    rows = [json.loads(line) for line in written("metrics.jsonl").splitlines() if line.strip()]
    now = reglib.get_registry().snapshot()
    return types.SimpleNamespace(
        cfg=cfg, result=result, workdir=workdir,
        rows=[r for r in rows if "loss" in r],
        telemetry=json.loads(written("telemetry.json") or "{}").get("metrics", {}),
        scopes=written("step_scopes_p0.json"),
        # Counted in this process's registry while the run traced.
        traced=lambda name: now.get(name, 0) - counters.get(name, 0),
    )


def _olmoe(run):
    final = run.result.final_metrics
    assert np.isfinite(final["loss"]) and final["loss"] > final["nll"]
    assert final["moe_load_max_over_mean"] >= 1.0
    # The weighted router losses are what ``losses`` adds to the objective
    # (one layer: the means over layers, once).
    weighted = 0.01 * final["moe_aux_loss"] + 0.001 * final["moe_z_loss"]
    assert final["aux_loss"] == pytest.approx(weighted, rel=0.2)


def _kimi_linear(run):
    final = run.rows[-1]
    assert np.isfinite(final["loss"])
    # 4 of 16 experts held: about a quarter of the assignments, and the
    # three routing statistics beside it; no auxiliary loss in the objective.
    assert 0.05 < final["moe_held_share"] < 0.6
    # The held rows of so small a step fit one slab, and the recomputed half keeps
    # its one expert layer's routing plan (``model.init`` and the step program).
    assert final["moe_held_slabs"] == 1.0 and run.telemetry["moe/plan_kept"] == 2
    assert final["moe_load_max_over_mean"] >= 1.0 and "moe_aux_loss" in final
    assert "aux_loss" not in final and final["loss"] == pytest.approx(final["nll"])
    check = subprocess.run(
        [sys.executable, "scripts/check_metrics_schema.py", os.path.join(run.workdir, "metrics.jsonl")],
        capture_output=True, text=True,
    )
    assert check.returncode == 0, check.stdout + check.stderr
    # The MLA layer's call and the KDA layer's, each counted once per traced
    # program (blockwise and plain on the CPU), the mixer's placement beside its core's route.
    assert run.telemetry["attention/route_blockwise"] >= 1 and run.telemetry.get("attention/route_fused", 0) == 0
    assert run.telemetry["kda/route_plain"] >= 1
    assert run.telemetry["kda/mixer_plain"] == run.telemetry["kda/route_plain"]
    # The plain route has no core whose results a half could keep; the counter is copied all the same.
    assert run.telemetry["remat/cores_kept"] == 0


def _olmo_hybrid(run):
    assert not any(k.startswith("moe_") for k in run.rows[-1]) and "gdn/route_kernel" not in run.telemetry


def _nemotron_h(run):
    held = [r["moe_held_share"] for r in run.rows if "moe_held_share" in r]
    assert held and all(0.0 < h < 1.0 for h in held)
    assert all("moe_load_max_over_mean" in r for r in run.rows if "moe_held_share" in r)
    assert all(1.0 <= r["moe_held_slabs"] <= 2.0 for r in run.rows if "moe_held_share" in r)
    assert run.telemetry["moe/plan_kept"] == 2 and run.telemetry["remat/products_kept"] > 0


_NARROW = {"num_heads": 4, "d_model": 64, "max_len": 40}
_SSM = {"ssm_num_heads": 4, "ssm_head_dim": 8, "ssm_state_dim": 16, "ssm_chunk": 16}
_FUSED_HEAD = dict(devices=1, num_steps=40, train_steps=12, fused_unembed=True, trace_export=True)
# Granite's and Nemotron's runs warm up in three steps: a dozen steps of
# the configs' 2,000-step warm-up move nothing a loss row can show over
# the batches' own noise.
_QUICK_WARMUP = {"warmup_steps": 3, "learning_rate": 3e-3}
# ``model``: the smallest model with every kind of layer; ``fit``: ``_fit``'s
# arguments; ``falls``: the loss rows do; ``head``: the state has a ``head``
# leaf; ``telemetry``: what telemetry.json holds to the count; ``traced``:
# what this process's registry counted meanwhile; ``scopes`` / ``absent``:
# on the step's map or not; ``more``: what else the run has to show.
FITS = {
    # On the data mesh of the 8 fake devices: experts in every layer, a row a device.
    "olmoe": dict(
        model={**_NARROW, "num_layers": 1, "d_ff": 32, "max_len": 32, "num_experts": 8, "moe_top_k": 2},
        fit=dict(devices=None, num_steps=32, train_steps=2), more=_olmoe,
    ),
    # A delta-rule layer over a dense feed-forward, latent attention over experts (4 of 16 held).
    "kimi_linear": dict(
        model={**_NARROW, "num_layers": 2, "layer_mixers": ("kda", "mla"), "d_ff": 32, "dense_d_ff": 96,
               "kda_num_heads": 4, "kda_head_dim": 16, "mla_kv_lora_rank": 24, "mla_nope_dim": 16,
               "mla_rope_dim": 8, "mla_v_dim": 16, "num_experts": 16, "moe_top_k": 4, "moe_held": (4, 4)},
        fit=dict(devices=1, num_steps=40, train_steps=4, trace_export=True),
        telemetry={"kda/route_kernel": 0, "kda/mixer_fused": 0}, more=_kimi_linear,
        scopes=("linear_attn", "kda_core", "attention_core", "moe_shared", "moe_dispatch", "moe_experts"),
    ),
    "olmo_hybrid": dict(
        model={**_NARROW, "num_layers": 2, "layer_mixers": ("gdn", "attention"), "num_heads": 3, "head_dim": 16,
               "d_ff": 96, "gdn_num_heads": 3, "gdn_key_dim": 12, "gdn_value_dim": 24},
        fit=_FUSED_HEAD, falls=True, recipe=True, more=_olmo_hybrid,
        telemetry={"gdn/route_plain": 2, "attention/route_blockwise": 2, "kda/route_plain": 0,
                   "unembed/grad_in_forward": 1},
        traced={reglib.GDN_ROUTE_PLAIN: 2},
        scopes=("linear_attn", "gdn_core", "attention_core", "unembed_loss", "optimizer"), absent=("kda_core",),
    ),
    # The fused head fed from the tied embedding.
    "granite_h_micro": dict(
        model={**_NARROW, **_SSM, "num_layers": 2, "layer_mixers": ("ssm", "attention"), "num_kv_heads": 2,
               "d_ff": 96},
        fit={**_FUSED_HEAD, "optimizer": _QUICK_WARMUP}, falls=True, recipe=True, head=False,
        telemetry={"ssd/route_plain": 2, "ssd/route_kernel": 0, "attention/route_blockwise": 2,
                   "gdn/route_plain": 0, "unembed/grad_in_forward": 1},
        traced={reglib.SSD_ROUTE_PLAIN: 2},
        scopes=("ssm", "ssd_core", "attention_core", "unembed_loss", "optimizer"),
        absent=("gdn_core", "linear_attn"),
    ),
    # A state-space layer, an expert layer that holds 4 of 8 experts' share, an attention layer.
    "nemotron3_nano": dict(
        model={**_NARROW, **_SSM, "num_layers": 3, "layer_mixers": ("ssm_only", "ffn_only", "attention_only"),
               "num_kv_heads": 2, "head_dim": 8, "d_model": 48, "d_ff": 40, "ssm_num_groups": 2,
               "num_experts": 8, "moe_top_k": 2, "moe_shared_d_ff": 56, "moe_held": (2, 4)},
        fit={**_FUSED_HEAD, "optimizer": _QUICK_WARMUP}, falls=True, recipe=True, head=True, more=_nemotron_h,
        telemetry={"ssd/route_plain": 2, "ssd/route_kernel": 0, "attention/route_blockwise": 2,
                   "unembed/grad_in_forward": 1},
        scopes=("ssm", "ssd_core", "moe", "moe_dispatch", "moe_experts", "moe_shared", "attention_core",
                "unembed_loss", "optimizer"),
        absent=("gdn_core", "linear_attn"),
    ),
    # Every kind of layer of the decoder-hybrid-decoder: a Mamba-1 layer, window and full
    # differential attention, a memory unit and a cross-attention over what two of them hand on.
    "phi4_mini_flash": dict(
        model={**_NARROW, "num_heads": 8, "num_kv_heads": 4, "num_layers": 6, "d_ff": 96, "attn_window": 16,
               "layer_mixers": ("mamba1", "attention", "mamba1", "attention_full", "gmu", "cross"),
               "layer_ids": (0, 1, 16, 17, 18, 19), "mamba1_inner": 128, "mamba1_state_dim": 4,
               "mamba1_dt_rank": 4, "mamba1_chunk": 16},
        fit={**_FUSED_HEAD, "optimizer": _QUICK_WARMUP}, falls=True, recipe=True, head=False,
        telemetry={"sscan/route_plain": 4, "sscan/route_kernel": 0, "attention/route_blockwise": 6,
                   "ssd/route_plain": 0, "unembed/grad_in_forward": 1},
        traced={reglib.SSCAN_ROUTE_PLAIN: 4},
        scopes=("ssm", "sscan_core", "gmu", "attention_core", "swa_core", "unembed_loss", "optimizer"),
        absent=("ssd_core", "linear_attn"),
    ),
}


@pytest.mark.parametrize("config", sorted(FITS))
def test_fit_trains_the_program_config_and_writes_what_its_readers_find(tmp_path, config):
    want = FITS[config]
    if want.get("recipe"):  # the published one, whatever the run warms up in
        recipe = get_config(config).optimizer
        assert recipe.warmup_steps == 2000 and recipe.clip_global_norm == 1.0
    run = _fit(str(tmp_path / "fit"), config, want["model"], **want["fit"])
    assert run.result.steps_run == int(run.result.state.step) == want["fit"]["train_steps"]
    if want.get("falls"):
        losses = [r["loss"] for r in run.rows]
        assert all(np.isfinite(losses)) and losses[-1] < losses[0]
    if "head" in want:
        assert ("head" in run.result.state.params) == want["head"]
    assert {k: run.telemetry[k] for k in want.get("telemetry", {})} == want.get("telemetry", {})
    assert {k: run.traced(k) for k in want.get("traced", {})} == want.get("traced", {})
    for name in want.get("scopes", ()):
        # A whole path element, bare or inside a transform's brackets.
        assert re.search(rf"[/(]{name}[/)]", run.scopes), name
    assert not [name for name in want.get("absent", ()) if name in run.scopes]
    want.get("more", lambda run: None)(run)
