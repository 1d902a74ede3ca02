"""Experiment configs: one dataclass per reference training configuration.

Replaces the reference's per-driver ``tf.app.flags`` blocks (SURVEY.md §5.6)
with typed dataclasses.  The registry names correspond to BASELINE.json's
config list [B:6-12]: MNIST LeNet, CIFAR-10 ResNet-32 sync-DP, ImageNet
Inception-v3, ImageNet ResNet-50 (the async-vs-sync A/B model), and the PTB
LSTM small/medium/large family.

Hyperparameters follow the reference lineage (TF tutorials / slim defaults):
e.g. Inception-v3's RMSProp(decay=0.9, momentum=0.9, eps=1.0), lr 0.045
decayed 0.94 every 2 epochs, label smoothing 0.1, aux-loss weight 0.4, EMA
0.9999 (SURVEY.md §2.1 R5); PTB's staged-LR SGD + global-norm clipping (R8).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import optax

from distributed_tensorflow_models_tpu.ops import optim

# Default multi-host preemption-notice poll cadence in steps — THE one
# definition: harness/train.py's loop fallback and harness/startup.py's
# dominant-chunk-length mirror must agree, or multi-host AOT compiles
# would target a chunk length the loop never produces.
PREEMPT_POLL_STEPS_DEFAULT = 20


@dataclasses.dataclass(frozen=True)
class OptimizerConfig:
    name: str = "sgd"  # sgd | momentum | rmsprop | adam
    learning_rate: float = 0.1
    # LR schedule: exponential decay (staircase) as in the reference
    # (TF learning_rate_decay, SURVEY.md §2.2 F16); None = constant.
    decay_steps: Optional[int] = None
    decay_rate: float = 0.94
    staircase: bool = True
    momentum: float = 0.9
    rmsprop_decay: float = 0.9
    rmsprop_epsilon: float = 1.0
    # Global-norm gradient clipping (PTB path, TF clip_ops.py:300).
    clip_global_norm: Optional[float] = None
    # Zaremba staged schedule (PTB): constant for ``hold_epochs`` epochs of
    # ``steps_per_epoch`` steps, then x ``decay_rate`` per epoch.  When set,
    # takes precedence over the exponential fields.
    steps_per_epoch: Optional[int] = None
    hold_epochs: Optional[int] = None
    # Linear warm-up over this many steps, on top of whatever follows
    # (0: none).  Step ``t`` (from 0) runs at ``(t + 1) / warmup_steps``
    # of the schedule's value.
    warmup_steps: int = 0

    def schedule(self) -> float | optax.Schedule:
        base = self._after_warmup()
        if not self.warmup_steps:
            return base
        return optim.linear_warmup(base, self.warmup_steps)

    def _after_warmup(self) -> float | optax.Schedule:
        if self.steps_per_epoch is not None and self.hold_epochs is not None:
            return optim.zaremba_decay(
                self.learning_rate,
                self.steps_per_epoch,
                self.hold_epochs,
                self.decay_rate,
            )
        if self.decay_steps is None:
            return self.learning_rate
        return optim.exponential_decay(
            self.learning_rate,
            self.decay_steps,
            self.decay_rate,
            staircase=self.staircase,
        )

    def make(self) -> optax.GradientTransformation:
        lr = self.schedule()
        if self.name == "sgd":
            tx = optim.sgd(lr)
        elif self.name == "momentum":
            tx = optim.tf_momentum(lr, self.momentum)
        elif self.name == "rmsprop":
            tx = optim.tf_rmsprop(
                lr,
                decay=self.rmsprop_decay,
                momentum=self.momentum,
                epsilon=self.rmsprop_epsilon,
            )
        elif self.name == "adam":
            tx = optim.adam(lr)
        else:
            raise ValueError(f"unknown optimizer {self.name!r}")
        if self.clip_global_norm is not None:
            tx = optax.chain(
                optim.clip_by_global_norm(self.clip_global_norm), tx
            )
        return tx


@dataclasses.dataclass(frozen=True)
class ExperimentConfig:
    """Everything one training run needs.  ``task`` selects the driver
    wiring: ``classification`` or ``lm``."""

    name: str
    model: str
    task: str = "classification"
    model_kwargs: dict[str, Any] = dataclasses.field(default_factory=dict)
    dataset: str = "mnist"  # mnist|cifar10|imagenet|imagenet_synthetic|ptb
    image_size: int = 28
    global_batch_size: int = 256
    optimizer: OptimizerConfig = dataclasses.field(
        default_factory=OptimizerConfig
    )
    # Loss shaping (Inception path, SURVEY.md §7.4.2).
    label_smoothing: float = 0.0
    weight_decay: float = 0.0
    aux_loss_weight: float = 0.0
    # EMA of weights for eval (TF moving_averages.py:284; None = off).
    ema_decay: Optional[float] = None
    # LM settings (R8).
    num_steps: int = 35
    vocab_size: int = 10000
    # Loop control (reference cadences: summaries/logs every 100 steps,
    # checkpoint every 600 s — TF monitored_session.py:517-532).
    train_steps: int = 1000
    # Fused multi-step dispatch: lax.scan the train step over this many
    # stacked batches per jitted call (core/train_loop.py::make_multi_step)
    # — one host dispatch + one metrics transfer per chunk instead of per
    # step.  1 = today's per-step loop.  Raise it for small/fast models
    # where host dispatch + hook overhead, not the chip, bounds step rate
    # (telemetry's dispatch_s vs step_time_s split is the diagnostic —
    # README "Performance").  Chunks auto-shrink to end exactly at
    # log_every_steps boundaries and train_steps, so every hook fires at
    # precisely the same steps as the unfused loop; trajectories are
    # bit-identical either way (tests/test_train_loop.py pins this).
    steps_per_loop: int = 1
    # Parallel host input pipeline: N worker threads run the per-batch
    # assemble/decode/augment in parallel behind an ordered-reassembly
    # stage (data/pipeline.py::HostPipeline) — the reference's
    # many-QueueRunner producer parallelism, made deterministic.  1 =
    # single producer thread.  The emitted batch stream is bit-identical
    # for ANY value and checkpoints stay resume-exact, so this is purely
    # a throughput knob: raise it when telemetry shows the host stream
    # starving the device (pipeline/prefetch_fill p95 fat) while workers
    # saturate (pipeline/worker_busy near 1) — README "Performance".
    data_workers: int = 1
    log_every_steps: int = 100
    checkpoint_every_secs: float = 600.0
    # Step-cadence checkpointing (None = clock-only).  Deterministic in
    # step, so it needs no multi-host clock broadcast and — unlike the
    # wall clock — reproduces exactly across restarts and replays;
    # chaos drills and bit-identity tests depend on that.  Both cadences
    # can be active at once (a save fires when either is due).
    checkpoint_every_steps: Optional[int] = None
    keep_checkpoints: int = 5
    # Restart-MTTR knobs (harness/startup.py; README "Performance").
    # xla_cache_dir: persistent XLA compilation cache for the production
    # path — a supervisor relaunch deserializes the train-step program
    # instead of recompiling it.  JAX_COMPILATION_CACHE_DIR, when set,
    # places the cache and wins over a path here; None = the fixed
    # <checkout>/.xla_cache; "" disables (startup.apply_compile_cache).
    # aot_compile: lower().compile() the train-step
    # program on a background thread *while the checkpoint restore
    # runs*, so a relaunch overlaps its two dominant serial costs; the
    # executable is bit-identical to the jit path's and a batch-spec
    # mismatch falls back to jit with only a wasted background compile
    # (a failed compile is re-raised, not fallen back from).
    xla_cache_dir: Optional[str] = None
    aot_compile: bool = True
    # Flight-recorder / event-trace knobs (telemetry/trace.py; README
    # "Observability").  trace_ring_events: bounded in-memory ring of
    # structured span/instant events — the default keeps tracing ON
    # (appends are ~1 µs, inside the telemetry 5 µs/step guard, and the
    # ring never touches disk on the happy path, so tier-1 wall time is
    # unchanged); 0 disables tracing entirely.  trace_export: write the
    # ring as Chrome-trace JSON (<workdir>/trace_p<i>.json,
    # Perfetto-loadable; scripts/fleet_report.py merges hosts) at every
    # fit exit — off by default (an artifact per fit is drill/debug
    # tooling, not a production default).  flight_recorder: dump the
    # ring + a registry snapshot to <workdir>/flight_recorder_p<i>.json
    # on abnormal exits (rollback, preemption, crash, chaos kill, and —
    # via the signal watcher — SIGTERM arrival even with the main
    # thread wedged in a dead peer's collective).
    trace_ring_events: int = 4096
    trace_export: bool = False
    flight_recorder: bool = True
    # Divergence policy (harness/train.py::fit).  "abort" = the reference
    # NanTensorHook behavior: a non-finite loss kills the run.  "rollback"
    # = restore the last finite checkpoint, advance the dataset cursor
    # exactly past the offending chunk (skip logged + counted as
    # train/skipped_batches), and retry — at most ``rollback_budget``
    # times per run, then abort.  README "Robustness".
    nan_policy: str = "abort"  # abort | rollback
    rollback_budget: int = 3
    # Step-progress watchdog (resilience/watchdog.py): warn when no chunk
    # completes within this many seconds (None = off); with
    # ``watchdog_abort`` the stall escalates to an abort attempt from the
    # second timeout interval on.  Live gauge:
    # train/watchdog_last_progress_s.
    watchdog_timeout_s: Optional[float] = None
    watchdog_abort: bool = False
    # Multi-host preemption-notice poll cadence (steps): the SIGTERM flag
    # is allgathered every this-many steps so all processes enter the
    # emergency checkpoint together (the poll is a collective — it cannot
    # run at every step for free).  Budget rule: poll_steps x step_time
    # must fit inside the fleet's preemption grace window, or the SIGKILL
    # lands before the flag is ever observed — lower it for slow-step
    # runs.  Single-process runs check the flag at every chunk boundary
    # and ignore this.
    preempt_poll_steps: int = PREEMPT_POLL_STEPS_DEFAULT
    # Deterministic chaos injection (resilience/chaos.py) — OFF when
    # empty.  Keys: pipeline_fail_at_batch, nan_at_step,
    # torn_checkpoint_at_step, sigterm_at_step (ints; each fires at most
    # once per process per workdir), plus the cross-host faults
    # kill_at_step (durably at-most-once per workdir), hide_newest_ckpt,
    # straggler_delay_ms — targeted at the process whose index is
    # chaos_host.  CLI: --chaos "nan_at_step=50,...".
    chaos: dict[str, Any] = dataclasses.field(default_factory=dict)
    eval_every_steps: Optional[int] = None
    eval_batches: Optional[int] = None
    seed: int = 0
    # Mesh axis sizes; -1 absorbs remaining devices (data axis).
    mesh_data: int = -1
    mesh_model: int = 1
    mesh_seq: int = 1
    mesh_pipe: int = 1
    mesh_expert: int = 1
    # Attention implementation for attention models: auto | reference |
    # blockwise ("auto" = the fused kernels on a TPU for the calls they
    # admit, blockwise elsewhere: ops/attention.py::auto_route, measured
    # on the chip, PERF.md PR 26).
    attn_impl: str = "auto"
    # Sequence/context parallelism over the ``seq`` axis: None | "ring"
    # (ppermute KV rotation) | "ulysses" (all_to_all head scatter).
    seq_impl: Optional[str] = None
    # Named tensor-parallel rule set (parallel/tensor.py RULE_SETS) applied
    # when mesh_model > 1; "" = fully replicated params.
    param_rules: str = ""
    # Fused chunked unembed+xent for LM configs (transformer only): the
    # head projection + cross entropy run chunked in one op, never
    # materializing [B*T, V] f32 logits (ops/losses.py).
    fused_unembed: bool = False

    def replace(self, **kw) -> "ExperimentConfig":
        return dataclasses.replace(self, **kw)


_CONFIGS: dict[str, ExperimentConfig] = {}


def _add(cfg: ExperimentConfig) -> ExperimentConfig:
    _CONFIGS[cfg.name] = cfg
    return cfg


# --- MNIST LeNet [B:7] — the single-worker reference config. -------------
_add(
    ExperimentConfig(
        name="lenet_mnist",
        model="lenet",
        dataset="mnist",
        image_size=28,
        global_batch_size=64,
        optimizer=OptimizerConfig(name="sgd", learning_rate=0.1),
        train_steps=2000,
    )
)

# --- CIFAR-10 ResNet-32 sync-replica DP [B:8]. ---------------------------
_add(
    ExperimentConfig(
        name="resnet32_cifar10",
        model="resnet32_cifar",
        dataset="cifar10",
        image_size=32,
        global_batch_size=128,
        optimizer=OptimizerConfig(
            name="momentum",
            learning_rate=0.1,
            momentum=0.9,
            decay_steps=20000,
            decay_rate=0.1,
        ),
        weight_decay=2e-4,
        train_steps=64000,
    )
)

# --- ImageNet Inception-v3 (slim) [B:9]. ---------------------------------
_add(
    ExperimentConfig(
        name="inception_v3_imagenet",
        model="inception_v3",
        dataset="imagenet",
        image_size=299,
        global_batch_size=256,
        optimizer=OptimizerConfig(
            name="rmsprop",
            learning_rate=0.045,
            rmsprop_decay=0.9,
            momentum=0.9,
            rmsprop_epsilon=1.0,
            # 0.94 decay every 2 epochs (epoch ~= 1.28M/256 = 5005 steps).
            decay_steps=10010,
            decay_rate=0.94,
        ),
        label_smoothing=0.1,
        aux_loss_weight=0.4,
        weight_decay=4e-5,
        ema_decay=0.9999,
        train_steps=500_000,
    )
)

# --- ImageNet ResNet-50 — the async-PS vs sync A/B model [B:10]. ---------
_add(
    ExperimentConfig(
        name="resnet50_imagenet",
        model="resnet50",
        dataset="imagenet",
        image_size=224,
        global_batch_size=256,
        optimizer=OptimizerConfig(
            name="momentum",
            learning_rate=0.1,
            momentum=0.9,
            decay_steps=150_000,  # ~30 epochs, staircase x0.1
            decay_rate=0.1,
        ),
        weight_decay=1e-4,
        train_steps=450_000,
    )
)

# --- Synthetic-input ResNet-50 (throughput benchmarking). ----------------
_add(
    ExperimentConfig(
        name="resnet50_synthetic",
        model="resnet50",
        dataset="imagenet_synthetic",
        image_size=224,
        global_batch_size=256,
        optimizer=OptimizerConfig(name="momentum", learning_rate=0.1),
        weight_decay=1e-4,
        train_steps=100,
    )
)

# --- PTB LSTM family [B:11] — Zaremba staged-LR SGD + grad clipping. -----
# Per-size (lr_decay, clip, hold_epochs "max_epoch", total epochs
# "max_max_epoch") exactly as the reference's small/medium/large configs.
# One epoch of the real PTB train split at batch 20 x num_steps ≈ 1327
# batches (20-step) / 1327·20/35 ≈ 758 (35-step).
for _size, _lr_decay, _clip, _hold, _total, _nsteps in (
    ("small", 0.5, 5.0, 4, 13, 20),
    ("medium", 0.8, 5.0, 6, 39, 35),
    ("large", 1 / 1.15, 10.0, 14, 55, 35),
):
    _spe = 929_589 // (20 * _nsteps)  # PTB train tokens / (batch*unroll)
    _add(
        ExperimentConfig(
            name=f"ptb_{_size}",
            model="ptb_lstm",
            task="lm",
            model_kwargs={"config": _size},
            dataset="ptb",
            global_batch_size=20,
            num_steps=_nsteps,
            optimizer=OptimizerConfig(
                name="sgd",
                learning_rate=1.0,
                decay_rate=_lr_decay,
                steps_per_epoch=_spe,
                hold_epochs=_hold,
                clip_global_norm=_clip,
            ),
            train_steps=_spe * _total,
        )
    )


# --- Transformer LM — the long-context/beyond-parity flagship. -----------
# Consumes the attention stack (ops/attention.py flash/blockwise), the
# sequence-parallel layer (parallel/ring.py via seq_impl + mesh_seq), the
# TP rule set (parallel/tensor.py via param_rules + mesh_model), and — in
# the _moe variant — expert parallelism (parallel/moe.py via mesh_expert).
_add(
    ExperimentConfig(
        name="transformer_lm",
        model="transformer_lm",
        task="lm",
        model_kwargs={
            "num_layers": 4,
            "num_heads": 8,
            "d_model": 256,
            "d_ff": 1024,
            "max_len": 512,
            "dropout_rate": 0.1,
        },
        dataset="ptb",
        global_batch_size=16,
        num_steps=256,  # sequence length per segment
        vocab_size=10000,
        optimizer=OptimizerConfig(
            name="adam", learning_rate=3e-4, clip_global_norm=1.0
        ),
        param_rules="transformer_tp",
        # Fused chunked head by default: this family is the
        # beyond-parity flagship, and the [B*T, V] f32 logits tensor is
        # its HBM ceiling (the PTB reference configs keep the two-stage
        # f32 head for TF-parity numerics; opt in there via
        # --fused-unembed).
        fused_unembed=True,
        train_steps=10_000,
    )
)

_add(
    _CONFIGS["transformer_lm"].replace(
        name="transformer_lm_moe",
        model_kwargs={
            **_CONFIGS["transformer_lm"].model_kwargs,
            "num_experts": 4,
        },
    )
)

# Modern decoder recipe: rotary positions, grouped-query KV (2 of 8
# heads), sliding-window local attention — the serving-lean variant
# (4x smaller KV cache, O(window) attention); tensor-parallel rules
# stay applicable (query/out/mlp shapes unchanged).
_add(
    _CONFIGS["transformer_lm"].replace(
        name="transformer_lm_modern",
        model_kwargs={
            **_CONFIGS["transformer_lm"].model_kwargs,
            "pos_encoding": "rope",
            "num_kv_heads": 2,
            "attn_window": 256,
        },
    )
)

# OLMoE-1B-7B (Muennighoff et al. 2024, arXiv:2409.02060; config.json of
# allenai/OLMoE-1B-7B-0125-Instruct): RMSNorm 1e-5, RMSNorm on the query
# and key projections, RoPE, no bias anywhere, 64 gated experts of width
# 1024 in every layer, softmax then top-8 without a capacity, router
# z-loss 0.001 beside the 0.01 load-balancing loss, no dropout.  Every
# width is the published one; the depth here is the published 16, which
# no single chip holds in training (benchmark/configs/olmoe.json runs 1).
_add(
    _CONFIGS["transformer_lm"].replace(
        name="olmoe",
        model_kwargs={
            "vocab_size": 50304,
            "num_layers": 16,
            "num_heads": 16,
            "d_model": 2048,
            "d_ff": 1024,
            "max_len": 4096,
            "dropout_rate": 0.0,
            "pos_encoding": "rope",
            "rope_theta": 10000.0,
            "norm": "rmsnorm",
            "norm_eps": 1e-5,
            "use_bias": False,
            "qk_norm": True,
            "num_experts": 64,
            "moe_router": "topk",
            "moe_top_k": 8,
            "moe_layers": "all",
            "moe_z_loss_weight": 0.001,
        },
        global_batch_size=8,
        num_steps=4096,
        vocab_size=50304,
    )
)

# Kimi-Linear-48B-A3B (Kimi Linear technical report, Moonshot AI 2025,
# arXiv:2510.26692; config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct):
# 27 pre-norm RMSNorm (1e-5) layers without a position encoding, three
# KDA mixers (32 heads of 128, short convolution 4) to one MLA mixer
# (kv_lora_rank 512, 128 + 64 query/key and 128 value channels, no query
# compression, nothing rotated); layer 1 a dense gated feed-forward of
# 9216, the other 26 with 256 gated experts of 1024, sigmoid scores, top-8
# renormalised times 2.446, one shared expert; vocabulary 163840, untied,
# no bias.  The router's selection bias is a buffer outside the gradient,
# held at zero, and there is no auxiliary loss.  Adam at 3e-4 like the
# other language models, behind a linear warm-up of 2,000 steps: at the
# full rate from the first step this router, which nothing balances, sends
# every token to the same eight experts within twenty steps (PERF.md, PR
# 30).  Every size is the published one; no single chip holds this in training
# (benchmark/configs/kimi_linear.json runs five layers, 8 of the experts
# and an eighth of the vocabulary: one chip's share of 32 x 8).
_KIMI_FULL_ATTENTION = (4, 8, 12, 16, 20, 24, 27)  # 1-based, config.json
_add(
    _CONFIGS["transformer_lm"].replace(
        name="kimi_linear",
        model_kwargs={
            "vocab_size": 163840,
            "num_layers": 27,
            "num_heads": 32,
            "d_model": 2304,
            "d_ff": 1024,
            "dense_d_ff": 9216,
            "max_len": 8192,
            "dropout_rate": 0.0,
            "pos_encoding": "none",
            "norm": "rmsnorm",
            "norm_eps": 1e-5,
            "use_bias": False,
            "mlp": "gated_silu",
            "layer_mixers": tuple(
                "mla" if i in _KIMI_FULL_ATTENTION else "kda"
                for i in range(1, 28)
            ),
            "kda_num_heads": 32,
            "kda_head_dim": 128,
            "kda_conv_size": 4,
            "mla_kv_lora_rank": 512,
            "mla_nope_dim": 128,
            "mla_rope_dim": 64,
            "mla_v_dim": 128,
            "num_experts": 256,
            "moe_router": "topk",
            "moe_top_k": 8,
            "moe_layers": "all",
            "moe_first_dense": 1,
            "moe_scoring": "sigmoid",
            "moe_renormalize": True,
            "moe_routed_scale": 2.446,
            "moe_shared_experts": 1,
            "moe_aux_loss_weight": 0.0,
            "remat": True,
        },
        global_batch_size=2,
        num_steps=8192,
        vocab_size=163840,
        optimizer=dataclasses.replace(
            _CONFIGS["transformer_lm"].optimizer, warmup_steps=2000
        ),
    )
)


# Olmo-Hybrid-7B (config.json of allenai/Olmo-Hybrid-7B, 2026-03): 32
# layers of width 3840, three gated delta-rule linear attentions (Yang,
# Kautz, Hatamizadeh 2024, arXiv:2412.06464: 30 heads of 96 key and 192
# value channels, short convolution 4, one decay a head and step, b in
# (0, 2): linear_allow_neg_eigval) to one full attention (30 heads of
# 128, rotary), a gated SiLU feed-forward of 11008, RMSNorm 1e-6, no bias,
# vocabulary 100352, untied.  What config.json does not say is the OLMo
# family's (OLMo 2, arXiv:2501.00656, kept by OLMo 3): each norm on its
# sub-layer's output inside the residual branch, RMSNorm over the whole
# query and key projections, RoPE (theta 500000) in the full-attention
# layers; the delta-rule layers take no positions
# (benchmark/configs/olmo_hybrid.json lists every such choice under
# ``assumed``).  Adam 3e-4 with clip 1.0 behind the 2,000-step warm-up of
# the other large language models, per-half recomputation, sequences of
# 8,192.  Every size is the published one; no single chip holds this in
# training (benchmark/configs/olmo_hybrid.json runs layers 1-4, 15 of each
# layer's 30 heads and an eighth of the vocabulary: one chip's share of 2
# x 8).
_add(
    _CONFIGS["transformer_lm"].replace(
        name="olmo_hybrid",
        model_kwargs={
            "vocab_size": 100352,
            "num_layers": 32,
            "num_heads": 30,
            "head_dim": 128,
            "d_model": 3840,
            "d_ff": 11008,
            "max_len": 65536,
            "dropout_rate": 0.0,
            "pos_encoding": "rope",
            "rope_theta": 500000.0,
            "norm": "rmsnorm",
            "norm_eps": 1e-6,
            "norm_placement": "post",
            "use_bias": False,
            "qk_norm": True,
            "mlp": "gated_silu",
            "layer_mixers": ("gdn", "gdn", "gdn", "attention") * 8,
            "gdn_num_heads": 30,
            "gdn_key_dim": 96,
            "gdn_value_dim": 192,
            "gdn_conv_size": 4,
            "remat": True,
        },
        global_batch_size=1,
        num_steps=8192,
        vocab_size=100352,
        optimizer=dataclasses.replace(
            _CONFIGS["transformer_lm"].optimizer, warmup_steps=2000
        ),
    )
)


# granite-4.0-h-micro (config.json of ibm-granite/granite-4.0-h-micro,
# 2025-10, model_type granitemoehybrid): 40 pre-norm RMSNorm (1e-5) layers
# of width 2048 without any position encoding; 36 Mamba-2 state-space
# layers (Dao and Gu 2024, arXiv:2405.21060: d_inner 4096 = 64 heads of
# 64 over a state of 128, one group, so one B and one C for all the
# heads; one causal depth-wise convolution of 4 taps with a bias over x,
# B and C) and, as layers 6, 16, 26 and 36, full attention over 32 query
# and 8 key/value heads of 64; no experts (num_local_experts 0): every
# layer's feed-forward is the gated SiLU one of 8192; Granite's four
# scalars (the embedding times 12, each sub-layer's output times 0.22
# before it joins the residual, attention scores times 1/64, logits over
# 8); vocabulary 100352, the head tied to the embedding, no bias.  What
# config.json does not say is listed under ``assumed`` in
# benchmark/configs/granite_h_micro.json.  Adam 3e-4 with clip 1.0 behind
# the 2,000-step warm-up of the other large language models, per-half
# recomputation, sequences of 8,192.  Every size is the published one; no
# single chip holds the 3.19 B parameters in training
# (benchmark/configs/granite_h_micro.json runs layers 1-10, one whole
# period, and an eighth of the vocabulary: the first of four pipeline
# stages, the vocabulary shared eight ways).
_GRANITE_H_ATTENTION = (6, 16, 26, 36)  # 1-based, config.json layer_types
_add(
    _CONFIGS["transformer_lm"].replace(
        name="granite_h_micro",
        model_kwargs={
            "vocab_size": 100352,
            "num_layers": 40,
            "num_heads": 32,
            "num_kv_heads": 8,
            "d_model": 2048,
            "d_ff": 8192,
            "max_len": 131072,
            "dropout_rate": 0.0,
            "pos_encoding": "none",
            "norm": "rmsnorm",
            "norm_eps": 1e-5,
            "use_bias": False,
            "mlp": "gated_silu",
            "layer_mixers": tuple(
                "attention" if i in _GRANITE_H_ATTENTION else "ssm"
                for i in range(1, 41)
            ),
            "ssm_num_heads": 64,
            "ssm_head_dim": 64,
            "ssm_state_dim": 128,
            "ssm_conv_size": 4,
            "embedding_multiplier": 12.0,
            "residual_multiplier": 0.22,
            "attention_multiplier": 0.015625,
            "logits_scaling": 8.0,
            "tie_embeddings": True,
            "remat": True,
        },
        global_batch_size=1,
        num_steps=8192,
        vocab_size=100352,
        optimizer=dataclasses.replace(
            _CONFIGS["transformer_lm"].optimizer, warmup_steps=2000
        ),
    )
)


# NVIDIA-Nemotron-3-Nano-30B-A3B (config.json of
# nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, 2025-12, model_type
# nemotron_h; the block is Nemotron-H's, NVIDIA 2025, arXiv:2504.03624):
# 52 pre-norm RMSNorm (1e-5) layers of width 2688 without any position
# encoding, **each one sub-layer alone** by hybrid_override_pattern: 23
# Mamba-2 mixers (M: d_inner 4096 = 64 heads of 64 over a state of 128,
# B and C one vector for each of eight groups of eight heads, the gated
# norm over each group's 512 channels; expand 2 would give 5376 and is
# used by no layer), 23 expert feed-forwards (E: 128 experts of 1856,
# two matrices around a squared ReLU without a gate; sigmoid scores, the
# top 6 renormalised times 2.5, n_group 1 so no group limit; one shared
# expert of 3712) and 6 attentions (*: 32 query heads of 128 over 2
# key/value heads, 4096 channels into a width of 2688); vocabulary
# 131072, untied, no bias.  The router's selection bias is a buffer
# outside the gradient, held at zero, and there is no auxiliary loss.
# Adam 3e-4 with clip 1.0 behind the 2,000-step warm-up of the other large
# language models (this router too is balanced by nothing: PERF.md, PR
# 30), per-half recomputation (a layer is one half), sequences of 8,192.
# Every size is the published one; no single chip holds one expert layer
# in training (benchmark/configs/nemotron3_nano.json runs layers 1-9, 8 of
# the experts and an eighth of the vocabulary: one chip's share of 16).
_NEMOTRON_LAYER = {"M": "ssm_only", "E": "ffn_only", "*": "attention_only"}
NEMOTRON3_NANO_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"
_add(
    _CONFIGS["transformer_lm"].replace(
        name="nemotron3_nano",
        model_kwargs={
            "vocab_size": 131072,
            "num_layers": 52,
            "num_heads": 32,
            "num_kv_heads": 2,
            "head_dim": 128,
            "d_model": 2688,
            "d_ff": 1856,
            "max_len": 262144,
            "dropout_rate": 0.0,
            "pos_encoding": "none",
            "norm": "rmsnorm",
            "norm_eps": 1e-5,
            "use_bias": False,
            "mlp": "relu2",
            "layer_mixers": tuple(
                _NEMOTRON_LAYER[kind] for kind in NEMOTRON3_NANO_PATTERN
            ),
            "ssm_num_heads": 64,
            "ssm_head_dim": 64,
            "ssm_state_dim": 128,
            "ssm_num_groups": 8,
            "ssm_conv_size": 4,
            "num_experts": 128,
            "moe_router": "topk",
            "moe_top_k": 6,
            "moe_layers": "all",
            "moe_scoring": "sigmoid",
            "moe_renormalize": True,
            "moe_routed_scale": 2.5,
            "moe_shared_experts": 1,
            "moe_shared_d_ff": 3712,
            "moe_expert": "relu2",
            "moe_aux_loss_weight": 0.0,
            "remat": True,
        },
        global_batch_size=1,
        num_steps=8192,
        vocab_size=131072,
        optimizer=dataclasses.replace(
            _CONFIGS["transformer_lm"].optimizer, warmup_steps=2000
        ),
    )
)


# Phi-4-mini-flash-reasoning (config.json of
# microsoft/Phi-4-mini-flash-reasoning, 2025-07, model_type phi4flash; the
# design is SambaY: Ren et al. 2025, arXiv:2507.06607, a Samba
# self-decoder, arXiv:2406.07522, under a cross-decoder): 32 pre-norm
# LayerNorm (1e-5) layers of width 2560 without any position encoding,
# each a mixer and a gated SiLU feed-forward of 10240 without bias;
# mb_per_layer 2 puts a Mamba-1 mixer (Gu and Dao 2023, arXiv:2312.00752:
# d_inner 5120, state 16, convolution 4, dt_rank 160, a decay for every
# channel and state) in the even layers up to 16 and differential
# attention (Ye et al. 2024, arXiv:2410.05258: 40 query and 20 key/value
# heads of 64 in pairs, biases on the projections) in the odd ones, under
# a window of 512 up to layer 15 and full in layer 17; from layer 18 on
# the even layers are gated memory units over layer 16's scan output and
# the odd ones cross-attentions (a query and an output projection) to
# layer 17's keys and values; vocabulary 200064, the head tied to the
# embedding.  What config.json does not say is listed under ``assumed`` in
# benchmark/configs/phi4_mini_flash.json.  Adam 3e-4 with clip 1.0 behind
# the 2,000-step warm-up of the other large language models, per-half
# recomputation, sequences of 8,192.  Every size is the published one; no
# single chip holds the 3.85 B parameters in training
# (benchmark/configs/phi4_mini_flash.json runs published layers 0, 1, 16,
# 17, 18, 19, every kind of layer once, and an eighth of the vocabulary).
PHI4_FLASH_LAYERS = tuple(
    ("gmu" if i % 2 == 0 else "cross") if i > 17
    else "mamba1" if i % 2 == 0
    else "attention" if i < 17 else "attention_full"
    for i in range(32)
)
_add(
    _CONFIGS["transformer_lm"].replace(
        name="phi4_mini_flash",
        model_kwargs={
            "vocab_size": 200064,
            "num_layers": 32,
            "num_heads": 40,
            "num_kv_heads": 20,
            "d_model": 2560,
            "d_ff": 10240,
            "max_len": 262144,
            "dropout_rate": 0.0,
            "pos_encoding": "none",
            "norm": "layernorm",
            "norm_eps": 1e-5,
            "use_bias": False,
            "attn_bias": True,
            "mlp": "gated_silu",
            "layer_mixers": PHI4_FLASH_LAYERS,
            "attn_window": 512,
            "attn_differential": True,
            "mamba1_inner": 5120,
            "mamba1_state_dim": 16,
            "mamba1_conv_size": 4,
            "mamba1_dt_rank": 160,
            "tie_embeddings": True,
            "remat": True,
        },
        global_batch_size=1,
        num_steps=8192,
        vocab_size=200064,
        optimizer=dataclasses.replace(
            _CONFIGS["transformer_lm"].optimizer, warmup_steps=2000
        ),
    )
)


def get_config(name: str, **overrides) -> ExperimentConfig:
    if name not in _CONFIGS:
        raise KeyError(f"unknown config {name!r}; have {sorted(_CONFIGS)}")
    cfg = _CONFIGS[name]
    return cfg.replace(**overrides) if overrides else cfg


def list_configs() -> list[str]:
    return sorted(_CONFIGS)
