"""Loss functions matching the reference's training objectives.

Cross entropy with optional label smoothing reproduces the slim
Inception-v3 objective (SURVEY.md §2.1 R5: "aux logits head; label
smoothing"); L2 weight decay reproduces the slim ``weight_decay``
regularizer added to every conv/fc kernel.
"""

from __future__ import annotations

import os
import sys
from typing import Any, Callable, Union

import jax
import jax.numpy as jnp

PyTree = Any

# ``jax.named_scope`` of the LM head's projection + cross entropy (fused:
# :func:`chunked_unembed_xent`; plain: :func:`token_xent` over the model's
# own logits): a path element of every instruction's ``op_name`` in the
# compiled step, which ``step_scopes_p<i>.json`` carries to the device
# trace (PERF.md section 3).
UNEMBED_LOSS_SCOPE = "unembed_loss"


def resolve_unembed_chunk(default: int = 2048) -> int:
    """Trace-time DTM_UNEMBED_CHUNK resolution (the DTM_CONV_IMPL
    contract: invalid values fail loudly naming the knob).  The knob
    exists for the r3 TPU surprise — the two-stage head beat the fused
    path ~3% at b16, and one hypothesis is per-chunk checkpoint
    boundaries (4 segments at the 2048 default); chunk_rows >= B*T
    collapses the fused head to a single remat'd segment, isolating
    chunking cost from fusion benefit."""
    env = os.environ.get("DTM_UNEMBED_CHUNK")
    if not env:
        return default
    try:
        v = int(env)
    except ValueError:
        raise ValueError(
            f"DTM_UNEMBED_CHUNK must be an integer, got {env!r}"
        ) from None
    if v < 1:
        raise ValueError(f"DTM_UNEMBED_CHUNK must be >= 1, got {env!r}")
    return v


def softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Per-example softmax cross entropy from integer labels.

    With ``label_smoothing`` = eps, targets become
    ``onehot * (1 - eps) + eps / num_classes`` — the slim
    ``losses.softmax_cross_entropy(label_smoothing=...)`` convention used by
    the reference's Inception-v3 training (SURVEY.md §2.1 R5).
    """
    num_classes = logits.shape[-1]
    onehot = jax.nn.one_hot(labels, num_classes, dtype=logits.dtype)
    if label_smoothing:
        onehot = (
            onehot * (1.0 - label_smoothing) + label_smoothing / num_classes
        )
    log_probs = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.sum(onehot * log_probs, axis=-1)


@jax.named_scope(UNEMBED_LOSS_SCOPE)
def token_xent(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Per-token NLL of an LM's logits: :func:`softmax_cross_entropy`
    under the ``unembed_loss`` scope, so the plain (two-stage) head's
    loss is named in the compiled step like the fused one's."""
    return softmax_cross_entropy(logits, targets)


def mean_softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
) -> jax.Array:
    """Batch-mean cross entropy.

    Inside a jitted step whose batch is sharded over the ``data`` mesh axis,
    this mean is a *global* mean: XLA lowers it to a partial sum plus an
    all-reduce over ICI, which is the entire TPU-native replacement for the
    reference's ConditionalAccumulator / take_grad(N) averaging protocol
    (TF sync_replicas_optimizer.py:275-293 — SURVEY.md §3.2).
    """
    return jnp.mean(softmax_cross_entropy(logits, labels, label_smoothing))


def l2_weight_decay(
    params: PyTree,
    scale: float,
    predicate: Callable[[str], bool] | None = None,
) -> jax.Array:
    """``scale * sum(0.5 * ||w||^2)`` over kernel parameters.

    ``predicate`` receives the '/'-joined parameter path; the default decays
    only arrays whose path ends in ``kernel`` (slim decays conv/fc weights
    but not biases or BN scales).
    """
    if predicate is None:
        predicate = lambda name: name.endswith("kernel")

    def path_str(path) -> str:
        return "/".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )

    leaves = jax.tree_util.tree_leaves_with_path(params)
    total = 0.0
    for path, leaf in leaves:
        if predicate(path_str(path)):
            total = total + 0.5 * jnp.sum(jnp.square(leaf))
    return scale * total


@jax.named_scope(UNEMBED_LOSS_SCOPE)
def chunked_unembed_xent(
    hidden: jax.Array,
    kernel: jax.Array,
    bias: jax.Array | None,
    targets: jax.Array,
    *,
    chunk_rows: Union[int, str] = "auto",
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Per-token NLL of ``Dense(hidden) -> softmax xent`` WITHOUT ever
    materializing the full ``[B*T, V]`` float32 logits tensor.

    The LM head is the single largest tensor in a small-vocab-model train
    step (d512/V10k at B16/T512: 328 MB of f32 logits forward plus the
    same again for the cotangent — more HBM traffic than all transformer
    blocks combined) and the reference-style two-stage
    ``logits = head(x); xent(logits)`` forces XLA to spill it.  This op
    scans over row chunks: each chunk's ``[chunk, V]`` logits live only
    inside one fused (projection -> logsumexp -> pick) body, the MXU
    matmul runs in ``compute_dtype`` (bfloat16 — twice the f32 MXU issue
    rate) with float32 accumulation, and ``jax.checkpoint`` makes the
    backward recompute chunk logits instead of storing them — peak memory
    drops from O(B*T*V) to O(chunk_rows*V) in both passes.  The kernel
    cotangent accumulates across scan iterations automatically.

    Equivalent math to ``softmax_cross_entropy(hidden @ kernel + bias,
    targets)`` (no label smoothing — LM targets are hard); with
    ``compute_dtype=float32`` the results agree to float round-off
    (pinned in tests/test_lm_train.py).

    Args:
      hidden: ``[B, T, d]`` final hidden states (post-ln_f).
      kernel: ``[d, V]`` unembedding matrix (the head Dense kernel).
      bias: ``[V]`` or None.
      targets: ``[B, T]`` int labels.
    Returns:
      ``[B, T]`` per-token negative log likelihood, float32.
    """
    B, T, d = hidden.shape
    n = B * T
    x = hidden.reshape(n, d)
    t = targets.reshape(n)
    if chunk_rows == "auto":
        # Resolved AT THE OP so every caller honors DTM_UNEMBED_CHUNK
        # through one validation path (same placement as DTM_CONV_IMPL
        # in ops/conv.py).
        chunk_rows = resolve_unembed_chunk()
    c = min(chunk_rows, n)
    if c != chunk_rows and os.environ.get("DTM_UNEMBED_CHUNK"):
        # The knob asked for a bigger chunk than this shape has rows:
        # clamping is correct math but would silently mislabel an A/B
        # artifact, so say what was actually measured (trace-time).
        print(
            f"[losses] DTM_UNEMBED_CHUNK={chunk_rows} clamped to {c} "
            f"(B*T={n})",
            file=sys.stderr,
        )
    pad = (-n) % c
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
    xc = x.reshape(-1, c, d).astype(compute_dtype)
    tc = t.reshape(-1, c)
    kmat = kernel.astype(compute_dtype)
    b32 = None if bias is None else bias.astype(jnp.float32)

    @jax.checkpoint
    def one_chunk(xi, ti):
        logits = jax.lax.dot_general(
            xi, kmat, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if b32 is not None:
            logits = logits + b32
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
        return lse - picked

    # Static Python unroll, NOT lax.scan: XLA's cost analysis visits a
    # scan body once regardless of trip count (core/train_loop.py,
    # InstrumentedMultiStep), so a scanned head would silently vanish from
    # FLOPs/MFU accounting.  The chunk count is small and static
    # (B*T/chunk_rows); each body stays checkpointed, so backward
    # recomputes chunk logits either way.
    nll = jnp.concatenate(
        [one_chunk(xc[i], tc[i]) for i in range(xc.shape[0])]
    )
    return nll[:n].reshape(B, T)
