"""Loss functions matching the reference's training objectives.

Cross entropy with optional label smoothing reproduces the slim
Inception-v3 objective (SURVEY.md §2.1 R5: "aux logits head; label
smoothing"); L2 weight decay reproduces the slim ``weight_decay``
regularizer added to every conv/fc kernel.
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from distributed_tensorflow_models_tpu.telemetry.registry import (
    UNEMBED_GRAD_IN_FORWARD,
    get_registry,
)

PyTree = Any

# ``jax.named_scope`` of the LM head's projection + cross entropy (fused:
# :func:`fused_unembed_mean_xent`; plain: :func:`token_xent` over the
# model's own logits): a path element of every instruction's ``op_name``
# in the compiled step, which ``step_scopes_p<i>.json`` carries to the
# device trace (PERF.md section 3).
UNEMBED_LOSS_SCOPE = "unembed_loss"

# Rows of one chunk of the fused head, clamped to the rows there are.
# Measured on a v5e at both token cells' head shapes (PERF.md PR 29):
# 1,024 rows cost 15%, 2,048 cost 0.7% and 3.6%, and 8,192 buy 1.0% and
# 3.4% for twice the ``[rows, V]`` f32 temporaries.
UNEMBED_CHUNK_ROWS = 4096


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


def _head_chunks(hidden, kernel, bias, targets, chunk_rows, compute_dtype):
    """The fused head's operands, cut into row chunks: ``(chunks, kmat,
    b32)`` with ``chunks`` a list of ``(x_i [c, d], t_i [c], real rows)``.
    The tail chunk is zero-padded to ``c`` rows; only its first ``real``
    rows count."""
    d = hidden.shape[-1]
    x = hidden.reshape(-1, d)
    t = targets.reshape(-1)
    n = x.shape[0]
    c = min(chunk_rows, n)
    pad = (-n) % c
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
        t = jnp.pad(t, (0, pad))
    xc = x.reshape(-1, c, d).astype(compute_dtype)
    tc = t.reshape(-1, c)
    # Static Python unroll, NOT lax.scan: XLA's cost analysis visits a
    # scan body once regardless of trip count (core/train_loop.py,
    # InstrumentedMultiStep), so a scanned head would silently vanish from
    # FLOPs/MFU accounting.  The chunk count is small and static
    # (B*T/chunk_rows).
    chunks = [
        (xc[i], tc[i], min(c, n - i * c)) for i in range(xc.shape[0])
    ]
    kmat = kernel.astype(compute_dtype)
    b32 = None if bias is None else bias.astype(jnp.float32)
    return chunks, kmat, b32


def _chunk_logits(xi, kmat, b32):
    """``[c, V]`` float32 logits of one chunk: the product in the
    operands' dtype with float32 accumulation, the bias in float32."""
    logits = jax.lax.dot_general(
        xi, kmat, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return logits if b32 is None else logits + b32


def _one_chunk_at_a_time(xi, carried):
    """Tie a chunk's rows to what the chunk before it finished, so that
    XLA cannot start (or merge) the chunks' independent logits products
    together and hold every ``[c, V]`` block at once."""
    return jax.lax.optimization_barrier((xi, carried))


def _chunk_nll(logits, ti):
    """``(nll [c], lse [c])`` of one chunk's logits, float32."""
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, ti[:, None], axis=-1)[:, 0]
    return lse - picked, lse


@jax.named_scope(UNEMBED_LOSS_SCOPE)
def chunked_unembed_xent(
    hidden: jax.Array,
    kernel: jax.Array,
    bias: jax.Array | None,
    targets: jax.Array,
    *,
    chunk_rows: int = UNEMBED_CHUNK_ROWS,
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Per-token NLL of ``Dense(hidden) -> softmax xent`` WITHOUT ever
    materializing the full ``[B*T, V]`` float32 logits tensor.

    The plain-autodiff statement of the fused head: what wants per-token
    values calls it, and :func:`fused_unembed_mean_xent` (the one ``fit``
    runs, which needs only the mean) is tested against it.  Each chunk's
    ``[chunk, V]`` logits live only inside one fused (projection ->
    logsumexp -> pick) body, the MXU matmul runs in ``compute_dtype``
    (bfloat16) with float32 accumulation, and ``jax.checkpoint`` makes the
    backward recompute chunk logits instead of storing them: peak memory
    O(chunk_rows*V) in both passes, at the price of a fourth
    vocabulary-sized product per chunk.

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
    chunks, kmat, b32 = _head_chunks(
        hidden, kernel, bias, targets, chunk_rows, compute_dtype
    )

    @jax.checkpoint
    def one_chunk(xi, ti):
        return _chunk_nll(_chunk_logits(xi, kmat, b32), ti)[0]

    nll = jnp.concatenate([one_chunk(xi, ti) for xi, ti, _ in chunks])
    return nll[: targets.size].reshape(targets.shape)


def fused_unembed_mean_xent(
    hidden: jax.Array,
    kernel: jax.Array,
    bias: jax.Array | None,
    targets: jax.Array,
    *,
    chunk_rows: int = UNEMBED_CHUNK_ROWS,
    compute_dtype: jnp.dtype = jnp.bfloat16,
) -> jax.Array:
    """Mean over tokens of :func:`chunked_unembed_xent`, with the gradient
    finished while each chunk's logits are live.

    A mean hands every token the same cotangent, so ``dlogits = (softmax
    - onehot) / n`` is known the moment a chunk's logits exist: under
    differentiation the forward pass makes both gradient products per
    chunk (``dlogits . W^T`` and ``x^T . dlogits``, the latter summed into
    ONE float32 ``[d, V]`` accumulator) and the backward pass only scales
    them by the loss's cotangent.  Three vocabulary-sized products a
    chunk where checkpointed autodiff runs four, nothing recomputed, no
    ``[chunk, V]`` array alive outside a chunk.  Undifferentiated
    (evaluation) it is one product a chunk.

    Same arithmetic as autodiff of the per-token op: products in
    ``compute_dtype`` with float32 accumulation (``dlogits`` stays float32
    beside its ``compute_dtype`` operand, as autodiff hands it over),
    softmax and log-sum-exp in float32; the weight gradient is summed
    over chunks in float32.  Each traced differentiated call counts once
    in ``unembed/grad_in_forward`` (the process-global registry).

    Args as :func:`chunked_unembed_xent`.  Returns the float32 scalar.
    """
    return _mean_xent(
        hidden, kernel, bias, targets, chunk_rows, jnp.dtype(compute_dtype)
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
@jax.named_scope(UNEMBED_LOSS_SCOPE)
def _mean_xent(hidden, kernel, bias, targets, chunk_rows, compute_dtype):
    chunks, kmat, b32 = _head_chunks(
        hidden, kernel, bias, targets, chunk_rows, compute_dtype
    )
    total = jnp.zeros((), jnp.float32)
    for xi, ti, real in chunks:
        xi, total = _one_chunk_at_a_time(xi, total)
        nll, _ = _chunk_nll(_chunk_logits(xi, kmat, b32), ti)
        total = total + jnp.sum(nll[:real])
    return total / targets.size


@jax.named_scope(UNEMBED_LOSS_SCOPE)
def _mean_xent_fwd(hidden, kernel, bias, targets, chunk_rows, compute_dtype):
    get_registry().counter(UNEMBED_GRAD_IN_FORWARD).inc()
    chunks, kmat, b32 = _head_chunks(
        hidden, kernel, bias, targets, chunk_rows, compute_dtype
    )
    n = targets.size
    d, V = kernel.shape
    total = jnp.zeros((), jnp.float32)
    dW = jnp.zeros((d, V), jnp.float32)
    db = None if bias is None else jnp.zeros((V,), jnp.float32)
    dxs = []
    for xi, ti, real in chunks:
        xi, dW = _one_chunk_at_a_time(xi, dW)
        logits = _chunk_logits(xi, kmat, b32)
        nll, lse = _chunk_nll(logits, ti)
        dlogits = (
            jnp.exp(logits - lse[:, None])
            - jax.nn.one_hot(ti, V, dtype=jnp.float32)
        ) / n
        if real < xi.shape[0]:  # the zero-padded tail adds nothing
            live = jnp.arange(xi.shape[0]) < real
            dlogits = jnp.where(live[:, None], dlogits, 0.0)
        total = total + jnp.sum(nll[:real])
        dxs.append(
            jax.lax.dot_general(
                dlogits, kmat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ).astype(hidden.dtype)
        )
        dW = dW + jax.lax.dot_general(
            xi, dlogits, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if db is not None:
            db = db + jnp.sum(dlogits, axis=0)
    dx = jnp.concatenate(dxs)[:n].reshape(hidden.shape)
    if db is not None:
        db = db.astype(bias.dtype)
    return total / n, (dx, dW.astype(kernel.dtype), db)


@jax.named_scope(UNEMBED_LOSS_SCOPE)
def _mean_xent_bwd(chunk_rows, compute_dtype, residuals, g):
    # The loss's cotangent scales what the forward pass finished; the
    # integer targets have none.
    scaled = [None if r is None else (g * r).astype(r.dtype) for r in residuals]
    return (*scaled, None)


_mean_xent.defvjp(_mean_xent_fwd, _mean_xent_bwd)
