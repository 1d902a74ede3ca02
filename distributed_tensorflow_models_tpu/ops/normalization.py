"""TPU-tuned batch normalization.

Drop-in replacement for ``flax.linen.BatchNorm`` used by every conv model in
the zoo (reference semantics: slim's conv+BN arg_scope and the CIFAR ResNet
tutorial BN — SURVEY.md §2.1 R4-R7).  Differences from the flax module are
purely about dtype discipline on TPU:

- The elementwise normalize/scale/shift path runs in the *input* dtype
  (bfloat16 in the zoo's training configs).  flax's ``BatchNorm`` with
  ``dtype=float32`` promotes the activation tensor to float32, which doubles
  HBM read+write traffic on what is a bandwidth-bound op; an early-round
  ResNet-50 reading put that at ~24% of end-to-end training throughput
  (old access layer, not re-measured).
- Statistics are always *accumulated* in float32 regardless of input dtype
  (a bfloat16 ``E[x^2] - E[x]^2`` would be numerically catastrophic), and the
  per-channel affine constants are folded in float32 down to one fused
  multiply-add in the activation dtype:  ``y = x * a + b`` with
  ``a = scale / sqrt(var + eps)`` and ``b = bias - mean * a``.

Parameter/collection layout is identical to ``flax.linen.BatchNorm``
(params ``scale``/``bias``; batch_stats ``mean``/``var``, biased variance),
so checkpoints and model code are interchangeable between the two.

Under ``jit`` with a batch-sharded input the statistics reductions are
*global* across the mesh automatically (XLA inserts the cross-chip psum) —
sync BN, the documented divergence from the reference's per-replica BN
(SURVEY.md §7.4.2).  Under ``shard_map``/``pmap``, where reductions are
per-shard, pass ``axis_name`` to restore the same global semantics.
"""

from __future__ import annotations

from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class BatchNorm(nn.Module):
    """Batch normalization with bf16-friendly I/O and float32 statistics.

    Attributes:
      use_running_average: eval mode — normalize with the stored running
        statistics instead of batch statistics.  As in flax, it may be left
        ``None`` at construction and supplied at call time; leaving it
        unspecified in both places is an error.
      momentum: running-statistics decay (slim inception uses 0.9997, the
        CIFAR/ResNet tutorials 0.9 — SURVEY.md §2.1 R4/R5).
      epsilon: numerical floor inside the rsqrt.
      axis_name: optional mapped axis to ``pmean`` statistics over (only
        needed under shard_map/pmap; under jit global-batch semantics are
        automatic).
      scale_init/bias_init: parameter initializers (zero ``scale_init`` is
        the ResNet last-BN identity-start trick).

    Unlike ``flax.linen.BatchNorm`` there is no ``dtype`` attribute: the
    elementwise path always runs in the *input* dtype and statistics always
    accumulate in float32, so a dtype knob would either lie or reintroduce
    the f32 activation round-trip this module exists to remove.  Passing
    ``dtype=`` raises a ``TypeError`` at construction — loud, not silent.
    """

    use_running_average: Optional[bool] = None
    momentum: float = 0.9
    epsilon: float = 1e-5
    axis_name: Optional[str] = None
    scale_init: nn.initializers.Initializer = nn.initializers.ones
    bias_init: nn.initializers.Initializer = nn.initializers.zeros

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        use_running_average: Optional[bool] = None,
    ) -> jax.Array:
        use_running_average = nn.merge_param(
            "use_running_average",
            self.use_running_average,
            use_running_average,
        )
        features = x.shape[-1]
        reduce_axes = tuple(range(x.ndim - 1))

        scale = self.param(
            "scale", self.scale_init, (features,), jnp.float32
        )
        bias = self.param(
            "bias", self.bias_init, (features,), jnp.float32
        )
        ra_mean = self.variable(
            "batch_stats",
            "mean",
            lambda *a: jnp.zeros(*a, jnp.float32),
            (features,),
        )
        ra_var = self.variable(
            "batch_stats",
            "var",
            lambda *a: jnp.ones(*a, jnp.float32),
            (features,),
        )

        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(jnp.float32)
            # Two sibling reductions over the same operand — XLA multi-output
            # fusion reads x once (bf16) and accumulates both in f32.
            mean = jnp.mean(xf, reduce_axes)
            mean_sq = jnp.mean(jnp.square(xf), reduce_axes)
            if self.axis_name is not None:
                mean, mean_sq = lax.pmean((mean, mean_sq), self.axis_name)
            var = jnp.maximum(mean_sq - jnp.square(mean), 0.0)
            if not self.is_initializing():
                m = self.momentum
                ra_mean.value = m * ra_mean.value + (1.0 - m) * mean
                ra_var.value = m * ra_var.value + (1.0 - m) * var

        inv = lax.rsqrt(var + self.epsilon) * scale
        shift = bias - mean * inv
        # One fused multiply-add in the activation dtype.
        return x * inv.astype(x.dtype) + shift.astype(x.dtype)
