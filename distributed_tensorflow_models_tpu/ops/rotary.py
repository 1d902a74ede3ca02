"""Rotary position embeddings (RoPE, Su et al.) — the relative-position
encoding used by modern decoder LMs in place of learned absolute tables.

Position enters attention by rotating each (even, odd) feature pair of q
and k by an angle proportional to the token's GLOBAL position, so the
q·k dot product depends only on relative distance.  Properties this
module's consumers rely on:

- Decode: keys are cached post-rotation, so a cached key never needs
  re-rotating as the query advances (the standard KV-cache convention);
  queries rotate by their own absolute position (the cache index).
- Sequence parallelism: rotation is position-elementwise, so each ring
  device rotates its local chunk by its global positions before the KV
  chunks start traveling — no cross-device coordination.
- Kernels: rotation happens before the attention call; the kernels and
  blockwise see ordinary q/k and need no RoPE awareness.

Half-split ("rotate_half", GPT-NeoX/Llama) convention: features [0, D/2)
pair with [D/2, D).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def rope_angles(positions: jax.Array, dim: int, theta: float = 10000.0):
    """Cos/sin tables for ``positions`` (any int shape) and head dim
    ``dim`` (must be even).  Returns f32 ``(..., dim/2)`` pairs."""
    if dim % 2:
        raise ValueError(f"RoPE head dim must be even, got {dim}")
    inv_freq = theta ** (
        -jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    )  # [dim/2]
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float = 10000.0,
) -> jax.Array:
    """Rotate ``x [B, T, H, D]`` by its tokens' global ``positions``
    (shape ``[T]`` or ``[B, T]``).  Rotation in f32, result cast back to
    the input dtype (bf16 activations rotate without accumulating
    round-off into the angle math)."""
    B, T, H, D = x.shape
    cos, sin = rope_angles(positions, D, theta)  # [..., T, D/2]
    # Broadcast to [B, T, 1, D/2] over heads.
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    cos, sin = cos[:, :, None, :], sin[:, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : D // 2], x32[..., D // 2 :]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    return out.astype(x.dtype)
