"""Elementwise-adjacent building blocks, deliberately written as plain jnp.

XLA fuses these into the surrounding matmuls (HBM-bandwidth win comes from
fusion, not hand kernels — pallas here would *block* fusion). fp32 internal
accumulation for norms regardless of the bf16 activations around them.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def rms_norm(x: jax.Array, weight: jax.Array, eps: float = 1e-6,
             unit_offset: bool = False) -> jax.Array:
    """RMSNorm with fp32 internal math, output in x.dtype. ``unit_offset``:
    the scale is ``1 + weight`` (weights stored around 0)."""
    dtype = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    scale = weight.astype(jnp.float32)
    if unit_offset:
        scale = 1.0 + scale
    return (y * scale).astype(dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    """SwiGLU activation: silu(gate) * up."""
    return jax.nn.silu(gate) * up


def rotary_embedding_tables(
    positions: jax.Array,
    head_dim: int,
    *,
    theta: float = 10000.0,
    dtype=jnp.float32,
) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) tables for RoPE; positions [..., S] -> [..., S, head_dim/2]."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.sin(angles).astype(dtype), jnp.cos(angles).astype(dtype)


def apply_rotary_embedding(
    x: jax.Array, sin: jax.Array, cos: jax.Array
) -> jax.Array:
    """Rotate pairs (split-half convention). x: [B, H, S, D]; sin/cos
    [S, R/2] or [B, S, R/2] (broadcast over heads). Tables narrower than
    the head (R < D) rotate its first R dimensions, ``rotate_half`` over
    those, and pass the rest through."""
    rot = 2 * sin.shape[-1]
    if rot < x.shape[-1]:
        return jnp.concatenate(
            [apply_rotary_embedding(x[..., :rot], sin, cos), x[..., rot:]], axis=-1)
    dtype = x.dtype
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    if sin.ndim == 2:  # [S, half] -> broadcast over batch+heads
        sin_b = sin[None, None, :, :].astype(jnp.float32)
        cos_b = cos[None, None, :, :].astype(jnp.float32)
    else:  # [B, S, half] -> broadcast over heads
        sin_b = sin[:, None, :, :].astype(jnp.float32)
        cos_b = cos[:, None, :, :].astype(jnp.float32)
    r1 = x1 * cos_b - x2 * sin_b
    r2 = x2 * cos_b + x1 * sin_b
    return jnp.concatenate([r1, r2], axis=-1).astype(dtype)
