"""Elementwise-adjacent building blocks, deliberately written as plain jnp.

XLA fuses these into the surrounding matmuls (HBM-bandwidth win comes from
fusion, not hand kernels — pallas here would *block* fusion). fp32 internal
accumulation for norms regardless of the bf16 activations around them.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


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


def two_terms(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Float32 ``x`` as ``hi + lo``, both float32: hi is x with its low 16 bits
    cleared, which bfloat16 holds exactly, lo the rest, which bfloat16 holds
    to 8 bits more. (Not x rounded to bfloat16 and back: the chip's compiler
    may drop such a pair of conversions as excess precision, and lo with it:
    my chip run, PR 44.) Plain ``lax``, so a pallas kernel may call it."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32) & jnp.uint32(0xFFFF0000)
    hi = jax.lax.bitcast_convert_type(bits, jnp.float32)
    return hi, x - hi


def two_term_matmul(x: jax.Array, w: jax.Array) -> jax.Array:
    """``x @ w`` in float32 with ``x`` taken as two terms of ``w``'s dtype, ``x
    = hi + lo``: both terms go through one product, stacked, so that the
    weights are read once (a bandwidth-bound step pays no byte for it, its
    matrix unit twice the rows) and the result carries ``x`` to 2^-16 where
    one bfloat16 term carries it to 2^-8. For the layers whose rounding a
    discrete choice downstream amplifies (the mixer with heads and the dense
    parts of a LatentMoE layer, whose router picks 22 of 512 by score: PERF.md
    section 6, PR 44). Weights of another dtype than bfloat16 take ``x`` in
    theirs, in one term."""
    f32 = jnp.float32
    if w.dtype != jnp.bfloat16:
        return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=f32)
    out = jnp.matmul(jnp.stack(two_terms(x.astype(f32))).astype(w.dtype), w, preferred_element_type=f32)
    return out[0] + out[1]


def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention scale for a context stretched ``factor`` times:
    ``0.1 x mscale x ln(factor) + 1`` (1 where nothing is stretched)."""
    return 1.0 if factor <= 1.0 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(head_dim: int, theta: float, factor: float, original_max_position: int,
                  beta_fast: float = 32.0, beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's frequencies for ``head_dim`` rotated dimensions (float32
    [head_dim / 2]). ``f_j = theta^(-2j / head_dim)``; the dimension that
    turns ``b`` times over the original context is ``cd(b) = head_dim x
    ln(original / (2 pi b)) / (2 ln theta)``; below ``low = floor(cd(beta_fast))``
    a frequency stays as it is (it turns often enough to extrapolate), above
    ``high = ceil(cd(beta_slow))`` it is divided by ``factor`` (interpolated),
    and between them the two are blended linearly."""
    half = head_dim // 2
    f = theta ** (-np.arange(half, dtype=np.float64) * 2.0 / head_dim)
    cd = lambda b: head_dim * math.log(original_max_position / (2 * math.pi * b)) / (2 * math.log(theta))
    low = max(math.floor(cd(beta_fast)), 0)
    high = min(math.ceil(cd(beta_slow)), head_dim - 1)
    if low == high:
        high += 0.001  # no division by zero where the two bounds meet
    ramp = np.clip((np.arange(half, dtype=np.float64) - low) / (high - low), 0.0, 1.0)
    return ((f / factor) * ramp + f * (1.0 - ramp)).astype(np.float32)


def rotary_embedding_tables(
    positions: jax.Array,
    head_dim: int,
    *,
    theta: float = 10000.0,
    dtype=jnp.float32,
    inv_freq=None,
    table_scale: float = 1.0,
) -> Tuple[jax.Array, jax.Array]:
    """(sin, cos) tables for RoPE; positions [..., S] -> [..., S, head_dim/2].
    ``inv_freq`` [head_dim / 2]: the frequencies where they are not
    ``theta^(-2j / head_dim)`` (:func:`yarn_inv_freq`); ``table_scale``
    multiplies both tables (YaRN's ``mscale / mscale_all_dim``)."""
    half = head_dim // 2
    if inv_freq is None:
        freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    else:
        freqs = jnp.asarray(inv_freq, jnp.float32)
    angles = positions.astype(jnp.float32)[..., None] * freqs
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    if table_scale != 1.0:
        sin, cos = sin * table_scale, cos * table_scale
    return sin.astype(dtype), cos.astype(dtype)


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
