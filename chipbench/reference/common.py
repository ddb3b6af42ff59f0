"""Pieces every reference shares: the seed's key, RMSNorm, fp8 rounding."""
from __future__ import annotations

import jax
import jax.numpy as jnp

#: largest finite float8 e4m3 value
FP8_MAX = 448.0


def key_from_seed(seed: int):
    """A PRNG key that depends on all of ``seed`` (up to 64 bits): a key
    of one 32-bit word would fold seeds that differ above bit 32."""
    if not 0 <= seed < 1 << 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def rms_norm(x, g, eps):
    """RMSNorm in float32 with weight ``1 + g`` (the offset storage)."""
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + g.astype(jnp.float32))


def fp8_round(x, axes):
    """``x`` rounded to float8 e4m3 with one scale per slice over ``axes``
    (the amax of the slice maps to the largest finite value), back in
    float32."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
