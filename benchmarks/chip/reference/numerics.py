"""Matrix products of the references: float32 at full precision, or, for
the control, both operands rounded to float8 (e4m3, one scale per tensor)
and multiplied in float32."""
from __future__ import annotations

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def to_fp8(x: jax.Array) -> jax.Array:
    """Per-tensor scaled round trip through float8 e4m3."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def dot(eq: str, a: jax.Array, b: jax.Array, fp8: bool) -> jax.Array:
    if fp8:
        a, b = to_fp8(a), to_fp8(b)
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) \
        * scale


def layer_norm(x, scale, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias
