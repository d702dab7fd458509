"""Shared model primitives: param specs, norms, RoPE, MLPs, embeddings.

Convention: every layer module exposes ``*_param_specs(cfg) -> dict`` mapping
param name to ``ParamSpec(shape, dims, init)``. ``dims`` are *logical* axis
names consumed by ``repro.distributed.sharding`` — a single source of truth
so init shapes and sharding rules can never diverge.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    dims: Tuple[Any, ...]           # logical dim names (None = replicated)
    init: str = "normal"            # normal | zeros | ones | small
    scale: float = 1.0

    def __post_init__(self):
        assert len(self.shape) == len(self.dims), (self.shape, self.dims)


ParamTree = Dict[str, Any]


def init_from_specs(rng: jax.Array, specs: Dict[str, Any], dtype=jnp.float32) -> ParamTree:
    """Initialize a (possibly nested) spec tree into concrete arrays."""
    flat, treedef = jax.tree_util.tree_flatten(
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))
    rngs = jax.random.split(rng, len(flat))
    leaves = []
    for r, spec in zip(rngs, flat):
        if spec.init == "zeros":
            leaves.append(jnp.zeros(spec.shape, dtype))
        elif spec.init == "ones":
            leaves.append(jnp.ones(spec.shape, dtype))
        else:
            fan_in = spec.shape[0] if len(spec.shape) > 1 else max(spec.shape[-1], 1)
            std = spec.scale / math.sqrt(fan_in)
            # drawn in the target dtype: no float32 copy of a bf16 weight
            leaves.append(jax.random.normal(r, spec.shape, dtype) * std)
    return jax.tree_util.tree_unflatten(treedef, leaves)


def axes_from_specs(specs) -> ParamTree:
    """Mirror the spec tree, replacing each ParamSpec with its dims tuple."""
    return jax.tree_util.tree_map(
        lambda s: s.dims, specs, is_leaf=lambda x: isinstance(x, ParamSpec))


def shapes_from_specs(specs, dtype=jnp.float32) -> ParamTree:
    """Mirror the spec tree with ShapeDtypeStructs (for dry-run lowering)."""
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype),
        specs, is_leaf=lambda x: isinstance(x, ParamSpec))


# ----------------------------------------------------------------------
# Norms
def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6,
             zero_centered: bool = False) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale) if zero_centered else scale
    return (x * w).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias
    return out.astype(dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    return jnp.tanh(x / cap) * cap if cap > 0 else x


# ----------------------------------------------------------------------
# Positional encodings
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)                       # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., seq, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_embedding(positions: jax.Array, dim: int) -> jax.Array:
    half = dim // 2
    freqs = jnp.exp(-math.log(10000.0) * jnp.arange(half, dtype=jnp.float32) / half)
    angles = positions[..., None].astype(jnp.float32) * freqs
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


# ----------------------------------------------------------------------
# MLPs
def mlp_param_specs(cfg, d_ff: int | None = None) -> Dict[str, ParamSpec]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.mlp_kind in ("swiglu", "geglu"):
        return {
            "w_gate": ParamSpec((d, f), ("d_model", "d_ff")),
            "w_up": ParamSpec((d, f), ("d_model", "d_ff")),
            "w_down": ParamSpec((f, d), ("d_ff", "d_model")),
        }
    return {
        "w_up": ParamSpec((d, f), ("d_model", "d_ff")),
        "w_down": ParamSpec((f, d), ("d_ff", "d_model")),
    }


def mlp_apply(cfg, p: ParamTree, x: jax.Array) -> jax.Array:
    wu = p["w_up"].astype(x.dtype)
    wd = p["w_down"].astype(x.dtype)
    if cfg.mlp_kind == "swiglu":
        h = jax.nn.silu(x @ p["w_gate"].astype(x.dtype)) * (x @ wu)
    elif cfg.mlp_kind == "geglu":
        h = jax.nn.gelu(x @ p["w_gate"].astype(x.dtype), approximate=True) * (x @ wu)
    else:
        h = jax.nn.gelu(x @ wu, approximate=True)
    return h @ wd


# ----------------------------------------------------------------------
# Embedding / head
def embed_param_specs(cfg) -> Dict[str, ParamSpec]:
    specs = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                                    ("vocab", "d_model"))}
    if not cfg.tie_embeddings:
        specs["lm_head"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                     ("d_model", "vocab"))
    if cfg.frontend_stub:
        # projection from stub modality embeddings into d_model
        specs["frontend_proj"] = ParamSpec((cfg.d_model, cfg.d_model),
                                           ("d_model", "d_model_out"))
    return specs


def embed_tokens(cfg, p: ParamTree, tokens: jax.Array, dtype) -> jax.Array:
    x = p["embedding"].astype(dtype)[tokens]
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), dtype)
    return x


def lm_logits(cfg, p: ParamTree, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = x @ p["embedding"].astype(x.dtype).T
    else:
        logits = x @ p["lm_head"].astype(x.dtype)
    return softcap(logits.astype(jnp.float32), cfg.final_logit_softcap)
