"""The served weights, drawn again layer by layer.

Recipe (the configuration file's ``weights``): the key of ladder level
``l`` is ``fold_in(PRNGKey(key), l)``, split three ways for the embedding,
the final norm and the stack of layers; the layer stack's key is split once
per layer, and each layer's key once per leaf of that layer, leaves in
sorted path order. A normal leaf is ``normal(k, shape, bf16) *
(1 / sqrt(shape[0]))`` (``shape[-1]`` for a vector); ``zeros`` and ``ones``
leaves draw nothing but still take a key. The same recipe at one layer at a
time gives what a jitted draw of the whole model gives, bit for bit, and
needs one layer's memory.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

Leaf = Tuple[Tuple[str, ...], Tuple[int, ...], str]    # path, shape, init


def _draw(key, leaves: List[Leaf], dtype) -> Dict[str, jax.Array]:
    leaves = sorted(leaves)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for k, (path, shape, init) in zip(keys, leaves):
        if init == "zeros":
            w = jnp.zeros(shape, dtype)
        elif init == "ones":
            w = jnp.ones(shape, dtype)
        else:
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            w = jax.random.normal(k, shape, dtype) * (1.0 / math.sqrt(fan_in))
        out["/".join(path)] = w
    return out


class LevelWeights:
    """Weights of one ladder level, drawn on demand, returned as float32."""

    def __init__(self, doc: Dict, level: int, embed_leaves: List[Leaf],
                 layer_leaves: List[Leaf], n_layers: int):
        w = doc["weights"]
        self.dtype = jnp.dtype(w["dtype"])
        lkey = jax.random.fold_in(jax.random.PRNGKey(w["key"]), level)
        self._top = jax.random.split(lkey, 3)   # embed, final_norm, layers
        self._layer_keys = jax.random.split(self._top[2], n_layers)
        self.n_layers = n_layers
        self._embed_leaves = tuple(embed_leaves)
        self._layer_leaves = tuple(layer_leaves)

    @functools.cached_property
    def _layer_fn(self):
        leaves, dtype = list(self._layer_leaves), self.dtype
        return jax.jit(lambda k: _draw(k, leaves, dtype))

    def embed(self) -> Dict[str, jax.Array]:
        leaves, dtype = list(self._embed_leaves), self.dtype
        return _to_f32(jax.jit(lambda k: _draw(k, leaves, dtype))(
            self._top[0]))

    def layer(self, i: int) -> Dict[str, jax.Array]:
        return _to_f32(self._layer_fn(self._layer_keys[i]))


def _to_f32(tree):
    """Widen after the draw has written its dtype: a widening fused into
    the draw would skip the rounding of ``normal * std`` to that dtype."""
    return {k: v.astype(jnp.float32) for k, v in tree.items()}
