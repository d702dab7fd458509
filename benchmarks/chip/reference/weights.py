"""The served weights, drawn again one group or one layer at a time.

Recipe (the configuration file's ``weights``): the key of ladder level
``l`` is ``fold_in(PRNGKey(key), l)``, split once per top-level group of
the program's parameters, in the program's order (``embed``,
``final_norm``, then the layer stacks, and whatever follows them). A stack
of ``n`` units (layers) splits its key ``n`` times, one per unit. A group's
or a unit's key is split once per leaf, leaves in sorted path order. A
normal leaf is ``normal(k, shape, bf16) * (scale / sqrt(shape[0]))``
(``shape[-1]`` for a vector; ``scale`` 1 unless the leaf gives one);
``zeros`` and ``ones`` leaves draw nothing but still take a key. The same
recipe at one unit at a time gives what a jitted draw of the whole model
gives, bit for bit, and needs one unit's memory.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# path, shape, init ("normal" | "zeros" | "ones"), and optionally the
# normal's scale
Leaf = Tuple


@dataclasses.dataclass(frozen=True)
class Group:
    """One top-level entry of the program's parameters. ``units`` is the
    length of a stack of layers, None for a group that is not stacked; a
    group that is a single array has one leaf, with the path ()."""
    name: str
    leaves: Tuple[Leaf, ...]
    units: Optional[int] = None


def _draw(key, leaves: List[Leaf], dtype) -> Dict[str, jax.Array]:
    leaves = sorted(leaves)
    keys = jax.random.split(key, len(leaves))
    out = {}
    for k, (path, shape, init, *scale) in zip(keys, leaves):
        if init == "zeros":
            w = jnp.zeros(shape, dtype)
        elif init == "ones":
            w = jnp.ones(shape, dtype)
        else:
            fan_in = shape[0] if len(shape) > 1 else max(shape[-1], 1)
            std = (scale[0] if scale else 1.0) / math.sqrt(fan_in)
            w = jax.random.normal(k, shape, dtype) * std
        out["/".join(path)] = w
    return out


class LevelWeights:
    """Weights of one ladder level, drawn on demand, returned as float32."""

    def __init__(self, doc: Dict, level: int, groups: Sequence[Group]):
        w = doc["weights"]
        self.dtype = jnp.dtype(w["dtype"])
        lkey = jax.random.fold_in(jax.random.PRNGKey(w["key"]), level)
        self.groups = {g.name: g for g in groups}
        self._keys = dict(zip(self.groups,
                              jax.random.split(lkey, len(groups))))
        for g in groups:
            if g.units is not None:
                self._keys[g.name] = jax.random.split(self._keys[g.name],
                                                      g.units)
        self._fns = {}

    def group(self, name: str, unit: Optional[int] = None
              ) -> Dict[str, jax.Array]:
        """The leaves of group ``name`` (of its unit ``unit``, for a
        stack), by their paths joined with "/"."""
        key = self._keys[name]
        if unit is not None:
            key = key[unit]
        if name not in self._fns:
            leaves, dtype = list(self.groups[name].leaves), self.dtype
            self._fns[name] = jax.jit(lambda k: _draw(k, leaves, dtype))
        return _to_f32(self._fns[name](key))

    # the one-stack architectures' names
    def embed(self) -> Dict[str, jax.Array]:
        return self.group("embed")

    def layer(self, i: int) -> Dict[str, jax.Array]:
        return self.group("layers", i)

    @property
    def n_layers(self) -> int:
        return self.groups["layers"].units

    def against(self, params) -> Iterator[Tuple[str, np.ndarray, np.ndarray]]:
        """Every leaf of every group, the drawn one beside the program's
        (``params``, its parameter tree), both float32 on the host: one
        ``(where, drawn, served)`` for each leaf of each unit. The leaves of
        each group have to be the program's, by name."""
        for name, g in self.groups.items():
            flat = jax.tree_util.tree_flatten_with_path(params[name])[0]
            served = {"/".join(p.key for p in path): leaf
                      for path, leaf in flat}
            drawn = {"/".join(leaf[0]) for leaf in g.leaves}
            if drawn != set(served):
                raise ValueError(f"group {name}: reference leaves "
                                 f"{sorted(drawn)}, program {sorted(served)}")
            for unit in (range(g.units) if g.units is not None else [None]):
                w = self.group(name, unit)
                for leaf_name, leaf in served.items():
                    got = leaf if unit is None else leaf[unit]
                    yield (f"{name}[{unit}]/{leaf_name}",
                           np.asarray(w[leaf_name]),
                           np.asarray(got, np.float32))


def _to_f32(tree):
    """Widen after the draw has written its dtype: a widening fused into
    the draw would skip the rounding of ``normal * std`` to that dtype."""
    return {k: v.astype(jnp.float32) for k, v in tree.items()}
