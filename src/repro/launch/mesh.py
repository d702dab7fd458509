"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so
importing this module never touches jax device state. The dry-run launcher
sets XLA_FLAGS --xla_force_host_platform_device_count=512 before any jax
import; tests and benches see the real single CPU device.
"""
from __future__ import annotations

import jax


def _make_mesh(shape, axes) -> jax.sharding.Mesh:
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_abstract_mesh(shape, axes) -> jax.sharding.AbstractMesh:
    """Device-free mesh: lets the 16x16 sharding rules be unit-tested on a
    1-CPU box."""
    return jax.sharding.AbstractMesh(tuple(shape), tuple(axes))


def make_local_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small (data, model) mesh over this host's devices. Asking for more
    devices than exist is an error, not a smaller mesh."""
    n = jax.device_count()
    if data * model > n:
        raise ValueError(f"a {data}x{model} mesh needs {data * model} "
                         f"devices; {n} exist")
    return _make_mesh((data, model), ("data", "model"))
