"""Compile-only checks of the served path for a described TPU v5e chip.

The TPU compiler is installed here and compiles for a chip that is described,
not attached: it refuses what Mosaic cannot tile and programs that do not fit
HBM, which interpret-mode tests never see. Nothing runs, so nothing here is a
time. The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops as kops
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.models import model as model_lib
from repro.roofline.analysis import V5E, chip_peaks

# phi4-mini widths
B, H, KV, D, S = 4, 24, 8, 128, 2048


@pytest.fixture(scope="module")
def one_chip():
    saved_log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"      # else libtpu logs under /tmp
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of it
    saved_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", saved_cache)
    if saved_log_dir is None:
        os.environ.pop("TPU_LOG_DIR", None)
    else:
        os.environ["TPU_LOG_DIR"] = saved_log_dir


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_decode_kernel_compiles(one_chip):
    g = H // KV
    compiled = jax.jit(decode_attention).lower(
        _sds((B, KV, g, D), one_chip), _sds((B, S, KV, D), one_chip),
        _sds((B, S, KV, D), one_chip),
        _sds((B, S), one_chip, jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_prefill_kernel_compiles(one_chip):
    compiled = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, return_lse=True)).lower(
        _sds((B, H, S, D), one_chip), _sds((B, KV, S, D), one_chip),
        _sds((B, KV, S, D), one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_fits_one_chip(one_chip, monkeypatch):
    """Decode step of a 2-layer, full-width phi4-mini slice in bf16 with the
    kernels compiled: it must fit one chip's HBM."""
    # the kernels pick interpret mode from the default backend, which is the
    # CPU here; this program is for the described TPU
    monkeypatch.setattr(kops, "_interpret", lambda: False)
    cfg = get_config("phi4-mini-3.8b").scaled(num_layers=2)
    as_chip = lambda tree: jax.tree_util.tree_map(
        lambda a: _sds(a.shape, one_chip, a.dtype), tree)
    params = as_chip(model_lib.abstract_params(cfg, jnp.bfloat16))
    caches = as_chip(model_lib.abstract_cache(cfg, B, S, jnp.bfloat16))
    lengths = _sds((B,), one_chip, jnp.int32)
    tokens = _sds((B,), one_chip, jnp.int32)
    step = jax.jit(lambda p, c, n, t: model_lib.decode_step(
        cfg, p, c, n, t, use_kernels=True), donate_argnums=(1,))
    compiled = step.lower(params, caches, lengths, tokens).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < used <= chip_peaks(V5E).hbm_bytes, used
