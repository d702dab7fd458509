"""Sharding-rules unit tests: divisibility fallback, axis-reuse, per-arch
param/cache spec coverage (these run on 1 CPU device via an abstract Mesh)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_NAMES, get_config
from repro.distributed import sharding as shd
from repro.launch.mesh import make_abstract_mesh


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: lets us unit-test 16x16 rules on a 1-CPU box
    # (constructed through the version-portable helper — the ctor
    # signature changed between jax 0.4.x and 0.5)
    return make_abstract_mesh((16, 16), ("data", "model"))


@pytest.fixture(scope="module")
def mesh3(request):
    return make_abstract_mesh((2, 16, 16), ("pod", "data", "model"))


def test_divisibility_fallback(mesh):
    rules = shd.make_rules(mesh, "train")
    # 8 experts can't shard over data(16) -> falls through to d_model
    spec = rules.spec_for((8, 4096, 14336), ("experts", "d_model", "expert_ff"))
    assert spec == P(None, "data", "model")
    # 256 experts can
    spec = rules.spec_for((256, 7168, 2048), ("experts", "d_model", "expert_ff"))
    assert spec == P("data", None, "model")


def test_axis_never_reused(mesh):
    rules = shd.make_rules(mesh, "train")
    for shape, dims in [
        ((64, 5120, 64, 128), ("layers", "d_model", "heads", "head_dim")),
        ((256, 4096, 16, 16), ("batch", "seq", "kv_heads", None)),
        ((128, 8, 8, 4096, 512), ("batch", "kv_heads", "heads",
                                  "scores_seq", None)),
    ]:
        spec = rules.spec_for(shape, dims)
        flat = [a for part in spec if part is not None
                for a in (part if isinstance(part, tuple) else (part,))]
        assert len(flat) == len(set(flat)), (shape, dims, spec)


def test_scores_seq_fallback(mesh):
    """8 kv-heads can't take the 16-way model axis; the seq dim must."""
    rules = shd.make_rules(mesh, "train")
    spec = rules.spec_for((256, 8, 3, 4096, 4096),
                          ("batch", "kv_heads", "heads", "scores_seq", None))
    assert spec == P("data", None, None, "model")


def test_serve_expert_grid(mesh, mesh3):
    rules = shd.make_rules(mesh, "serve")
    # deepseek: 256 routed experts over the full 256-chip grid
    spec = rules.spec_for((256, 7168, 2048),
                          ("experts", "d_model", "expert_ff"))
    assert spec == P(("data", "model"))
    rules3 = shd.make_rules(mesh3, "serve")
    spec3 = rules3.spec_for((512, 7168, 2048),
                            ("experts", "d_model", "expert_ff"))
    assert spec3 == P(("pod", "data", "model"))


def test_serve_long_shards_kv_seq(mesh):
    rules = shd.make_rules(mesh, "serve_long")
    spec = rules.spec_for((9, 1, 524288, 8, 128),
                          (None, "batch", "kv_seq", None, None))
    assert spec == P(None, None, ("data", "model"))


@pytest.mark.parametrize("arch", ARCH_NAMES)
@pytest.mark.parametrize("mode", ["train", "serve"])
def test_param_shardings_cover_arch(arch, mode, mesh):
    """Every param leaf gets a legal spec. Train mode (ZeRO-3) must leave
    essentially nothing replicated; serve mode may deliberately replicate
    small attention projections over data (no per-step all-gathers) but the
    replicated total must stay within a small HBM budget."""
    cfg = get_config(arch)
    rules = shd.make_rules(mesh, mode)
    shardings = shd.param_shardings(rules, cfg)
    from repro.models import transformer as tfm
    shapes = tfm.abstract_params(cfg)
    flat_sh = jax.tree_util.tree_leaves(shardings)
    flat_shape = jax.tree_util.tree_leaves(shapes)
    assert len(flat_sh) == len(flat_shape)
    replicated_bytes = sum(
        int(np.prod(sds.shape)) * 2           # bf16 deployment
        for sh, sds in zip(flat_sh, flat_shape) if sh.spec == P())
    budget = 64 * 2**20 if mode == "train" else 2 * 2**30
    assert replicated_bytes <= budget, (
        f"{arch}/{mode}: {replicated_bytes/2**30:.2f} GiB replicated")


@pytest.mark.parametrize("arch", ["qwen3-32b", "deepseek-v3-671b",
                                  "rwkv6-1.6b", "jamba-1.5-large-398b"])
def test_cache_shardings_cover_arch(arch, mesh):
    cfg = get_config(arch)
    rules = shd.make_rules(mesh, "serve")
    shardings = shd.cache_shardings(rules, cfg, batch=128, max_len=32768)
    for leaf in jax.tree_util.tree_leaves(shardings):
        assert leaf.spec is not None


def test_make_local_mesh_refuses_more_devices_than_exist():
    """Asking for more devices than exist is an error, not a smaller mesh."""
    from repro.launch.mesh import make_local_mesh
    n = jax.device_count()
    assert make_local_mesh(data=n).devices.size == n
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(data=n + 1)
