"""Roofline analysis unit tests: HLO collective parser, cost conventions."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config, get_shape
from repro.roofline import analysis as ra


def test_collective_parser_synthetic():
    hlo = """
  %ag = bf16[16,1024,512]{2,1,0} all-gather(bf16[1,1024,512] %x), replica_groups={{0,1,2,3,4,5,6,7,8,9,10,11,12,13,14,15}}, dimensions={0}
  %ar = f32[256,128]{1,0} all-reduce(f32[256,128] %y), replica_groups=[16,16]<=[256], to_apply=%add
  %rs = f32[16,128]{1,0} reduce-scatter(f32[256,128] %y2), replica_groups={{0,1}}, dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(bf16[64,64] %z), source_target_pairs={{0,1}}
  %no = f32[8,8]{1,0} add(f32[8,8] %a, f32[8,8] %b)
"""
    stats = ra.parse_collectives(hlo)
    assert stats.counts["all-gather"] == 1
    assert stats.counts["all-reduce"] == 1
    assert stats.counts["reduce-scatter"] == 1
    assert stats.counts["collective-permute"] == 1
    ag_bytes = 16 * 1024 * 512 * 2
    assert stats.wire_bytes["all-gather"] == pytest.approx(
        ag_bytes * 15 / 16)
    ar_bytes = 256 * 128 * 4
    assert stats.wire_bytes["all-reduce"] == pytest.approx(
        2 * ar_bytes * 15 / 16)
    rs_bytes = 16 * 128 * 4
    assert stats.wire_bytes["reduce-scatter"] == pytest.approx(rs_bytes * 1)
    assert stats.wire_bytes["collective-permute"] == pytest.approx(
        64 * 64 * 2)


def test_cost_analysis_is_per_device():
    """Documented convention: compiled cost_analysis reports the
    per-partition module (verified here on a sharded matmul). Mesh built
    through the launcher helper so the AxisType version gate is covered."""
    from repro.launch.mesh import make_local_mesh
    _ = make_local_mesh()
    A = jax.ShapeDtypeStruct((256, 128), jnp.float32)
    B = jax.ShapeDtypeStruct((128, 64), jnp.float32)
    comp = jax.jit(lambda a, b: a @ b).lower(A, B).compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    assert ca["flops"] == pytest.approx(2 * 256 * 128 * 64)


def test_scan_body_counted_once_motivates_unroll():
    """The dry-run unrolls because XLA counts a while body once; this test
    pins that behaviour so a jax upgrade that changes it gets noticed."""
    def f(x, ws):
        y, _ = jax.lax.scan(lambda c, w: (c @ w, None), x, ws)
        return y
    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((10, 64, 64), jnp.float32)
    comp = jax.jit(f).lower(x, ws).compile()
    ca = comp.cost_analysis()
    ca = ca[0] if isinstance(ca, list) else ca
    one = 2 * 64 * 64 * 64
    assert ca["flops"] < 2 * one      # body counted once, not 10x

    comp_unrolled = jax.jit(
        lambda x, ws: jax.lax.scan(lambda c, w: (c @ w, None), x, ws,
                                   unroll=True)[0]).lower(x, ws).compile()
    ca2 = comp_unrolled.cost_analysis()
    ca2 = ca2[0] if isinstance(ca2, list) else ca2
    assert ca2["flops"] == pytest.approx(10 * one)


def test_model_flops_conventions():
    cfg = get_config("phi4-mini-3.8b")
    n = cfg.param_count(active_only=True)
    train = ra.model_flops(cfg, get_shape("train_4k"), 256)
    assert train == pytest.approx(6 * n * 256 * 4096 / 256)
    dec = ra.model_flops(cfg, get_shape("decode_32k"), 256)
    assert dec == pytest.approx(2 * n * 128 / 256)
    # MoE: active-only params
    ds = get_config("deepseek-v3-671b")
    assert ds.param_count(active_only=True) < 0.1 * ds.param_count()


def test_roofline_dominant_term():
    v5e = ra.chip_peaks(ra.V5E)
    r = ra.Roofline(flops=1e12, hbm_bytes=1e9, collective_bytes=1e6,
                    compute_s=1e12 / v5e.flops,
                    memory_s=1e9 / v5e.hbm_bw,
                    collective_s=1e6 / v5e.ici_bw,
                    collectives=ra.CollectiveStats({}, {}),
                    model_flops=5e11)
    assert r.dominant == "compute"
    assert 0 < r.roofline_fraction <= 1


def test_chip_peaks_unknown_kind_raises():
    """Peaks come from the table keyed by device_kind; an unknown kind is an
    error, never the v5e default."""
    v5e = ra.chip_peaks("TPU v5 lite")
    assert v5e.flops == 197e12 and v5e.hbm_bw == 819e9
    with pytest.raises(KeyError, match="TPU v99"):
        ra.chip_peaks("TPU v99")
