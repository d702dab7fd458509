"""Per-kernel shape/dtype sweeps: Pallas (interpret=True on CPU) vs the
pure-jnp oracles in kernels/ref.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention
from repro.kernels.flash_attention import flash_attention
from repro.kernels.rwkv6_wkv import rwkv6_wkv
from repro.kernels.ssm_scan import ssm_scan

TOL = {jnp.float32: 5e-5, jnp.bfloat16: 2e-2}


def _tol(dtype):
    return TOL[jnp.bfloat16] if dtype == jnp.bfloat16 else TOL[jnp.float32]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,h,kv,s,d", [
    (1, 4, 4, 128, 64),       # MHA
    (2, 8, 2, 256, 64),       # GQA 4:1
    (1, 4, 1, 192, 128),      # MQA, ragged seq vs block
])
@pytest.mark.parametrize("window,softcap", [(None, 0.0), (64, 0.0),
                                            (None, 30.0)])
def test_flash_attention(b, h, kv, s, d, window, softcap, dtype, rng):
    ks = jax.random.split(rng, 3)
    q = jax.random.normal(ks[0], (b, h, s, d), dtype)
    k = jax.random.normal(ks[1], (b, kv, s, d), dtype)
    v = jax.random.normal(ks[2], (b, kv, s, d), dtype)
    out = flash_attention(q, k, v, window=window, softcap=softcap,
                          interpret=True, block_q=64, block_k=64)
    exp = ref.flash_attention_ref(q, k, v, window=window, softcap=softcap)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,kv,g,s,d", [(2, 4, 2, 256, 64), (1, 8, 1, 128, 128),
                                        (2, 2, 8, 192, 64),
                                        (2, 8, 3, 160, 128)])  # s % block_k
def test_decode_attention(b, kv, g, s, d, dtype, rng):
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (b, kv, g, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv, d), dtype)   # native cache layout
    v = jax.random.normal(ks[2], (b, s, kv, d), dtype)
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    mask = jnp.arange(s)[None, :] < lengths[:, None]
    out = decode_attention(q, k, v, mask, interpret=True, block_k=64)
    exp = ref.decode_attention_ref(q, jnp.swapaxes(k, 1, 2),
                                   jnp.swapaxes(v, 1, 2), mask)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("b,s,d,n,chunk,block_d", [
    (2, 128, 96, 8, 32, 32),
    (1, 64, 256, 16, 64, 128),
])
def test_ssm_scan(b, s, d, n, chunk, block_d, dtype, rng):
    ks = jax.random.split(rng, 5)
    u = jax.random.normal(ks[0], (b, s, d), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, d)) * 0.5).astype(dtype)
    bm = jax.random.normal(ks[2], (b, s, n), dtype)
    cm = jax.random.normal(ks[3], (b, s, n), dtype)
    a = -jnp.exp(jax.random.normal(ks[4], (d, n)) * 0.3)
    dskip = jnp.ones((d,), jnp.float32)
    y, h = ssm_scan(u, dt, bm, cm, a, dskip, interpret=True,
                    block_d=block_d, chunk=chunk)
    y_ref, h_ref = ref.ssm_scan_ref(u, dt, bm, cm, a, dskip)
    tol = _tol(dtype) * 4  # recurrence accumulates rounding over S steps
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("bh,s,dk,dv,chunk", [(4, 64, 32, 32, 16),
                                              (2, 128, 64, 64, 64)])
def test_rwkv6_wkv(bh, s, dk, dv, chunk, dtype, rng):
    ks = jax.random.split(rng, 5)
    r = jax.random.normal(ks[0], (bh, s, dk), dtype)
    k = (jax.random.normal(ks[1], (bh, s, dk)) * 0.3).astype(dtype)
    v = jax.random.normal(ks[2], (bh, s, dv), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (bh, s, dk))).astype(dtype)
    u = (jax.random.normal(ks[4], (bh, dk)) * 0.1).astype(dtype)
    y, st = rwkv6_wkv(r, k, v, w, u, interpret=True, chunk=chunk)
    y_ref, st_ref = ref.rwkv6_wkv_ref(r, k, v, w, u)
    tol = _tol(dtype) * 4
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=tol, rtol=tol)


def test_model_kernel_integration(rng):
    """use_kernels=True must agree with the einsum path end-to-end."""
    from repro.configs import get_smoke_config
    from repro.models import forward, init_params
    for arch in ("qwen3-32b", "rwkv6-1.6b", "jamba-1.5-large-398b"):
        cfg = get_smoke_config(arch).scaled(dtype="float32")
        params = init_params(cfg, rng)
        toks = jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)
        l0, _ = forward(cfg, params, toks, use_kernels=False)
        l1, _ = forward(cfg, params, toks, use_kernels=True)
        np.testing.assert_allclose(np.asarray(l0), np.asarray(l1),
                                   atol=5e-4, rtol=5e-4)


def test_interpret_only_on_cpu(monkeypatch):
    """Kernels compile on TPU and interpret on the CPU; any other backend
    is an error, never a silent interpreter."""
    from repro.kernels import ops
    for backend, interpret in (("cpu", True), ("tpu", False)):
        monkeypatch.setattr(ops.jax, "default_backend", lambda b=backend: b)
        assert ops._interpret() is interpret
    monkeypatch.setattr(ops.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="gpu"):
        ops._interpret()


@pytest.mark.parametrize("window,softcap", [(None, 0.0), (32, 20.0)])
def test_flash_attention_grad(window, softcap, rng):
    """The custom_vjp backward kernels (lse/delta as (B, H, Sq, 1)) match
    autodiff through the oracle."""
    from repro.kernels import ops
    ks = jax.random.split(rng, 4)
    b, s, h, kv, d = 1, 128, 4, 2, 64
    q = jax.random.normal(ks[0], (b, s, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, kv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, kv, d), jnp.float32)
    ct = jax.random.normal(ks[3], (b, s, h, d), jnp.float32)

    def loss(fn, q, k, v):
        return jnp.sum(fn(q, k, v) * ct)

    kern = lambda q, k, v: ops.flash_attention(
        q, k, v, window=window, attn_softcap=softcap)
    oracle = lambda q, k, v: jnp.swapaxes(ref.flash_attention_ref(
        *(jnp.swapaxes(x, 1, 2) for x in (q, k, v)), causal=True,
        window=window, softcap=softcap), 1, 2)
    got = jax.grad(functools.partial(loss, kern), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(functools.partial(loss, oracle), argnums=(0, 1, 2))(q, k, v)
    for g_, w_ in zip(got, want):
        np.testing.assert_allclose(np.asarray(g_), np.asarray(w_),
                                   atol=2e-4, rtol=2e-4)
