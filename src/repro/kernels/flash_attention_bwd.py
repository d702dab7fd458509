"""Flash-attention backward Pallas TPU kernels.

Standard recomputation form (no stored probabilities): given q, k, v, dout,
the fwd log-sum-exp ``lse`` and ``delta = rowsum(dout * out)``, per block

    p  = exp(q k^T * scale - lse)
    dv += p^T dout
    ds = p * (dout v^T - delta) * scale
    dk += ds^T q
    dq += ds k

Two kernels, mirroring the fwd tiling:
  * dq kernel  — grid (b, h, nq, nk): dq accumulates in VMEM across the
    kv (innermost) steps.
  * dkv kernel — grid (b, kv_head, nk, g*nq): the (g x nq) pairs of this kv
    head's query group run as one sequential innermost dim so dk/dv
    accumulate in VMEM without materialising per-q-head partials.

Softcap backward is included (d tanh); window/causal masks match fwd.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _rows_cols(q_off, qi, kj, block_q, block_k):
    rows = q_off + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return rows, cols


def _p_and_mask(q, k, lse, rows, cols, *, scale, causal, window, softcap,
                seq_len):
    s_raw = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        t = jnp.tanh(s_raw / softcap)
        s = t * softcap
        dcap = 1.0 - t * t          # d softcap / d s_raw
    else:
        s = s_raw
        dcap = None
    mask = cols < seq_len
    if causal:
        mask &= cols <= rows
    if window is not None:
        mask &= cols > rows - window
    p = jnp.where(mask, jnp.exp(s - lse), 0.0)
    return p, dcap, mask


def _dq_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
               dq_ref, dq_acc, *, scale, causal, window, softcap,
               block_q, block_k, seq_len):
    qi, kj = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    q_off = off_ref[0]
    rows, cols = _rows_cols(q_off, qi, kj, block_q, block_k)
    run = True
    if causal:
        run = kj * block_k <= q_off + qi * block_q + block_q - 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        p, dcap, _ = _p_and_mask(q, k, lse, rows, cols, scale=scale,
                                 causal=causal, window=window,
                                 softcap=softcap, seq_len=seq_len)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if dcap is not None:
            ds = ds * dcap
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kj == nk - 1)
    def _finish():
        dq_ref[0, 0] = dq_acc[...].astype(dq_ref.dtype)


def _dkv_kernel(off_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal, window,
                softcap, block_q, block_k, seq_len, nq):
    kj, gq = pl.program_id(2), pl.program_id(3)
    ngq = pl.num_programs(3)
    qi = gq % nq

    @pl.when(gq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q_off = off_ref[0]
    rows, cols = _rows_cols(q_off, qi, kj, block_q, block_k)
    run = True
    if causal:
        run = kj * block_k <= q_off + qi * block_q + block_q - 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)
        k = k_ref[0, 0].astype(jnp.float32)
        v = v_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]
        p, dcap, _ = _p_and_mask(q, k, lse, rows, cols, scale=scale,
                                 causal=causal, window=window,
                                 softcap=softcap, seq_len=seq_len)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale
        if dcap is not None:
            ds = ds * dcap
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(gq == ngq - 1)
    def _finish():
        dk_ref[0, 0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_acc[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, dout, lse, delta, *,
                        causal: bool = True, window: Optional[int] = None,
                        softcap: float = 0.0, scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 512,
                        q_offset=None, interpret: bool = False):
    """q/dout: (B,H,Sq,D); k/v: (B,KV,S,D); lse/delta: (B,H,Sq,1).
    Returns (dq, dk, dv) with dk/dv group-summed to (B,KV,S,D)."""
    b, h, sq, d = q.shape
    kv = k.shape[1]
    s = k.shape[2]
    g = h // kv
    block_q = min(block_q, sq)
    block_k = min(block_k, s)
    nq = pl.cdiv(sq, block_q)
    nk = pl.cdiv(s, block_k)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    if q_offset is None:
        q_offset = jnp.zeros((1,), jnp.int32)
    else:
        q_offset = jnp.asarray(q_offset, jnp.int32).reshape(1)

    common = dict(scale=scale, causal=causal, window=window, softcap=softcap,
                  block_q=block_q, block_k=block_k, seq_len=s)

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b_, h_, i, j: (b_, h_, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_offset, q, k, v, dout, lse, delta)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common, nq=nq),
        grid=(b, kv, nk, g * nq),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, kv_, j, gq: (b_, kv_ * g + gq // nq,
                                                 gq % nq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, kv_, j, gq: (b_, kv_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, kv_, j, gq: (b_, kv_, j, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, kv_, j, gq: (b_, kv_ * g + gq // nq,
                                                 gq % nq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, kv_, j, gq: (b_, kv_ * g + gq // nq,
                                                 gq % nq, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, kv_, j, gq: (b_, kv_ * g + gq // nq,
                                                 gq % nq, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d), lambda b_, kv_, j, gq: (b_, kv_, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, kv_, j, gq: (b_, kv_, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, s, d), k.dtype),
            jax.ShapeDtypeStruct((b, kv, s, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_offset, q, k, v, dout, lse, delta)
    return dq, dk, dv
