"""Flash attention (prefill/train) Pallas TPU kernel.

Tiling: grid (batch, q_head, q_blocks, kv_blocks); the kv dim is the
innermost ("arbitrary") grid dim so the fp32 accumulator / running max /
running denominator live in VMEM scratch across kv steps (online softmax).
Q/K/V blocks are VMEM tiles via BlockSpec; GQA is handled in the K/V index
map (q head h reads kv head h // group_size) so no KV repetition is ever
materialised. Causal + sliding-window masking and gemma2-style logit
softcap are applied in-kernel.

Block sizes default to (128, 512) — MXU-aligned (multiples of 128 in the
lane dim, head_dim padded to 128) and small enough that the working set
  q(128xD) + k/v(512xD) + acc(128xD) fp32 + scores(128x512) fp32
fits well inside the ~16 MiB/core VMEM budget at D<=256.
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


def _kernel(off_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
            l_ref, *, scale: float, causal: bool, window: Optional[int],
            softcap: float, block_q: int, block_k: int, seq_len: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)
    nk = pl.num_programs(3)
    q_off = off_ref[0]     # global offset of this shard's q rows (SMEM)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    rows = q_off + qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    run = True
    if causal:
        # skip fully-masked kv blocks above the diagonal
        run = kj * block_k <= q_off + qi * block_q + block_q - 1

    @pl.when(run)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, D)
        v = v_ref[0, 0].astype(jnp.float32)            # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        mask = cols < seq_len
        if causal:
            mask &= cols <= rows
        if window is not None:
            mask &= cols > rows - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                             # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                          # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)                 # (bq, 1)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        # log-sum-exp per row — the bwd kernels recompute p from it
        lse_ref[0, 0] = m_ref[...] + jnp.log(denom)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: float = 0.0, scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 512,
                    q_offset=None, return_lse: bool = False,
                    interpret: bool = False):
    """q: (B, H, Sq, D); k/v: (B, KV, S, D). Returns (B, H, Sq, D)
    (+ the per-row log-sum-exp (B, H, Sq, 1) when ``return_lse`` — the
    backward kernels consume it; the trailing unit dim keeps its block
    (block_q, 1) legal for Mosaic, which a (1, block_q) row over H is not).

    ``q_offset``: global position of q row 0 — lets a shard_map caller
    sequence-shard the query grid (each shard passes its own offset) while
    K/V stay whole."""
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

    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        block_q=block_q, block_k=block_k, seq_len=s)

    _res = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, block_q, d), lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda b_, h_, i, j: (b_, h_ // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda b_, h_, i, j: (b_, h_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, sq, d), q.dtype),
            jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q_offset, q, k, v)
    out, lse = _res
    if return_lse:
        return out, lse
    return out
