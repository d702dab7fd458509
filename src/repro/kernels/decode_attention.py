"""Single-token GQA decode attention Pallas TPU kernel (flash-decode).

The decode hot loop is memory-bound: the whole KV cache is streamed once
per step. Tiling: grid (batch, kv_blocks); each program streams one
(block_k x KV x D) K/V tile through VMEM and updates, for every kv head, an
online-softmax accumulator over its G=H/KV query heads — the query tile
(KV x G x D) stays resident in VMEM across the whole sweep, so HBM traffic is
exactly one pass over K + V (the roofline minimum). The tile spans all kv
heads because Mosaic needs the last two block dims to be (8, 128)-aligned or
whole, and a one-head slice of the (B, S, KV, D) cache is neither.

Per-row validity (ragged lengths / ring buffers) comes in as a mask
(B, S), tiled alongside K as int32 rows of shape (1, block_k).
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


def _kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, mout_ref, lout_ref,
            acc_ref, m_ref, l_ref, *, scale: float, softcap: float, kv: int):
    kj = pl.program_id(1)
    nk = pl.num_programs(1)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    valid = mask_ref[0] != 0                            # (1, bk)
    for h in range(kv):
        q = q_ref[0, h].astype(jnp.float32)             # (G, D)
        k = k_ref[0, :, h, :].astype(jnp.float32)       # (bk, D)
        v = v_ref[0, :, h, :].astype(jnp.float32)       # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[h] = m_new

    @pl.when(kj == nk - 1)
    def _finish():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)
        mout_ref[0] = m_ref[...]
        lout_ref[0] = l_ref[...]


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     mask: jax.Array, *, softcap: float = 0.0,
                     scale: Optional[float] = None, block_k: int = 512,
                     return_stats: bool = False,
                     interpret: bool = False):
    """q: (B, KV, G, D) one query token per head-group; k/v: (B, S, KV, D)
    — the model's NATIVE cache layout, so no transpose pass over the cache
    is ever materialised; mask: (B, S) bool (valid cache slots). Returns
    (B, KV, G, D) — plus the per-shard online-softmax stats (m, l):
    (B, KV, G, 1) when ``return_stats`` (distributed flash-decode merges
    shards with them)."""
    b, kv, g, d = q.shape
    s = k.shape[1]
    # a ragged last block would read mask slots past the cache end
    block_k = math.gcd(s, min(block_k, s))
    nk = s // block_k
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_kernel, scale=scale, softcap=softcap, kv=kv)
    mask = mask.astype(jnp.int32).reshape(b, 1, s)

    out, m, l = pl.pallas_call(
        kernel,
        grid=(b, nk),
        in_specs=[
            pl.BlockSpec((1, kv, g, d), lambda b_, j: (b_, 0, 0, 0)),
            pl.BlockSpec((1, block_k, kv, d), lambda b_, j: (b_, j, 0, 0)),
            pl.BlockSpec((1, block_k, kv, d), lambda b_, j: (b_, j, 0, 0)),
            pl.BlockSpec((1, 1, block_k), lambda b_, j: (b_, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, kv, g, d), lambda b_, j: (b_, 0, 0, 0)),
            pl.BlockSpec((1, kv, g, 1), lambda b_, j: (b_, 0, 0, 0)),
            pl.BlockSpec((1, kv, g, 1), lambda b_, j: (b_, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, kv, g, d), q.dtype),
            jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, kv, g, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((kv, g, d), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
            pltpu.VMEM((kv, g, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v, mask)
    if return_stats:
        return out, m, l
    return out
