"""RWKV6 (Finch) WKV recurrence Pallas TPU kernel.

Per head:  y_t = r_t . (S_{t-1} + (u * k_t) v_t^T),
           S_t = diag(w_t) S_{t-1} + k_t v_t^T,
with data-dependent per-channel decay w_t. Sequential in t, parallel over
(batch, head). Grid (batch*heads, seq_chunks), seq chunks innermost; the
(Dk x Dv) fp32 state lives in VMEM scratch across chunks, one pass over
r/k/v/w, rank-1 updates inside a fori_loop.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, y_ref, sout_ref, s_ref, *,
            chunk: int):
    sj = pl.program_id(1)
    ns = pl.num_programs(1)

    @pl.when(sj == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)                   # (chunk, Dk)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                   # (chunk, Dv)
    w = w_ref[0].astype(jnp.float32)                   # (chunk, Dk)
    u = u_ref[0]                                       # (1, Dk)

    def step(t, carry):
        s, ys = carry                                  # s: (Dk, Dv)
        kv = k[t][:, None] * v[t][None, :]             # (Dk, Dv)
        y = jnp.sum((s + u[0][:, None] * kv) * r[t][:, None], axis=0)
        s = w[t][:, None] * s + kv
        ys = jax.lax.dynamic_update_index_in_dim(ys, y, t, 0)
        return s, ys

    ys0 = jnp.zeros((chunk, v.shape[1]), jnp.float32)
    s, ys = jax.lax.fori_loop(0, chunk, step, (s_ref[...], ys0))
    s_ref[...] = s
    y_ref[0] = ys.astype(y_ref.dtype)

    @pl.when(sj == ns - 1)
    def _emit_state():
        sout_ref[0] = s.astype(sout_ref.dtype)


def rwkv6_wkv(r: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
              u: jax.Array, *, chunk: int = 128,
              interpret: bool = False):
    """r/k/w: (BH, S, Dk); v: (BH, S, Dv); u: (BH, Dk) bonus.
    Returns (y (BH, S, Dv), s_final (BH, Dk, Dv) fp32). Caller folds
    (batch, heads) into BH."""
    bh, s, dk = r.shape
    dv = v.shape[-1]
    chunk = min(chunk, s)
    ns = pl.cdiv(s, chunk)

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(bh, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, chunk, dv), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, chunk, dk), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, 1, dk), lambda b_, j: (b_, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, dv), lambda b_, j: (b_, j, 0)),
            pl.BlockSpec((1, dk, dv), lambda b_, j: (b_, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, dv), r.dtype),
            jax.ShapeDtypeStruct((bh, dk, dv), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(r, k, v, w, u.reshape(bh, 1, dk))
