"""Mamba selective-scan Pallas TPU kernel.

The recurrence h_t = exp(dt_t A) h_t-1 + (dt_t u_t) B_t, y_t = C_t . h_t is
sequential in t but embarrassingly parallel over (batch, d_inner). TPU
adaptation of the CUDA selective-scan: grid (batch, d_blocks, seq_chunks)
with seq_chunks innermost ("arbitrary"), the (block_d x N) fp32 state
resident in VMEM scratch across chunks, and a fori_loop over the chunk's
timesteps inside the kernel — HBM traffic is one pass over u/dt/B/C plus
one y write, never materialising the (S x d x N) decay tensors that a
naive jnp formulation would.

A (d, N) enters as a block over d; B_t/C_t (chunk, N) tiles are shared
across all d blocks of a batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(u_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hout_ref,
            h_ref, *, chunk: int):
    sj = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    a = a_ref[...]                                     # (bd, N) fp32
    d_skip = d_ref[...]                                # (1, bd)
    u = u_ref[0].astype(jnp.float32)                   # (chunk, bd)
    dt = dt_ref[0].astype(jnp.float32)                 # (chunk, bd)
    bm = b_ref[0].astype(jnp.float32)                  # (chunk, N)
    cm = c_ref[0].astype(jnp.float32)                  # (chunk, N)

    def step(t, carry):
        h, ys = carry
        da = jnp.exp(dt[t][:, None] * a)               # (bd, N)
        h = da * h + (dt[t] * u[t])[:, None] * bm[t][None, :]
        y = jnp.sum(h * cm[t][None, :], axis=1)        # (bd,)
        ys = jax.lax.dynamic_update_index_in_dim(ys, y, t, 0)
        return h, ys

    ys0 = jnp.zeros((chunk, a.shape[0]), jnp.float32)
    h, ys = jax.lax.fori_loop(0, chunk, step, (h_ref[...], ys0))
    h_ref[...] = h
    y_ref[0] = (ys + u * d_skip).astype(y_ref.dtype)

    @pl.when(sj == ns - 1)
    def _emit_state():
        hout_ref[0] = h.astype(hout_ref.dtype)


def ssm_scan(u: jax.Array, dt: jax.Array, bm: jax.Array, cm: jax.Array,
             a: jax.Array, d_skip: jax.Array, *, block_d: int = 512,
             chunk: int = 128, interpret: bool = False):
    """u, dt: (B, S, d_in); bm, cm: (B, S, N); a: (d_in, N) (negative);
    d_skip: (d_in,). Returns (y, h_final): y (B, S, d_in) = scan +
    u * d_skip, h_final (B, d_in, N) fp32 (seeds the decode state)."""
    b, s, d_in = u.shape
    n = bm.shape[-1]
    block_d = min(block_d, d_in)
    chunk = min(chunk, s)
    nd = pl.cdiv(d_in, block_d)
    ns = pl.cdiv(s, chunk)

    kernel = functools.partial(_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=(b, nd, ns),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i)),
            pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i)),
            pl.BlockSpec((1, chunk, n), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, i, j: (b_, j, 0)),
            pl.BlockSpec((block_d, n), lambda b_, i, j: (i, 0)),
            pl.BlockSpec((1, block_d), lambda b_, i, j: (0, i)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda b_, i, j: (b_, j, i)),
            pl.BlockSpec((1, block_d, n), lambda b_, i, j: (b_, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, s, d_in), u.dtype),
            jax.ShapeDtypeStruct((b, d_in, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(u, dt, bm, cm, a, d_skip.reshape(1, -1))
