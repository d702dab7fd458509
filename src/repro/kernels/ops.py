"""jit'd wrappers dispatching model-layout calls onto the Pallas kernels.

On TPU the kernels compile to Mosaic. On the CPU they run in interpret
mode — the kernel body runs as JAX ops, which is how tests check them
against the oracles. Any other backend is an error, never a silent
interpreter. ``force_ref()`` routes everything to the pure-jnp oracles
instead (used by tests to cross-check the dispatch layer itself).

When sharding rules are active (``repro.distributed.ctx``), the kernels run
under ``shard_map``: batch shards over (pod, data); the flash query grid
sequence-shards over model (each shard passes its global q-offset into the
kernel, K/V stay whole per shard); decode sequence-shards the KV cache over
model and merges the per-shard online-softmax stats with psum — the
distributed flash-decode pattern. This matches how a Mosaic kernel is
deployed on a real pod (the kernel itself never issues collectives).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.ctx import current_rules
from repro.kernels import decode_attention as dec_k
from repro.kernels import flash_attention as fa_k
from repro.kernels import ref
from repro.kernels import rwkv6_wkv as rwkv_k
from repro.kernels import ssm_scan as ssm_k

_FORCE_REF = False


def force_ref(on: bool = True):
    global _FORCE_REF
    _FORCE_REF = on


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas TPU kernels cannot run on {backend!r}: "
                           "they compile on 'tpu' and interpret on 'cpu'")
    return backend == "cpu"


def _shard_axes(mesh, size: int, cands) -> Tuple[str, ...]:
    axes = []
    names = dict(mesh.shape)
    for a in cands:
        if a in names and size % (names[a] * math.prod(
                names[x] for x in axes)) == 0:
            axes.append(a)
    return tuple(axes)


# ----------------------------------------------------------------------
def _flash_layout(mesh, b, s):
    b_axes = _shard_axes(mesh, b, ("pod", "data"))
    s_axes = _shard_axes(mesh, s, ("model",))
    bspec = b_axes if len(b_axes) != 1 else b_axes[0]
    sspec = s_axes[0] if s_axes else None
    return b_axes, s_axes, (bspec or None), sspec


def _flash_fwd_call(qt, kt, vt, window, softcap, scale):
    """Shard-mapped fwd kernel; returns (out, lse) in (B,H,S,D) layout."""
    call = functools.partial(fa_k.flash_attention, causal=True, window=window,
                             softcap=softcap, scale=scale, return_lse=True,
                             interpret=_interpret())
    rules = current_rules()
    if rules is None:
        return call(qt, kt, vt)
    mesh = rules.mesh
    b, h, s, d = qt.shape
    _, s_axes, bspec, sspec = _flash_layout(mesh, b, s)

    def body(q_, k_, v_):
        off = (jax.lax.axis_index(s_axes[0]) * q_.shape[2]
               if s_axes else jnp.int32(0))
        return call(q_, k_, v_, q_offset=off)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, sspec, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None)),
        out_specs=(P(bspec, None, sspec, None), P(bspec, None, sspec, None)),
        check_vma=False)(qt, kt, vt)


def _flash_bwd_call(qt, kt, vt, dout, lse, delta, window, softcap, scale):
    from repro.kernels import flash_attention_bwd as fab
    call = functools.partial(fab.flash_attention_bwd, causal=True,
                             window=window, softcap=softcap, scale=scale,
                             interpret=_interpret())
    rules = current_rules()
    if rules is None:
        return call(qt, kt, vt, dout, lse, delta)
    mesh = rules.mesh
    b, h, s, d = qt.shape
    _, s_axes, bspec, sspec = _flash_layout(mesh, b, s)

    def body(q_, k_, v_, do_, lse_, delta_):
        off = (jax.lax.axis_index(s_axes[0]) * q_.shape[2]
               if s_axes else jnp.int32(0))
        dq, dk, dv = call(q_, k_, v_, do_, lse_, delta_, q_offset=off)
        if s_axes:   # each q-seq shard holds partial dk/dv — reduce
            dk = jax.lax.psum(dk, s_axes)
            dv = jax.lax.psum(dv, s_axes)
        return dq, dk, dv

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, sspec, None),
                  P(bspec, None, None, None),
                  P(bspec, None, None, None),
                  P(bspec, None, sspec, None),
                  P(bspec, None, sspec, None),
                  P(bspec, None, sspec, None)),
        out_specs=(P(bspec, None, sspec, None),
                   P(bspec, None, None, None),
                   P(bspec, None, None, None)),
        check_vma=False)(qt, kt, vt, dout, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(qt, kt, vt, window, softcap, scale):
    out, _ = _flash_fwd_call(qt, kt, vt, window, softcap, scale)
    return out


def _flash_vjp_fwd(qt, kt, vt, window, softcap, scale):
    out, lse = _flash_fwd_call(qt, kt, vt, window, softcap, scale)
    return out, (qt, kt, vt, out, lse)


def _flash_vjp_bwd(window, softcap, scale, res, dout):
    qt, kt, vt, out, lse = res
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)
    dq, dk, dv = _flash_bwd_call(qt, kt, vt, dout, lse, delta,
                                 window, softcap, scale)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def flash_attention(q, k, v, *, window: Optional[int] = None,
                    attn_softcap: float = 0.0,
                    scale: Optional[float] = None) -> jax.Array:
    """Model layout q: (B,S,H,D), k/v: (B,S,KV,D) -> (B,S,H,D).
    Differentiable: fwd/bwd both run the Pallas kernels (custom_vjp)."""
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    if _FORCE_REF:
        out = ref.flash_attention_ref(qt, kt, vt, causal=True, window=window,
                                      softcap=attn_softcap, scale=scale)
        return jnp.swapaxes(out, 1, 2)
    out = _flash(qt, kt, vt, window, attn_softcap, scale)
    return jnp.swapaxes(out, 1, 2)


# ----------------------------------------------------------------------
def decode_attention(q, k, v, mask, *, attn_softcap: float = 0.0,
                     scale: Optional[float] = None) -> jax.Array:
    """Model layout q: (B,1,H,D), k/v: (B,S,KV,D), mask: (B,S) ->
    (B,1,H,D). Distributed flash-decode: KV sequence shards over model (+
    data when batch can't take it); per-shard (out, m, l) merge via psum."""
    b, _, h, d = q.shape
    kv = k.shape[2]
    s = k.shape[1]
    g = h // kv
    qd = q[:, 0].reshape(b, kv, g, d)
    if _FORCE_REF:
        kt = jnp.swapaxes(k, 1, 2)
        vt = jnp.swapaxes(v, 1, 2)
        out = ref.decode_attention_ref(qd, kt, vt, mask,
                                       softcap=attn_softcap, scale=scale)
        return out.reshape(b, 1, h, d)

    rules = current_rules()

    def local(q_, k_, v_, m_):
        # kernel consumes the native (B,S,KV,D) cache layout — no transpose
        return dec_k.decode_attention(q_, k_, v_, m_, softcap=attn_softcap,
                                      scale=scale, return_stats=True,
                                      interpret=_interpret())

    if rules is None:
        out, _, _ = local(qd, k, v, mask)
        return out.reshape(b, 1, h, d)

    mesh = rules.mesh
    b_axes = _shard_axes(mesh, b, ("pod", "data"))
    rest = tuple(a for a in ("pod", "data", "model")
                 if a in dict(mesh.shape) and a not in b_axes)
    s_axes = _shard_axes(mesh, s, rest)
    bspec = b_axes if len(b_axes) != 1 else (b_axes[0] if b_axes else None)
    sspec = (s_axes if len(s_axes) != 1 else s_axes[0]) if s_axes else None

    def body(q_, k_, v_, m_):
        out, mx, l = local(q_, k_, v_, m_)        # out (B,KV,G,D); mx,l (B,KV,G,1)
        if s_axes:
            m_star = jax.lax.pmax(mx, s_axes)
            w = jnp.exp(mx - m_star) * l           # (B,KV,G,1)
            num = jax.lax.psum((out * w).astype(jnp.float32), s_axes)
            den = jax.lax.psum(w, s_axes)
            out = (num / jnp.maximum(den, 1e-30)).astype(out.dtype)
        return out

    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None),
                  P(bspec, sspec, None, None),
                  P(bspec, sspec, None, None),
                  P(bspec, sspec)),
        out_specs=P(bspec, None, None, None),
        check_vma=False)(qd, k, v, mask)
    return out.reshape(b, 1, h, d)


def ssm_scan(u, dt, bm, cm, a, d_skip):
    if _FORCE_REF:
        return ref.ssm_scan_ref(u, dt, bm, cm, a, d_skip)
    return ssm_k.ssm_scan(u, dt, bm, cm, a, d_skip, interpret=_interpret())


def rwkv6_wkv(r, k, v, w, u):
    if _FORCE_REF:
        return ref.rwkv6_wkv_ref(r, k, v, w, u)
    return rwkv_k.rwkv6_wkv(r, k, v, w, u, interpret=_interpret())
