"""Plain float32 reference of a dense decoder: RMSNorm, grouped-query
causal attention with rotary positions (rotate-half, whole head), a SwiGLU
or GELU MLP, tied or separate output head. Written from the published
description; the file's ``reduced`` keys say where the served model departs
from the published one, and this follows the served one."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

import counts as counts_mod

from . import numerics as nx
from .weights import Group, LevelWeights


def served_config(doc: Dict) -> Dict:
    """What the program's model config has to say for this file."""
    return {"d_model": doc["hidden_size"],
            "num_layers": doc["num_hidden_layers"],
            "num_heads": doc["num_attention_heads"],
            "num_kv_heads": doc["num_key_value_heads"],
            "head_dim": doc["head_dim"], "d_ff": doc["intermediate_size"],
            "vocab_size": doc["vocab_size"],
            "tie_embeddings": doc["tie_word_embeddings"],
            "mlp_kind": {"silu": "swiglu"}.get(doc["hidden_act"], "gelu"),
            "rope_theta": doc["rope_theta"], "norm_eps": doc["rms_norm_eps"],
            "attention_kind": "full", "pos_kind": "rope", "qk_norm": False,
            "post_norms": False, "zero_centered_norm": False,
            "attn_logit_softcap": 0.0, "final_logit_softcap": 0.0,
            "moe": None, "frontend_stub": False,
            "dtype": doc["torch_dtype"]}


def served_ladder(doc: Dict, level: int) -> Dict:
    """What the program's config of ladder level ``level`` has to say."""
    lv = doc["ladder"][level]
    return {"d_ff": lv["intermediate_size"],
            "num_layers": lv["num_hidden_layers"]}


def counts(doc: Dict, level: int) -> counts_mod.Sizes:
    """The operations and bytes of ladder level ``level`` (``counts.py``)."""
    lv = doc["ladder"][level]
    return counts_mod.Sizes(
        mixer="gqa", mlp={"silu": "swiglu"}.get(doc["hidden_act"], "gelu"),
        layers=lv["num_hidden_layers"], d_model=doc["hidden_size"],
        d_ff=lv["intermediate_size"], vocab=doc["vocab_size"],
        heads=doc["num_attention_heads"],
        kv_heads=doc["num_key_value_heads"], head_dim=doc["head_dim"],
        tied=doc["tie_word_embeddings"])


def smoke_sizes(doc: Dict, cfg) -> Dict:
    """The file's sizes for the program's model config ``cfg`` (the CPU
    tests put the program's smoke sizes in the file with it)."""
    return dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
                intermediate_size=cfg.d_ff, vocab_size=cfg.vocab_size)


def weights(doc: Dict, level: int) -> LevelWeights:
    d, h, kv = doc["hidden_size"], doc["num_attention_heads"], \
        doc["num_key_value_heads"]
    hd, v = doc["head_dim"], doc["vocab_size"]
    lv = doc["ladder"][level]
    f = lv["intermediate_size"]
    layer = [
        (("sub0", "attn", "wq"), (d, h, hd), "normal"),
        (("sub0", "attn", "wk"), (d, kv, hd), "normal"),
        (("sub0", "attn", "wv"), (d, kv, hd), "normal"),
        (("sub0", "attn", "wo"), (h, hd, d), "normal"),
        (("sub0", "norm_mixer"), (d,), "ones"),
        (("sub0", "norm_mlp"), (d,), "ones"),
    ]
    if doc["hidden_act"] == "silu":
        layer.append((("sub0", "mlp", "w_gate"), (d, f), "normal"))
    layer += [(("sub0", "mlp", "w_up"), (d, f), "normal"),
              (("sub0", "mlp", "w_down"), (f, d), "normal")]
    embed = [(("embedding",), (v, d), "normal")]
    if not doc["tie_word_embeddings"]:
        embed.append((("lm_head",), (d, v), "normal"))
    return LevelWeights(doc, level, [
        Group("embed", tuple(embed)),
        Group("final_norm", (((), (d,), "ones"),)),
        Group("layers", tuple(layer), lv["num_hidden_layers"])])


def _rope(x, theta):
    """x: (B, S, H, D), positions 0..S-1, rotate-half over the whole head."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "act", "fp8"))
def _layer(w, x, *, eps, theta, act, fp8):
    b, s, _ = x.shape
    h = nx.rms_norm(x, w["sub0/norm_mixer"], eps)
    q = _rope(nx.dot("bsd,dhk->bshk", h, w["sub0/attn/wq"], fp8), theta)
    k = _rope(nx.dot("bsd,dhk->bshk", h, w["sub0/attn/wk"], fp8), theta)
    v = nx.dot("bsd,dhk->bshk", h, w["sub0/attn/wv"], fp8)
    nh, nkv, hd = q.shape[2], k.shape[2], q.shape[3]
    k = jnp.repeat(k, nh // nkv, axis=2)     # head i reads kv head i // g
    v = jnp.repeat(v, nh // nkv, axis=2)
    sc = jnp.einsum("bshk,bthk->bhst", q, k, precision=nx.HIGHEST) \
        / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", p, v, precision=nx.HIGHEST)
    x = x + nx.dot("bshk,hkd->bsd", o, w["sub0/attn/wo"], fp8)
    h = nx.rms_norm(x, w["sub0/norm_mlp"], eps)
    up = nx.dot("bsd,df->bsf", h, w["sub0/mlp/w_up"], fp8)
    if act == "silu":
        mid = jax.nn.silu(nx.dot("bsd,df->bsf", h, w["sub0/mlp/w_gate"],
                                 fp8)) * up
    else:
        mid = jax.nn.gelu(up, approximate=True)
    return x + nx.dot("bsf,fd->bsd", mid, w["sub0/mlp/w_down"], fp8)


@functools.partial(jax.jit, static_argnames=("eps", "last", "tied", "fp8"))
def _head(e, final_norm_scale, x, *, eps, last, tied, fp8):
    x = nx.rms_norm(x[:, -last:], final_norm_scale, eps)
    if tied:
        return nx.dot("bsd,vd->bsv", x, e["embedding"], fp8)
    return nx.dot("bsd,dv->bsv", x, e["lm_head"], fp8)


def logits(doc: Dict, level: int, blocks: List[jax.Array], last: int,
           fp8: bool = False) -> List[jax.Array]:
    """Logits at the last ``last`` positions of each block of token rows
    (B, S), run layer by layer over all blocks."""
    lw = weights(doc, level)
    e = lw.embed()
    xs = [e["embedding"][t] for t in blocks]
    kw = dict(eps=doc["rms_norm_eps"], theta=doc["rope_theta"],
              act=doc["hidden_act"], fp8=fp8)
    for i in range(lw.n_layers):
        w = lw.layer(i)
        xs = [_layer(w, x, **kw) for x in xs]
        del w
    ones = jnp.ones((doc["hidden_size"],), jnp.float32)
    return [_head(e, ones, x, eps=doc["rms_norm_eps"], last=last,
                  tied=doc["tie_word_embeddings"], fp8=fp8) for x in xs]
