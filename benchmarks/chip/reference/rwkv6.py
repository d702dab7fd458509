"""Plain float32 reference of the served RWKV6 ("Finch") block: token shift
with a static mix, r/k/v/g projections, data-dependent decay
w = exp(-exp(w0 + tanh(x A) B)), the WKV recurrence with bonus u, a
LayerNorm over the width, the gated output projection, and the channel mix
sigmoid(x Wr) * (relu(x Wk)^2 Wv); RMSNorm before each half and at the end.
The configuration file's ``assumed`` lists where this departs from the
published Finch block (the reference follows the served block)."""
from __future__ import annotations

import functools
from typing import Dict, List

import jax
import jax.numpy as jnp

from . import numerics as nx
from .weights import LevelWeights


def served_config(doc: Dict) -> Dict:
    """What the program's model config has to say for this file."""
    return {"d_model": doc["hidden_size"],
            "num_layers": doc["num_hidden_layers"],
            "d_ff": doc["intermediate_size"], "vocab_size": doc["vocab_size"],
            "tie_embeddings": doc["tie_word_embeddings"],
            "ssm.kind": "rwkv6", "ssm.wkv_head_dim": doc["head_size"],
            "norm_eps": doc["layer_norm_epsilon"], "attention_kind": "none",
            "pos_kind": "none", "zero_centered_norm": False,
            "final_logit_softcap": 0.0, "frontend_stub": False,
            "dtype": doc["torch_dtype"]}


def weights(doc: Dict, level: int) -> LevelWeights:
    d, hs = doc["hidden_size"], doc["head_size"]
    nh = doc["attention_hidden_size"] // hs
    r = doc["time_decay_extra_dim"]
    lv = doc["ladder"][level]
    f = lv["intermediate_size"]
    p = ("sub0", "rwkv")
    layer = [
        (("sub0", "norm_mixer"), (d,), "ones"),
        (("sub0", "norm_mlp"), (d,), "ones"),
        (p + ("mix",), (5, d), "zeros"),
        (p + ("w_r",), (d, d), "normal"),
        (p + ("w_k",), (d, d), "normal"),
        (p + ("w_v",), (d, d), "normal"),
        (p + ("w_g",), (d, d), "normal"),
        (p + ("w_o",), (d, d), "normal"),
        (p + ("decay_w0",), (d,), "zeros"),
        (p + ("decay_a",), (d, r), "normal"),
        (p + ("decay_b",), (r, d), "normal"),
        (p + ("bonus_u",), (nh, hs), "zeros"),
        (p + ("ln_scale",), (d,), "ones"),
        (p + ("ln_bias",), (d,), "zeros"),
        (p + ("cm_mix",), (2, d), "zeros"),
        (p + ("cm_k",), (d, f), "normal"),
        (p + ("cm_v",), (f, d), "normal"),
        (p + ("cm_r",), (d, d), "normal"),
    ]
    embed = [(("embedding",), (doc["vocab_size"], d), "normal")]
    if not doc["tie_word_embeddings"]:
        embed.append((("lm_head",), (d, doc["vocab_size"]), "normal"))
    return LevelWeights(doc, level, embed, layer, lv["num_hidden_layers"])


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], axis=1)


def _wkv(r, k, v, w, u):
    """r, k, v, w: (B, S, H, D); u: (H, D). Sequential in time."""
    def step(s, inp):
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        y = jnp.einsum("bhk,bhkv->bhv", r_t, s + u[None, :, :, None] * kv,
                       precision=nx.HIGHEST)
        return w_t[..., None] * s + kv, y
    b, _, h, d = r.shape
    s0 = jnp.zeros((b, h, d, d), jnp.float32)
    xs = tuple(jnp.moveaxis(t, 1, 0) for t in (r, k, v, w))
    _, ys = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(ys, 0, 1)


@functools.partial(jax.jit, static_argnames=("eps", "ln_eps", "hs", "fp8"))
def _layer(w, x, *, eps, ln_eps, hs, fp8):
    b, s, d = x.shape
    nh = d // hs
    P = "sub0/rwkv/"
    h = nx.rms_norm(x, w["sub0/norm_mixer"], eps)
    prev = _shift(h)
    mix = w[P + "mix"]
    xr, xk, xv, xw, xg = (h + (prev - h) * mix[i] for i in range(5))
    r = nx.dot("bsd,de->bse", xr, w[P + "w_r"], fp8).reshape(b, s, nh, hs)
    k = nx.dot("bsd,de->bse", xk, w[P + "w_k"], fp8).reshape(b, s, nh, hs)
    v = nx.dot("bsd,de->bse", xv, w[P + "w_v"], fp8).reshape(b, s, nh, hs)
    g = jax.nn.silu(nx.dot("bsd,de->bse", xg, w[P + "w_g"], fp8))
    ww = w[P + "decay_w0"] + nx.dot(
        "bsr,rd->bsd", jnp.tanh(nx.dot("bsd,dr->bsr", xw, w[P + "decay_a"],
                                       fp8)), w[P + "decay_b"], fp8)
    decay = jnp.exp(-jnp.exp(ww)).reshape(b, s, nh, hs)
    y = _wkv(r, k, v, decay, w[P + "bonus_u"]).reshape(b, s, d)
    y = nx.layer_norm(y, w[P + "ln_scale"], w[P + "ln_bias"], ln_eps)
    x = x + nx.dot("bsd,de->bse", y * g, w[P + "w_o"], fp8)
    h = nx.rms_norm(x, w["sub0/norm_mlp"], eps)
    prev = _shift(h)
    cm = w[P + "cm_mix"]
    xk = h + (prev - h) * cm[0]
    xr = h + (prev - h) * cm[1]
    kk = jnp.square(jax.nn.relu(nx.dot("bsd,df->bsf", xk, w[P + "cm_k"],
                                       fp8)))
    out = jax.nn.sigmoid(nx.dot("bsd,de->bse", xr, w[P + "cm_r"], fp8)) * \
        nx.dot("bsf,fd->bsd", kk, w[P + "cm_v"], fp8)
    return x + out


@functools.partial(jax.jit, static_argnames=("eps", "last", "fp8"))
def _head(e, x, *, eps, last, fp8):
    x = nx.rms_norm(x[:, -last:], 1.0, eps)
    if "lm_head" in e:
        return nx.dot("bsd,dv->bsv", x, e["lm_head"], fp8)
    return nx.dot("bsd,vd->bsv", x, e["embedding"], fp8)


def logits(doc: Dict, level: int, blocks: List[jax.Array], last: int,
           fp8: bool = False) -> List[jax.Array]:
    lw = weights(doc, level)
    e = lw.embed()
    xs = [e["embedding"][t] for t in blocks]
    eps = doc["layer_norm_epsilon"]
    for i in range(lw.n_layers):
        w = lw.layer(i)
        xs = [_layer(w, x, eps=eps, ln_eps=eps, hs=doc["head_size"], fp8=fp8)
              for x in xs]
        del w
    return [_head(e, x, eps=eps, last=last, fp8=fp8) for x in xs]
