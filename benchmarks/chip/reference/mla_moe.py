"""Plain float32 reference of one chip's share of a DeepSeek-V3-style
decoder: multi-head latent attention in its decompressed form (every head's
keys and values expanded from the latent), leading dense SwiGLU layers,
then expert layers with a shared expert and the published routing.

Routing (``scoring_func`` "sigmoid", ``topk_method`` "noaux_tc"), in float32
over all ``router_experts`` router outputs: sigmoid scores plus a
correction bias choose; each of ``n_group`` groups scores the sum of its
two best; the ``topk_group`` best groups are kept; the
``num_experts_per_tok`` best experts within them are chosen; their weights
are the unbiased scores, normalised and times ``routed_scaling_factor``.
The chip holds ``n_routed_experts`` of them, ``[0, n_routed_experts)``:
each held expert runs over every token, times its weight (0 where the
token is not routed to it); what absent experts would add is left out.

Written from the published description; the file's ``reduced`` and
``departures`` say where the served model departs from the published one,
and this follows the served one: rotary positions rotate-half over the 64
rope dimensions, with no YaRN scaling and no softmax factor."""
from __future__ import annotations

import functools
import math
from typing import Dict, List

import jax
import jax.numpy as jnp

from . import numerics as nx
from .weights import Group, LevelWeights

WEIGHT_BYTES = 2        # bf16 weights and latent cache


def served_config(doc: Dict) -> Dict:
    """What the program's model config has to say for this file."""
    return {"d_model": doc["hidden_size"],
            "num_layers": doc["num_hidden_layers"],
            "num_heads": doc["num_attention_heads"],
            "num_kv_heads": doc["num_key_value_heads"],
            "vocab_size": doc["vocab_size"],
            "tie_embeddings": doc["tie_word_embeddings"],
            "mlp_kind": {"silu": "swiglu"}[doc["hidden_act"]],
            "rope_theta": doc["rope_theta"], "norm_eps": doc["rms_norm_eps"],
            "attention_kind": "mla", "pos_kind": "rope",
            "mla.q_lora_rank": doc["q_lora_rank"],
            "mla.kv_lora_rank": doc["kv_lora_rank"],
            "mla.qk_nope_head_dim": doc["qk_nope_head_dim"],
            "mla.qk_rope_head_dim": doc["qk_rope_head_dim"],
            "mla.v_head_dim": doc["v_head_dim"],
            "moe.num_experts": doc["router_experts"],
            "moe.held": doc["n_routed_experts"],
            "moe.num_shared_experts": doc["n_shared_experts"],
            "moe.scoring": doc["scoring_func"],
            "moe.n_group": doc["n_group"],
            "moe.topk_group": doc["topk_group"],
            "moe.router_scale": doc["routed_scaling_factor"],
            "moe.first_moe_layer": doc["first_k_dense_replace"],
            "moe.moe_every": doc["moe_layer_freq"],
            "num_dense_layers": doc["first_k_dense_replace"],
            "mtp_depth": doc["num_nextn_predict_layers"],
            "post_norms": False, "zero_centered_norm": False,
            "final_logit_softcap": 0.0, "frontend_stub": False,
            "dtype": doc["torch_dtype"]}


def served_ladder(doc: Dict, level: int) -> Dict:
    """What the program's config of ladder level ``level`` has to say."""
    lv = doc["ladder"][level]
    return {"num_layers": lv["num_hidden_layers"],
            "d_ff_dense": lv["intermediate_size"],
            "moe.top_k": lv["num_experts_per_tok"],
            "moe.d_ff_expert": lv["moe_intermediate_size"]}


def smoke_sizes(doc: Dict, cfg) -> Dict:
    """The file's sizes for the program's model config ``cfg`` (the CPU
    tests put the program's smoke sizes in the file with it)."""
    m, e = cfg.mla, cfg.moe
    return dict(hidden_size=cfg.d_model, num_hidden_layers=cfg.num_layers,
                num_attention_heads=cfg.num_heads,
                num_key_value_heads=cfg.num_kv_heads,
                vocab_size=cfg.vocab_size, q_lora_rank=m.q_lora_rank,
                kv_lora_rank=m.kv_lora_rank,
                qk_nope_head_dim=m.qk_nope_head_dim,
                qk_rope_head_dim=m.qk_rope_head_dim, v_head_dim=m.v_head_dim,
                router_experts=e.num_experts, n_routed_experts=e.held,
                n_shared_experts=e.num_shared_experts,
                num_experts_per_tok=e.top_k, n_group=e.n_group,
                topk_group=e.topk_group,
                moe_intermediate_size=e.d_ff_expert,
                intermediate_size=cfg.d_ff_dense,
                first_k_dense_replace=cfg.num_dense_layers,
                num_nextn_predict_layers=cfg.mtp_depth)


# ----------------------------------------------------------------------
# weights: the program's groups and leaf names
def _mla_leaves(doc: Dict) -> List:
    d, h = doc["hidden_size"], doc["num_attention_heads"]
    q, kv = doc["q_lora_rank"], doc["kv_lora_rank"]
    nope, rope, v = (doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
                     doc["v_head_dim"])
    return [(("sub0", "attn", "w_dq"), (d, q), "normal"),
            (("sub0", "attn", "q_norm"), (q,), "ones"),
            (("sub0", "attn", "w_uq"), (q, h, nope + rope), "normal"),
            (("sub0", "attn", "w_dkv"), (d, kv + rope), "normal"),
            (("sub0", "attn", "kv_norm"), (kv,), "ones"),
            (("sub0", "attn", "w_uk"), (kv, h, nope), "normal"),
            (("sub0", "attn", "w_uv"), (kv, h, v), "normal"),
            (("sub0", "attn", "wo"), (h, v, d), "normal"),
            (("sub0", "norm_mixer"), (d,), "ones"),
            (("sub0", "norm_mlp"), (d,), "ones")]


def weights(doc: Dict, level: int) -> LevelWeights:
    lv = doc["ladder"][level]
    d, v = doc["hidden_size"], doc["vocab_size"]
    f, fe = lv["intermediate_size"], lv["moe_intermediate_size"]
    held, fs = doc["n_routed_experts"], fe * doc["n_shared_experts"]
    # an expert's matrices draw with std 1/sqrt(their input width): the
    # recipe divides by sqrt(shape[0]), the held count, so scale by it
    in_scale, mid_scale = math.sqrt(held / d), math.sqrt(held / fe)
    dense = _mla_leaves(doc) + [
        (("sub0", "mlp", "w_gate"), (d, f), "normal"),
        (("sub0", "mlp", "w_up"), (d, f), "normal"),
        (("sub0", "mlp", "w_down"), (f, d), "normal")]
    moe = _mla_leaves(doc) + [
        (("sub0", "moe", "w_router"), (d, doc["router_experts"]), "normal",
         0.1),
        (("sub0", "moe", "router_bias"), (doc["router_experts"],), "zeros"),
        (("sub0", "moe", "we_gate"), (held, d, fe), "normal", in_scale),
        (("sub0", "moe", "we_up"), (held, d, fe), "normal", in_scale),
        (("sub0", "moe", "we_down"), (held, fe, d), "normal", mid_scale),
        (("sub0", "moe", "ws_gate"), (d, fs), "normal"),
        (("sub0", "moe", "ws_up"), (d, fs), "normal"),
        (("sub0", "moe", "ws_down"), (fs, d), "normal")]
    k = doc["first_k_dense_replace"]
    return LevelWeights(doc, level, [
        Group("embed", ((("embedding",), (v, d), "normal"),
                        (("lm_head",), (d, v), "normal"))),
        Group("final_norm", (((), (d,), "ones"),)),
        Group("dense_layers", tuple(dense), k),
        Group("layers", tuple(moe), lv["num_hidden_layers"] - k)])


# ----------------------------------------------------------------------
# counts
class Counts:
    """Operations and bytes of one ladder level (``counts.py``'s
    conventions): MLA projections, causal attention (QK over the nope and
    rope dimensions, PV over the value dimension), the dense and shared
    MLPs, the router, and the routed experts' rows expected on this chip
    (T·k·held/E); a decode step streams every weight but the held
    experts', of which it reads those that its B rows are expected to
    touch, held·(1 − (1 − k/E)^B), and the latent cache over the context;
    the head on the last position only."""

    def __init__(self, doc: Dict, level: int):
        lv = doc["ladder"][level]
        d, h = doc["hidden_size"], doc["num_attention_heads"]
        q, kv = doc["q_lora_rank"], doc["kv_lora_rank"]
        nope, rope, v = (doc["qk_nope_head_dim"], doc["qk_rope_head_dim"],
                         doc["v_head_dim"])
        self.h, self.kv, self.rope, self.qk, self.v = h, kv, rope, nope + rope, v
        self.mla = (d * q + q * h * (nope + rope) + d * (kv + rope)
                    + kv * h * (nope + v) + h * v * d)
        self.dense = 3 * d * lv["intermediate_size"]
        self.expert = 3 * d * lv["moe_intermediate_size"]
        self.router_e = doc["router_experts"]
        self.router = d * self.router_e
        self.shared = doc["n_shared_experts"] * self.expert
        self.held, self.k = doc["n_routed_experts"], lv["num_experts_per_tok"]
        self.layers = lv["num_hidden_layers"]
        self.n_dense = doc["first_k_dense_replace"]
        self.n_moe = self.layers - self.n_dense
        self.head = d * doc["vocab_size"]

    def _mlps(self, tokens: float) -> float:
        """Operations of the dense, shared, router and routed MLPs of every
        layer for ``tokens`` tokens."""
        routed = tokens * self.k * self.held / self.router_e
        return (2.0 * tokens * self.n_dense * self.dense
                + self.n_moe * (2.0 * tokens * (self.shared + self.router)
                                + 2.0 * routed * self.expert))

    def prefill_flops(self, batch: int, seq: int) -> float:
        tokens = batch * seq
        pairs = seq * (seq + 1) / 2
        attn = 2.0 * batch * self.h * (self.qk + self.v) * pairs
        return (self.layers * (2.0 * tokens * self.mla + attn)
                + self._mlps(tokens) + 2.0 * batch * self.head)

    def decode_flops(self, batch: int, context: float) -> float:
        # absorbed: scores over the latent and rope key, values in the latent
        attn = 2.0 * batch * self.h * (2 * self.kv + self.rope) * context
        return (self.layers * (2.0 * batch * self.mla + attn)
                + self._mlps(batch) + 2.0 * batch * self.head)

    def decode_bytes(self, batch: int, context: float) -> float:
        touched = self.held * (1.0 - (1.0 - self.k / self.router_e) ** batch)
        weights = (self.layers * self.mla + self.n_dense * self.dense
                   + self.n_moe * (self.shared + self.router
                                   + touched * self.expert) + self.head)
        # read the valid latent and rope key, write the new token's
        cache = batch * (self.kv + self.rope) * (context + 1)
        return WEIGHT_BYTES * (weights + self.layers * cache)


def counts(doc: Dict, level: int) -> Counts:
    return Counts(doc, level)


# ----------------------------------------------------------------------
# forward
def _rope(x, theta):
    """x: (B, S, ..., D), positions 0..S-1, rotate-half over D."""
    s, d = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    shape = (1, s) + (1,) * (x.ndim - 3) + (d // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _swiglu(h, gate, up, down, fp8):
    g = nx.dot("bsd,df->bsf", h, gate, fp8)
    return nx.dot("bsf,fd->bsd", jax.nn.silu(g) * nx.dot(
        "bsd,df->bsf", h, up, fp8), down, fp8)


def _mla(w, x, eps, theta, nope, fp8):
    """Latent attention, decompressed: every head's keys and values are
    expanded from the latent, then causal softmax attention."""
    a = lambda name: w[f"sub0/attn/{name}"]       # noqa: E731
    s = x.shape[1]
    ql = nx.rms_norm(nx.dot("bsd,dq->bsq", x, a("w_dq"), fp8), a("q_norm"),
                     eps)
    q = nx.dot("bsq,qhk->bshk", ql, a("w_uq"), fp8)
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], theta)
    kvr = nx.dot("bsd,dc->bsc", x, a("w_dkv"), fp8)
    r = a("kv_norm").shape[0]
    latent = nx.rms_norm(kvr[..., :r], a("kv_norm"), eps)
    k_rope = _rope(kvr[..., r:], theta)                       # (B,S,rope)
    k_nope = nx.dot("bsc,chk->bshk", latent, a("w_uk"), fp8)
    v = nx.dot("bsc,chk->bshk", latent, a("w_uv"), fp8)
    sc = (jnp.einsum("bshk,bthk->bhst", q_nope, k_nope, precision=nx.HIGHEST)
          + jnp.einsum("bshk,btk->bhst", q_rope, k_rope,
                       precision=nx.HIGHEST)) \
        / jnp.sqrt(jnp.float32(q.shape[-1]))
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    o = jnp.einsum("bhst,bthk->bshk", p, v, precision=nx.HIGHEST)
    return nx.dot("bshk,hkd->bsd", o, a("wo"), fp8)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "nope", "fp8"))
def _dense_layer(w, x, *, eps, theta, nope, fp8):
    x = x + _mla(w, nx.rms_norm(x, w["sub0/norm_mixer"], eps), eps, theta,
                 nope, fp8)
    h = nx.rms_norm(x, w["sub0/norm_mlp"], eps)
    return x + _swiglu(h, w["sub0/mlp/w_gate"], w["sub0/mlp/w_up"],
                       w["sub0/mlp/w_down"], fp8)


def _route(w, h, *, n_group, topk_group, top_k, scale, fp8):
    """(B, S, E) weight of each expert for each token: the published
    selection, 0 where the token is not routed."""
    scores = jax.nn.sigmoid(nx.dot("bsd,de->bse", h, w["sub0/moe/w_router"],
                                   fp8))
    choice = scores + w["sub0/moe/router_bias"]
    b, s, e = choice.shape
    grouped = choice.reshape(b, s, n_group, e // n_group)
    two_best = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)      # (B,S,G)
    kept = two_best >= jnp.sort(two_best, axis=-1)[..., -topk_group, None]
    allowed = jnp.where(kept[..., None], grouped, -jnp.inf).reshape(b, s, e)
    routed = allowed >= jnp.sort(allowed, axis=-1)[..., -top_k, None]
    wts = jnp.where(routed, scores, 0.0)
    return wts / wts.sum(-1, keepdims=True) * scale


@functools.partial(jax.jit, static_argnames=(
    "eps", "theta", "nope", "n_group", "topk_group", "top_k", "scale", "fp8"))
def _moe_layer(w, x, *, eps, theta, nope, n_group, topk_group, top_k, scale,
               fp8):
    x = x + _mla(w, nx.rms_norm(x, w["sub0/norm_mixer"], eps), eps, theta,
                 nope, fp8)
    h = nx.rms_norm(x, w["sub0/norm_mlp"], eps)
    gate = _route(w, h, n_group=n_group, topk_group=topk_group, top_k=top_k,
                  scale=scale, fp8=fp8)
    out = _swiglu(h, w["sub0/moe/ws_gate"], w["sub0/moe/ws_up"],
                  w["sub0/moe/ws_down"], fp8)
    for e in range(w["sub0/moe/we_gate"].shape[0]):       # the held experts
        out = out + gate[..., e:e + 1] * _swiglu(
            h, w["sub0/moe/we_gate"][e], w["sub0/moe/we_up"][e],
            w["sub0/moe/we_down"][e], fp8)
    return x + out


@functools.partial(jax.jit, static_argnames=("eps", "last", "fp8"))
def _head(e, x, *, eps, last, fp8):
    x = nx.rms_norm(x[:, -last:], jnp.ones((x.shape[-1],), jnp.float32), eps)
    return nx.dot("bsd,dv->bsv", x, e["lm_head"], fp8)


def logits(doc: Dict, level: int, blocks: List[jax.Array], last: int,
           fp8: bool = False) -> List[jax.Array]:
    """Logits at the last ``last`` positions of each block of token rows
    (B, S), run layer by layer over all blocks."""
    lw = weights(doc, level)
    lv = doc["ladder"][level]
    e = lw.embed()
    xs = [e["embedding"][t] for t in blocks]
    kw = dict(eps=doc["rms_norm_eps"], theta=float(doc["rope_theta"]),
              nope=doc["qk_nope_head_dim"], fp8=fp8)
    for i in range(doc["first_k_dense_replace"]):
        w = lw.group("dense_layers", i)
        xs = [_dense_layer(w, x, **kw) for x in xs]
        del w
    route = dict(n_group=doc["n_group"], topk_group=doc["topk_group"],
                 top_k=lv["num_experts_per_tok"],
                 scale=doc["routed_scaling_factor"])
    for i in range(lw.n_layers):
        w = lw.layer(i)
        xs = [_moe_layer(w, x, **kw, **route) for x in xs]
        del w
    return [_head(e, x, eps=doc["rms_norm_eps"], last=last, fp8=fp8)
            for x in xs]
