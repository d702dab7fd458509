"""Mixture-of-Experts: a router over every expert, and a dropless grouped
matmul over the experts this layer holds.

A token's top-k is chosen over all ``num_experts`` router outputs. The
layer holds experts ``[0, held)`` (``MoEConfig.held``: all of them, or one
chip's share of an expert-parallel deployment) and computes, for the tokens
routed to them, the held experts' weighted outputs, plus the shared expert.
What absent experts would add is left out. The routed (token, expert)
rows are sorted by expert and run through ``jax.lax.ragged_dot``: no
capacity, so no token is dropped under any routing, and the work is
O(T·k·d_ff·d) like the real thing.

Routing (``MoEConfig.scoring``), logits and scores in float32:
  * ``softmax`` (mixtral, jamba): top-k of the softmax, renormalised;
  * ``sigmoid`` (deepseek-v3, noaux_tc): sigmoid scores plus a correction
    bias choose; each of ``n_group`` groups scores the sum of its top 2,
    the ``topk_group`` best groups are kept, and the top-k is chosen
    within them; the weights are the unbiased scores, renormalised.
Both are then scaled by ``router_scale``.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import ParamSpec, ParamTree


def moe_param_specs(cfg: ModelConfig) -> Dict[str, ParamSpec]:
    m = cfg.moe
    d, f, e = cfg.d_model, m.d_ff_expert, m.held
    # the init divides a leaf's std by sqrt(shape[0]), the expert count of
    # a stack; the scale turns that into sqrt(input width)
    s_in, s_mid = ((math.sqrt(e / d), math.sqrt(e / f))
                   if m.expert_init_by_width else (1.0, 1.0))
    specs = {
        "w_router": ParamSpec((d, m.num_experts), ("d_model", None), scale=0.1),
        "we_gate": ParamSpec((e, d, f), ("experts", "d_model", "expert_ff"),
                             scale=s_in),
        "we_up": ParamSpec((e, d, f), ("experts", "d_model", "expert_ff"),
                           scale=s_in),
        "we_down": ParamSpec((e, f, d), ("experts", "expert_ff", "d_model"),
                             scale=s_mid),
    }
    if m.scoring == "sigmoid":
        specs["router_bias"] = ParamSpec((m.num_experts,), (None,),
                                         init="zeros")
    if m.num_shared_experts:
        fs = f * m.num_shared_experts
        specs.update({
            "ws_gate": ParamSpec((d, fs), ("d_model", "d_ff")),
            "ws_up": ParamSpec((d, fs), ("d_model", "d_ff")),
            "ws_down": ParamSpec((fs, d), ("d_ff", "d_model")),
        })
    return specs


def route(cfg: ModelConfig, p: ParamTree, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """x: (T, d). Returns (gates, idx), each (T, k): the chosen experts of
    every token among all ``num_experts``, and their weights (float32)."""
    m = cfg.moe
    logits = x.astype(jnp.float32) @ p["w_router"].astype(jnp.float32)
    if m.scoring == "softmax":
        gates, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), m.top_k)
    else:
        scores = jax.nn.sigmoid(logits)
        choice = scores + p["router_bias"].astype(jnp.float32)
        t = x.shape[0]
        grouped = choice.reshape(t, m.n_group, m.num_experts // m.n_group)
        group_score = jnp.sum(jax.lax.top_k(grouped, 2)[0], axis=-1)
        _, keep = jax.lax.top_k(group_score, m.topk_group)     # (T, groups)
        kept = jnp.zeros((t, m.n_group), bool).at[
            jnp.arange(t)[:, None], keep].set(True)
        choice = jnp.where(kept[:, :, None], grouped, -jnp.inf)
        _, idx = jax.lax.top_k(choice.reshape(t, m.num_experts), m.top_k)
        gates = jnp.take_along_axis(scores, idx, axis=-1)
    gates = gates / jnp.sum(gates, axis=-1, keepdims=True)
    return gates * m.router_scale, idx


def moe_apply(cfg: ModelConfig, p: ParamTree, x: jax.Array
              ) -> Tuple[jax.Array, jax.Array]:
    """x: (B, S, d) -> (out (B, S, d), counts). ``counts`` (float32, (2,)):
    the rows routed to held experts, and the rows the grouped matmuls were
    given (a static T·min(k, held))."""
    m = cfg.moe
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    held, k = m.held, m.top_k

    gates, idx = route(cfg, p, x2)                               # (T, k)
    mine = idx < held
    # the (token, expert) pairs sorted by expert, the held experts' first; a
    # token has at most min(k, held) of them, so the first T·min(k, held)
    # rows hold every routed row
    rows = t * min(k, held)
    perm = jnp.argsort(jnp.where(mine, idx, held).reshape(t * k))  # stable
    sizes = jnp.sum(jax.nn.one_hot(idx, held, dtype=jnp.int32), axis=(0, 1))

    xs = x2[perm[:rows] // k]
    h = jax.nn.silu(jax.lax.ragged_dot(xs, p["we_gate"].astype(x.dtype), sizes))
    h = h * jax.lax.ragged_dot(xs, p["we_up"].astype(x.dtype), sizes)
    y = jax.lax.ragged_dot(h, p["we_down"].astype(x.dtype), sizes)

    # back to (token, k); a pair routed to an expert held elsewhere adds 0
    got = jnp.take(y, jnp.argsort(perm), axis=0, mode="fill", fill_value=0)
    got = jnp.where(mine.reshape(t * k, 1), got, 0).reshape(t, k, d)
    out = jnp.einsum("tkd,tk->td", got, jnp.where(mine, gates, 0.0),
                     preferred_element_type=jnp.float32)

    if m.num_shared_experts:
        hs = jax.nn.silu(x2 @ p["ws_gate"].astype(x.dtype)) * (
            x2 @ p["ws_up"].astype(x.dtype))
        out = out + hs @ p["ws_down"].astype(x.dtype)
    counts = jnp.stack([jnp.sum(mine), rows]).astype(jnp.float32)
    return out.astype(x.dtype).reshape(b, s, d), counts


def aux_load_balance_loss(cfg: ModelConfig, router_logits: jax.Array) -> jax.Array:
    """Switch-style load-balance aux loss (training)."""
    m = cfg.moe
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    _, idx = jax.lax.top_k(probs, m.top_k)
    e = m.num_experts
    frac_tokens = jnp.mean(
        jax.nn.one_hot(idx, e, dtype=jnp.float32).sum(-2), axis=tuple(range(idx.ndim - 1)))
    frac_probs = jnp.mean(probs, axis=tuple(range(probs.ndim - 1)))
    return e * jnp.sum(frac_tokens * frac_probs)
