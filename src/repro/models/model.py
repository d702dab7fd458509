"""Public model API: forward / loss (train), prefill / decode_step (serve).

For ``frontend_stub`` archs (musicgen, llava-next) the modality frontend is a
stub: callers pass precomputed frame/patch embeddings which are projected and
prepended to the token embeddings; positions cover the concatenated stream.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard_activation
from repro.models import transformer as tfm
from repro.models.layers import (embed_tokens, lm_logits, rms_norm,
                                 sinusoidal_embedding)

# re-exports for convenience
init_params = tfm.init_params
abstract_params = tfm.abstract_params
param_logical_axes = tfm.param_logical_axes
init_cache = tfm.init_cache
abstract_cache = tfm.abstract_cache


def _dtype(cfg: ModelConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def _embed_inputs(cfg: ModelConfig, params, tokens: jax.Array,
                  embeds: Optional[jax.Array]) -> jax.Array:
    dtype = _dtype(cfg)
    x = embed_tokens(cfg, params["embed"], tokens, dtype)
    if cfg.frontend_stub:
        assert embeds is not None, f"{cfg.name} needs stub frontend embeddings"
        fe = embeds.astype(dtype) @ params["embed"]["frontend_proj"].astype(dtype)
        x = jnp.concatenate([fe, x], axis=1)
    if cfg.pos_kind == "sinusoidal":
        pos = jnp.arange(x.shape[1])
        x = x + sinusoidal_embedding(pos, cfg.d_model).astype(dtype)[None]
    return x


def _backbone(cfg: ModelConfig, params, x, positions, caches, lengths, *,
              mode: str, use_kernels: bool, remat: bool = False,
              unroll: int | bool = 1, remat_policy: str = "nothing"):
    new_caches = {}
    aux_total = jnp.float32(0.0)
    for g in tfm.layer_plan(cfg):
        c = caches[g.name] if caches is not None else None
        x, c_out, aux = tfm.group_apply(
            cfg, g, params[g.name], x, positions, c, lengths,
            mode=mode, use_kernels=use_kernels, remat=remat, unroll=unroll,
            remat_policy=remat_policy)
        if c_out is not None:
            new_caches[g.name] = c_out
        aux_total = aux_total + aux
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), cfg.norm_eps,
                 zero_centered=cfg.zero_centered_norm)
    return x, new_caches, aux_total


def forward(cfg: ModelConfig, params, tokens: jax.Array,
            embeds: Optional[jax.Array] = None, *, use_kernels: bool = False,
            remat: bool = False, unroll: int | bool = 1
            ) -> Tuple[jax.Array, jax.Array]:
    """Full-sequence forward. Returns (logits over token positions, aux_loss)."""
    x = _embed_inputs(cfg, params, tokens, embeds)
    x = shard_activation(x, ("batch", "seq", None))
    positions = jnp.arange(x.shape[1])[None, :]
    x, _, aux = _backbone(cfg, params, x, positions, None, None,
                          mode="dense", use_kernels=use_kernels, remat=remat,
                          unroll=unroll)
    if cfg.frontend_stub:   # logits only over the token region
        x = x[:, embeds.shape[1]:]
    logits = lm_logits(cfg, params["embed"], x)
    return shard_activation(logits, ("batch", "seq", "vocab")), aux


def _mtp_loss(cfg: ModelConfig, params, x_final, tokens, targets_mask):
    """DeepSeek MTP: predict token t+2 from (h_t, emb(t+1)) through one extra
    block; returns the auxiliary CE term."""
    p = params["mtp"]
    dtype = x_final.dtype
    emb_next = embed_tokens(cfg, params["embed"], tokens[:, 1:], dtype)
    h = rms_norm(x_final[:, :-1], p["norm_h"].astype(jnp.float32), cfg.norm_eps)
    e = rms_norm(emb_next, p["norm_e"].astype(jnp.float32), cfg.norm_eps)
    merged = jnp.concatenate([h, e], axis=-1) @ p["proj"].astype(dtype)
    positions = jnp.arange(merged.shape[1])[None, :]
    sl = tfm.layer_plan(cfg)[-1].pattern[0]
    sl_dense = tfm.SubLayer(sl.mixer, d_ff=cfg.d_ff_dense or cfg.d_ff)
    merged, _, _ = tfm.sublayer_apply(
        cfg, sl_dense, p["block"], merged, positions, None, None,
        mode="dense", use_kernels=False)
    merged = rms_norm(merged, p["final_norm"].astype(jnp.float32), cfg.norm_eps)
    logits = lm_logits(cfg, params["embed"], merged)      # (B, S-1, V)
    tgt = tokens[:, 2:]                                   # token t+2
    lg = logits[:, :-1]
    ce = _ce(lg, tgt) * targets_mask[:, 2:]
    return jnp.sum(ce) / jnp.maximum(jnp.sum(targets_mask[:, 2:]), 1.0)


def _ce(logits: jax.Array, targets: jax.Array) -> jax.Array:
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return logz - gold


def loss_fn(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            use_kernels: bool = False, remat: bool = False,
            unroll: int | bool = 1, remat_policy: str = "nothing",
            aux_weight: float = 0.01, mtp_weight: float = 0.1) -> Tuple[jax.Array, Dict]:
    """Next-token CE (+ MoE load-balance aux, + MTP aux for deepseek).

    Runs the backbone once and shares the final hidden states between the
    main LM head and the MTP head.
    """
    tokens = batch["tokens"]
    embeds = batch.get("embeds")
    x = _embed_inputs(cfg, params, tokens, embeds)
    x = shard_activation(x, ("batch", "seq", None))
    positions = jnp.arange(x.shape[1])[None, :]
    x, _, aux = _backbone(cfg, params, x, positions, None, None,
                          mode="dense", use_kernels=use_kernels, remat=remat,
                          unroll=unroll, remat_policy=remat_policy)
    if cfg.frontend_stub:
        x = x[:, embeds.shape[1]:]
    logits = lm_logits(cfg, params["embed"], x)
    logits = shard_activation(logits, ("batch", "seq", "vocab"))

    targets = tokens[:, 1:]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones_like(tokens, dtype=jnp.float32)
    ce = _ce(logits[:, :-1], targets) * mask[:, 1:]
    loss = jnp.sum(ce) / jnp.maximum(jnp.sum(mask[:, 1:]), 1.0)
    metrics = {"ce": loss, "aux": aux}
    total = loss + aux_weight * aux
    if cfg.mtp_depth > 0:
        mtp = _mtp_loss(cfg, params, x, tokens, mask)
        metrics["mtp"] = mtp
        total = total + mtp_weight * mtp
    return total, metrics


# ----------------------------------------------------------------------
# Serving paths
def prefill(cfg: ModelConfig, params, tokens: jax.Array,
            embeds: Optional[jax.Array] = None, *, use_kernels: bool = False,
            unroll: int | bool = 1) -> Tuple[jax.Array, ...]:
    """Process the prompt; returns (last-position logits, raw seq-length
    caches), and for a config with experts a third entry: the rows routed
    to held experts and the rows the grouped matmuls were given, summed over
    the expert layers (float32, (2,)). The engine pads the caches into
    max_len decode caches."""
    x = _embed_inputs(cfg, params, tokens, embeds)
    x = shard_activation(x, ("batch", "seq", None))
    positions = jnp.arange(x.shape[1])[None, :]
    caches_in = init_cache(cfg, x.shape[0], max_len=1, dtype=_dtype(cfg))
    x, caches, counts = _backbone(cfg, params, x, positions, caches_in, None,
                                  mode="prefill", use_kernels=use_kernels,
                                  remat=False, unroll=unroll)
    logits = lm_logits(cfg, params["embed"], x[:, -1:])
    if cfg.moe is None:
        return logits[:, 0], caches
    return logits[:, 0], caches, counts


def decode_step(cfg: ModelConfig, params, caches, lengths: jax.Array,
                tokens: jax.Array, *, use_kernels: bool = False,
                unroll: int | bool = 1) -> Tuple[jax.Array, Any, jax.Array]:
    """One decode step. tokens: (B,) new token ids; lengths: (B,) current
    context lengths. Returns (logits (B,V), new caches, lengths+1)."""
    x = embed_tokens(cfg, params["embed"], tokens[:, None], _dtype(cfg))
    if cfg.pos_kind == "sinusoidal":
        x = x + sinusoidal_embedding(lengths[:, None],
                                     cfg.d_model).astype(x.dtype)
    x = shard_activation(x, ("batch", None, None))
    positions = lengths[:, None]
    x, new_caches, _ = _backbone(cfg, params, x, positions, caches, lengths,
                                 mode="decode", use_kernels=use_kernels,
                                 remat=False, unroll=unroll)
    logits = lm_logits(cfg, params["embed"], x)[:, 0]
    return logits, new_caches, lengths + 1
