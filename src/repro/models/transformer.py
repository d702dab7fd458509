"""Model assembly: layer plan -> scanned super-blocks -> full model.

Every architecture is expressed as a list of *groups*; each group is a stack
of identical *units* (super-blocks) scanned with ``lax.scan`` over stacked
params, keeping HLO size and compile time bounded at 512 devices:

  * homogeneous archs: one group, unit = 1 layer, n_units = L
  * gemma2: unit = (local layer, global layer), n_units = L/2
  * jamba: unit = 8 layers (attn at idx 3, rest mamba; MoE on odd idx)
  * deepseek: group "dense_layers" (3 units) + group "layers" (58 units)
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.ctx import shard_activation
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import (ParamSpec, axes_from_specs, init_from_specs,
                                 mlp_apply, mlp_param_specs, rms_norm,
                                 shapes_from_specs)


@dataclasses.dataclass(frozen=True)
class SubLayer:
    mixer: str                    # gqa | mla | mamba | rwkv
    is_global: bool = True        # local_global archs: global vs sliding
    mlp: str = "dense"            # dense | moe | none (rwkv: channel-mix)
    d_ff: int = 0                 # dense MLP width for this sublayer


@dataclasses.dataclass(frozen=True)
class Group:
    name: str
    pattern: Tuple[SubLayer, ...]
    n_units: int


def layer_plan(cfg: ModelConfig) -> List[Group]:
    if cfg.attention_kind == "none":          # rwkv6
        return [Group("layers", (SubLayer("rwkv", mlp="none"),), cfg.num_layers)]

    if cfg.hybrid_block_size > 1:             # jamba
        bs = cfg.hybrid_block_size
        assert cfg.num_layers % bs == 0
        pattern = []
        for i in range(bs):
            mixer = "gqa" if i in cfg.attn_layer_idx else "mamba"
            is_moe = cfg.layer_is_moe(i)
            pattern.append(SubLayer(mixer, mlp="moe" if is_moe else "dense",
                                    d_ff=cfg.d_ff))
        return [Group("layers", tuple(pattern), cfg.num_layers // bs)]

    if cfg.attention_kind == "local_global":  # gemma2
        assert cfg.num_layers % 2 == 0
        pattern = (SubLayer("gqa", is_global=False, d_ff=cfg.d_ff),
                   SubLayer("gqa", is_global=True, d_ff=cfg.d_ff))
        return [Group("layers", pattern, cfg.num_layers // 2)]

    mixer = "mla" if cfg.attention_kind == "mla" else "gqa"
    groups: List[Group] = []
    if cfg.num_dense_layers > 0:              # deepseek dense prelude
        groups.append(Group("dense_layers",
                            (SubLayer(mixer, d_ff=cfg.d_ff_dense),),
                            cfg.num_dense_layers))
    rest = cfg.num_layers - cfg.num_dense_layers
    body_is_moe = cfg.moe is not None
    groups.append(Group(
        "layers",
        (SubLayer(mixer, mlp="moe" if body_is_moe else "dense", d_ff=cfg.d_ff),),
        rest))
    return groups


# ----------------------------------------------------------------------
# Param specs
def _norm_spec(cfg) -> ParamSpec:
    init = "zeros" if cfg.zero_centered_norm else "ones"
    return ParamSpec((cfg.d_model,), ("d_model",), init=init)


def sublayer_param_specs(cfg: ModelConfig, sl: SubLayer) -> Dict[str, Any]:
    specs: Dict[str, Any] = {"norm_mixer": _norm_spec(cfg)}
    if cfg.post_norms:
        specs["norm_mixer_post"] = _norm_spec(cfg)
    if sl.mixer == "gqa":
        specs["attn"] = attn.attn_param_specs(cfg)
    elif sl.mixer == "mla":
        specs["attn"] = attn.mla_param_specs(cfg)
    elif sl.mixer == "mamba":
        specs["mamba"] = ssm_mod.mamba_param_specs(cfg)
    elif sl.mixer == "rwkv":
        specs["rwkv"] = ssm_mod.rwkv_param_specs(cfg)
        specs["norm_mlp"] = _norm_spec(cfg)   # channel-mix norm
        return specs
    if sl.mlp == "dense":
        specs["norm_mlp"] = _norm_spec(cfg)
        specs["mlp"] = mlp_param_specs(cfg, sl.d_ff)
        if cfg.post_norms:
            specs["norm_mlp_post"] = _norm_spec(cfg)
    elif sl.mlp == "moe":
        specs["norm_mlp"] = _norm_spec(cfg)
        specs["moe"] = moe_mod.moe_param_specs(cfg)
    return specs


def unit_param_specs(cfg: ModelConfig, group: Group) -> Dict[str, Any]:
    return {f"sub{i}": sublayer_param_specs(cfg, sl)
            for i, sl in enumerate(group.pattern)}


def model_param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    from repro.models.layers import embed_param_specs
    specs: Dict[str, Any] = {"embed": embed_param_specs(cfg),
                             "final_norm": _norm_spec(cfg)}
    for g in layer_plan(cfg):
        specs[g.name] = unit_param_specs(cfg, g)   # stacked n_units at init
    if cfg.mtp_depth > 0:
        specs["mtp"] = {
            "proj": ParamSpec((2 * cfg.d_model, cfg.d_model),
                              ("d_model", "d_model_out")),
            "norm_h": _norm_spec(cfg),
            "norm_e": _norm_spec(cfg),
            "block": sublayer_param_specs(
                cfg, SubLayer("mla" if cfg.attention_kind == "mla" else "gqa",
                              d_ff=cfg.d_ff_dense or cfg.d_ff)),
            "final_norm": _norm_spec(cfg),
        }
    return specs


def init_params(cfg: ModelConfig, rng: jax.Array, dtype=jnp.float32):
    specs = model_param_specs(cfg)
    plan = {g.name: g for g in layer_plan(cfg)}
    out = {}
    rngs = jax.random.split(rng, len(specs))
    for r, (name, sub) in zip(rngs, specs.items()):
        if name in plan:
            n = plan[name].n_units
            init_one = functools.partial(init_from_specs, specs=sub, dtype=dtype)
            out[name] = jax.vmap(lambda rr: init_one(rr))(jax.random.split(r, n))
        else:
            out[name] = init_from_specs(r, sub, dtype)
    return out


def abstract_params(cfg: ModelConfig, dtype=jnp.float32):
    """ShapeDtypeStruct pytree for dry-run lowering (no allocation)."""
    specs = model_param_specs(cfg)
    plan = {g.name: g for g in layer_plan(cfg)}
    out = {}
    for name, sub in specs.items():
        tree = shapes_from_specs(sub, dtype)
        if name in plan:
            n = plan[name].n_units
            tree = jax.tree_util.tree_map(
                lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)
        out[name] = tree
    return out


def param_logical_axes(cfg: ModelConfig):
    """Logical dim names mirroring the param tree (stacked dims get 'layers')."""
    specs = model_param_specs(cfg)
    plan = {g.name: g for g in layer_plan(cfg)}
    out = {}
    for name, sub in specs.items():
        tree = axes_from_specs(sub)
        if name in plan:
            tree = jax.tree_util.tree_map(
                lambda dims: ("layers",) + tuple(dims),
                tree, is_leaf=lambda x: isinstance(x, tuple))
        out[name] = tree
    return out


# ----------------------------------------------------------------------
# Sublayer application
def _norm(cfg, scale, x):
    return rms_norm(x, scale.astype(jnp.float32), cfg.norm_eps,
                    zero_centered=cfg.zero_centered_norm)


def sublayer_apply(cfg: ModelConfig, sl: SubLayer, p, x, positions,
                   cache, lengths, *, mode: str, use_kernels: bool):
    """mode: 'dense' (train, no cache out), 'prefill', 'decode'.
    Returns (x, new_cache, aux): for an expert sublayer ``aux`` is its
    router logits in dense mode and its expert row counts
    (``moe_apply``) in prefill mode, else None."""
    aux = None
    h = _norm(cfg, p["norm_mixer"], x)
    if sl.mixer == "gqa":
        if mode == "decode":
            out, new_cache = attn.gqa_attention_decode(
                cfg, p["attn"], h, cache, lengths, is_global=sl.is_global,
                use_kernel=use_kernels)
        else:
            out, new_cache = attn.gqa_attention_dense(
                cfg, p["attn"], h, positions, is_global=sl.is_global,
                use_kernel=use_kernels)
    elif sl.mixer == "mla":
        if mode == "decode":
            out, new_cache = attn.mla_attention_decode(
                cfg, p["attn"], h, cache, lengths)
        else:
            out, new_cache = attn.mla_attention_dense(cfg, p["attn"], h, positions)
    elif sl.mixer == "mamba":
        state = cache if mode == "decode" else None
        out, new_cache = ssm_mod.mamba_apply_dense(
            cfg, p["mamba"], h, state,
            use_kernel=use_kernels and mode != "decode")
    elif sl.mixer == "rwkv":
        state = cache if mode == "decode" else ssm_mod.init_rwkv_state(
            cfg, x.shape[0], x.dtype)
        out, new_wkv, new_shift = ssm_mod.rwkv_time_mix(
            cfg, p["rwkv"], h, state,
            use_kernel=use_kernels and mode != "decode")
        x = x + out
        h2 = _norm(cfg, p["norm_mlp"], x)
        cm_out, new_shift_c = ssm_mod.rwkv_channel_mix(cfg, p["rwkv"], h2, state)
        x = x + cm_out
        new_cache = ssm_mod.RWKVState(wkv=new_wkv, shift_t=new_shift,
                                      shift_c=new_shift_c)
        return x, new_cache, aux
    else:
        raise ValueError(sl.mixer)

    if cfg.post_norms:
        out = _norm(cfg, p["norm_mixer_post"], out)
    # named for selective remat: policy "save_attn" keeps mixer outputs so
    # the backward never recomputes the (flash) attention forward
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "mixer_out")
    x = x + out
    x = shard_activation(x, ("batch", "seq", None))

    if sl.mlp == "dense":
        h = _norm(cfg, p["norm_mlp"], x)
        out = mlp_apply(cfg, p["mlp"], h)
        if cfg.post_norms:
            out = _norm(cfg, p["norm_mlp_post"], out)
        x = x + out
    elif sl.mlp == "moe":
        h = _norm(cfg, p["norm_mlp"], x)
        out, counts = moe_mod.moe_apply(cfg, p["moe"], h)
        if mode == "dense":  # collect router logits for aux loss
            aux = h.reshape(-1, cfg.d_model) @ p["moe"]["w_router"].astype(h.dtype)
        elif mode == "prefill":
            aux = counts
        x = x + out
    x = shard_activation(x, ("batch", "seq", None))
    return x, new_cache, aux


def init_sublayer_cache(cfg: ModelConfig, sl: SubLayer, batch: int,
                        max_len: int, dtype=jnp.bfloat16):
    if sl.mixer == "gqa":
        return attn.init_kv_cache(cfg, batch, max_len, is_global=sl.is_global,
                                  dtype=dtype)
    if sl.mixer == "mla":
        return attn.init_mla_cache(cfg, batch, max_len, dtype)
    if sl.mixer == "mamba":
        return ssm_mod.init_mamba_state(cfg, batch, dtype)
    if sl.mixer == "rwkv":
        return ssm_mod.init_rwkv_state(cfg, batch, dtype)
    raise ValueError(sl.mixer)


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    """Full-model cache pytree: per group, per sublayer, stacked n_units."""
    out = {}
    for g in layer_plan(cfg):
        unit = {}
        for i, sl in enumerate(g.pattern):
            one = init_sublayer_cache(cfg, sl, batch, max_len, dtype)
            unit[f"sub{i}"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (g.n_units,) + a.shape), one)
        out[g.name] = unit
    return out


def abstract_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=jnp.bfloat16):
    return jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
        jax.eval_shape(lambda: init_cache(cfg, batch, max_len, dtype)))


# ----------------------------------------------------------------------
# Group application (scan over units)
def group_apply(cfg: ModelConfig, group: Group, params_stacked, x, positions,
                caches_stacked, lengths, *, mode: str, use_kernels: bool,
                remat: bool = False, unroll: int | bool = 1,
                remat_policy: str = "nothing"):
    """Returns (x, new_caches_stacked, aux_sum). ``aux_sum``: the
    load-balance loss in dense mode; in prefill mode, for a group with
    expert sublayers, their expert row counts (2,) summed over the group.

    ``unroll``: passed to lax.scan. The dry-run unrolls fully (unroll=True)
    because XLA's cost_analysis counts a while-loop body once regardless of
    trip count — unrolling makes the roofline terms correct and lets XLA
    fuse across layer boundaries. Production training keeps unroll=1 for
    bounded compile time."""

    def unit(carry, scanned):
        x, aux_sum = carry
        p_unit = scanned[0]
        cache_unit = scanned[1] if caches_stacked is not None else {}
        new_caches = {}
        for i, sl in enumerate(group.pattern):
            c_in = cache_unit.get(f"sub{i}") if caches_stacked is not None else None
            x, c_out, aux = sublayer_apply(
                cfg, sl, p_unit[f"sub{i}"], x, positions, c_in, lengths,
                mode=mode, use_kernels=use_kernels)
            if mode != "dense" and c_out is not None:
                new_caches[f"sub{i}"] = c_out
            if aux is not None:
                aux_sum = aux_sum + (moe_mod.aux_load_balance_loss(cfg, aux)
                                     if mode == "dense" else aux)
        return (x, aux_sum), (new_caches if mode != "dense" else 0.0)

    if remat:
        if remat_policy == "save_attn":
            policy = jax.checkpoint_policies.save_only_these_names("mixer_out")
        else:
            policy = jax.checkpoint_policies.nothing_saveable
        unit = jax.checkpoint(unit, policy=policy)

    counted = mode == "prefill" and any(sl.mlp == "moe" for sl in group.pattern)
    aux0 = jnp.zeros((2,), jnp.float32) if counted else jnp.float32(0.0)
    scanned = (params_stacked,) if caches_stacked is None else (
        params_stacked, caches_stacked)
    (x, aux_sum), caches_out = jax.lax.scan(unit, (x, aux0),
                                            scanned, unroll=unroll)
    return x, (caches_out if mode != "dense" else None), aux_sum
