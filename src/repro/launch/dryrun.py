"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this builds the production mesh, installs the matching
sharding rules, lowers the jitted step (train_step / prefill / decode_step)
against ShapeDtypeStruct inputs, compiles, and prints memory/cost analysis
plus the three-term roofline derived from the compiled artifact.

Accounting methods:
  * direct      — lower the full model with the layer scan fully unrolled
                  (XLA cost_analysis counts a while-loop body once, so the
                  scan form undercounts by ~num_layers).
  * extrapolate — (default) compile the SAME step at 2 and 4 scanned units
                  (identical width/sharding, reduced depth, unrolled) and
                  linearly extrapolate every per-unit-linear metric (FLOPs,
                  bytes, collective wire/counts, arg/temp sizes) to the full
                  depth: m(U) = m4 + (m4-m2)/2 * (U-4). Exact for metrics
                  that are affine in unit count — which FLOPs/bytes/
                  collectives are — and ~20x faster to compile at 512
                  devices. Validated against `direct` in
                  tests/test_dryrun_extrapolation.py.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-32b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--json out.json]
"""
# The dry-run needs 512 placeholder devices; jax locks device count on first
# init, so this MUST precede every other import (including repro.*).
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))
# (no `from __future__ import annotations` here — the XLA_FLAGS assignment
# must be the first executable statement in the module.)

import argparse
import functools
import json
import sys
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, ARCH_NAMES, get_config, get_shape
from repro.distributed import sharding as shd
from repro.distributed.ctx import use_sharding_rules
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.roofline import analysis as roofline
from repro.train import train_step as ts


def _replicated(mesh):
    return jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())


def dataclasses_replace_shape(shape, seq_len: int):
    import dataclasses
    return dataclasses.replace(shape, seq_len=seq_len)


def _batch_shardings(rules, batch_specs):
    out = {}
    for k, v in batch_specs.items():
        if k == "tokens":
            dims = ("batch", "seq")[: v.ndim]
        elif k == "embeds":
            dims = ("batch", "seq", None)
        elif k in ("lengths",):
            dims = ("batch",)
        else:
            dims = tuple([None] * v.ndim)
        out[k] = rules.named_sharding(v.shape, dims)
    return out


# ----------------------------------------------------------------------
# depth scaling for the extrapolation method
def _unit_block(cfg) -> int:
    """Layers per scanned unit of the scalable (last) group."""
    if cfg.hybrid_block_size > 1:
        return cfg.hybrid_block_size
    if cfg.attention_kind == "local_global":
        return 2
    return 1


def scalable_units(cfg) -> int:
    return (cfg.num_layers - cfg.num_dense_layers) // _unit_block(cfg)


def reduced_config(cfg, units: int):
    """Same width/sharding, the scalable group reduced to ``units``."""
    return cfg.scaled(num_layers=cfg.num_dense_layers
                      + units * _unit_block(cfg))


# ----------------------------------------------------------------------
def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               mode_override: Optional[str] = None,
               use_kernels: bool = False,
               microbatches: int = 1,
               unroll: bool = True,
               remat_policy: str = "nothing",
               cfg_override=None,
               shape_override=None):
    """Lower + compile one (arch, shape, mesh) cell. Returns
    (lowered, compiled, mesh, rules)."""
    # f32 lowering: XLA-CPU emulates bf16 dots by upconversion, inflating
    # both FLOPs (~4x) and byte counts with artifact converts that a TPU
    # lowering would not have. We lower in f32 (same op graph, honest FLOP
    # counts) and apply a documented bf16-deployment normalisation to the
    # memory/collective roofline terms (see roofline.analyze / EXPERIMENTS).
    cfg = (cfg_override or get_config(arch)).scaled(dtype="float32")
    shape = shape_override or get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.kind == "train":
        mode = "train"
    elif shape.kind == "decode" and shape.global_batch == 1:
        mode = "serve_long"
    else:
        mode = "serve"
    rules = shd.make_rules(mesh, mode_override or mode)

    specs = input_specs(cfg, shape)
    with mesh, use_sharding_rules(rules):
        if shape.kind == "train":
            tcfg = ts.TrainConfig(remat=True, microbatches=microbatches,
                                  use_kernels=use_kernels,
                                  unroll=unroll, remat_policy=remat_policy)
            state = ts.abstract_train_state(cfg, tcfg)
            p_shard = shd.param_shardings(rules, cfg)
            opt_shard = ts.TrainState(
                params=p_shard,
                opt=type(state.opt)(step=_replicated(mesh), mu=p_shard,
                                    nu=p_shard))
            b_shard = _batch_shardings(rules, specs["batch"])
            fn = functools.partial(ts.train_step, cfg, tcfg)
            jitted = jax.jit(fn,
                             in_shardings=(opt_shard, b_shard),
                             out_shardings=(opt_shard, None))
            lowered = jitted.lower(state, specs["batch"])
        elif shape.kind == "prefill":
            params = model_lib.abstract_params(cfg, dtype=jnp.float32)
            p_shard = shd.param_shardings(rules, cfg)
            fn = functools.partial(model_lib.prefill, cfg,
                                   use_kernels=use_kernels, unroll=unroll)
            t_shard = _batch_shardings(rules, specs)
            if "embeds" in specs:
                jitted = jax.jit(lambda p, t, e: fn(p, t, e),
                                 in_shardings=(p_shard, t_shard["tokens"],
                                               t_shard["embeds"]))
                lowered = jitted.lower(params, specs["tokens"],
                                       specs["embeds"])
            else:
                jitted = jax.jit(lambda p, t: fn(p, t),
                                 in_shardings=(p_shard, t_shard["tokens"]))
                lowered = jitted.lower(params, specs["tokens"])
        else:  # decode
            params = model_lib.abstract_params(cfg, dtype=jnp.float32)
            p_shard = shd.param_shardings(rules, cfg)
            c_shard = shd.cache_shardings(rules, cfg, shape.global_batch,
                                          shape.seq_len)
            l_shard = rules.named_sharding((shape.global_batch,), ("batch",))
            t_shard = rules.named_sharding((shape.global_batch,), ("batch",))
            fn = functools.partial(model_lib.decode_step, cfg,
                                   use_kernels=use_kernels, unroll=unroll)
            jitted = jax.jit(
                lambda p, c, l, t: fn(p, c, l, t),
                in_shardings=(p_shard, c_shard, l_shard, t_shard),
                out_shardings=(None, c_shard, l_shard),
                donate_argnums=(1,))   # in-place cache update
            lowered = jitted.lower(params, specs["caches"],
                                   specs["lengths"], specs["tokens"])
        compiled = lowered.compile()
    return lowered, compiled, mesh, rules


def _raw_metrics(compiled) -> Dict[str, Any]:
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = roofline.parse_collectives(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "coll_wire": dict(coll.wire_bytes),
        "coll_counts": dict(coll.counts),
        "arg_bytes": float(getattr(mem, "argument_size_in_bytes", 0) or 0),
        "temp_bytes": float(getattr(mem, "temp_size_in_bytes", 0) or 0),
        "out_bytes": float(getattr(mem, "output_size_in_bytes", 0) or 0),
    }


def _extrapolate(m1: Dict, m2: Dict, k1: int, k2: int, units: int) -> Dict:
    def ext(a, b):
        return b + (b - a) / (k2 - k1) * (units - k2)

    out: Dict[str, Any] = {}
    for key in ("flops", "bytes", "arg_bytes", "temp_bytes", "out_bytes"):
        out[key] = max(ext(m1[key], m2[key]), 0.0)
    out["coll_wire"] = {k: max(ext(m1["coll_wire"][k], m2["coll_wire"][k]), 0.0)
                        for k in m2["coll_wire"]}
    out["coll_counts"] = {
        k: int(round(max(ext(m1["coll_counts"][k], m2["coll_counts"][k]), 0)))
        for k in m2["coll_counts"]}
    return out


K_SMALL, K_BIG = 2, 4


def _depth_extrapolated(arch, shape_name, cfg, multi_pod, shape_override,
                        **kw):
    """Compile at 2 and 4 units and extrapolate to full depth. Returns
    (raw_metrics, rules)."""
    units = scalable_units(cfg)
    if units <= K_BIG:
        _, compiled, _, rules = lower_cell(
            arch, shape_name, multi_pod=multi_pod,
            shape_override=shape_override, **kw)
        return _raw_metrics(compiled), rules
    m = []
    rules = None
    for k in (K_SMALL, K_BIG):
        _, compiled, _, rules = lower_cell(
            arch, shape_name, multi_pod=multi_pod,
            cfg_override=reduced_config(cfg, k),
            shape_override=shape_override, **kw)
        m.append(_raw_metrics(compiled))
    return _extrapolate(m[0], m[1], K_SMALL, K_BIG, units), rules


def _quad_fit(ss, vals, s_target: float) -> float:
    """Exact quadratic through three (S, value) points, evaluated at
    s_target — prefill costs are polynomial (<= deg 2) in sequence length."""
    import numpy as np
    coef = np.polyfit(np.asarray(ss, float), np.asarray(vals, float), 2)
    return float(max(np.polyval(coef, s_target), 0.0))


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True, method: str = "extrapolate",
             **kw) -> Dict[str, Any]:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    n_dev = 512 if multi_pod else 256
    t0 = time.time()
    if method == "extrapolate" and shape.kind == "prefill":
        # depth extrapolation at three sequence lengths + exact quadratic
        # fit over S (attention scores are the only S^2 term)
        s_points = (4096, 6144, 8192) if cfg.frontend_stub else (
            2048, 4096, 8192)
        ms, rules = [], None
        for s in s_points:
            sh = dataclasses_replace_shape(shape, s)
            raw_s, rules = _depth_extrapolated(
                arch, shape_name, cfg, multi_pod, sh, **kw)
            ms.append(raw_s)
        raw = {}
        for key in ("flops", "bytes", "arg_bytes", "temp_bytes", "out_bytes"):
            raw[key] = _quad_fit(s_points, [m[key] for m in ms],
                                 shape.seq_len)
        raw["coll_wire"] = {
            k: _quad_fit(s_points, [m["coll_wire"][k] for m in ms],
                         shape.seq_len) for k in ms[0]["coll_wire"]}
        raw["coll_counts"] = {
            k: int(round(_quad_fit(s_points,
                                   [m["coll_counts"][k] for m in ms],
                                   shape.seq_len)))
            for k in ms[0]["coll_counts"]}
        method_tag = (f"extrapolate({K_SMALL},{K_BIG})x"
                      f"quadS{s_points}->{shape.seq_len}")
    elif method == "extrapolate":
        units = scalable_units(cfg)
        raw, rules = _depth_extrapolated(arch, shape_name, cfg, multi_pod,
                                         None, **kw)
        method_tag = (f"extrapolate({K_SMALL},{K_BIG})->{units}"
                      if units > K_BIG else "direct")
    else:
        _, compiled, mesh, rules = lower_cell(
            arch, shape_name, multi_pod=multi_pod, **kw)
        raw = _raw_metrics(compiled)
        method_tag = "direct"

    # SSM/RWKV time recurrences scan inside each layer — add the analytic
    # correction for the body-counted-once undercount (see roofline module)
    corr = roofline.ssm_scan_correction(cfg, shape.seq_len,
                                        shape.global_batch, n_dev, shape.kind)
    raw["flops"] += corr["flops"]
    raw["bytes"] += corr["bytes"]
    if kw.get("use_kernels"):
        # Pallas attention replaces the einsum path; its interpret-mode grid
        # loops are counted once, so add the kernel's true analytic cost
        fcorr = roofline.flash_attention_correction(
            cfg, shape.seq_len, shape.global_batch, n_dev, shape.kind)
        raw["flops"] += fcorr["flops"]
        raw["bytes"] += fcorr["bytes"]
    compile_s = time.time() - t0

    mf = roofline.model_flops(cfg, shape, n_dev)
    # bf16-deployment normalisation of the f32 lowering (see lower_cell):
    #  serve: weights/caches/activations all bf16 on TPU -> 0.5 both terms
    #  train: f32 master params/moments stay f32, activations deploy bf16
    #         -> 0.65 memory (mixed). Collectives per kind: ZeRO-3 weight
    #         all-gathers deploy bf16 (FSDP mixed-precision: cast before
    #         gather) -> 0.5; gradient all-reduce / reduce-scatter stay f32.
    if shape.kind == "train":
        mem_scale = 0.65
        coll_scales = {"all-gather": 0.5}
        coll_default = 1.0
    else:
        mem_scale = 0.5
        coll_scales = {}
        coll_default = 0.5

    v5e = roofline.chip_peaks(roofline.V5E)
    hbm = raw["bytes"] * mem_scale
    wire = sum(v * coll_scales.get(k, coll_default)
               for k, v in raw["coll_wire"].items())
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "mode": rules.name,
        "method": method_tag,
        "compile_s": round(compile_s, 1),
        "flops_per_dev": raw["flops"],
        "hbm_bytes_per_dev": hbm,
        "collective_wire_bytes": wire,
        "compute_s": raw["flops"] / v5e.flops,
        "memory_s": hbm / v5e.hbm_bw,
        "collective_s": wire / v5e.ici_bw,
        "model_flops_per_dev": mf,
        "collective_counts": raw["coll_counts"],
        "collective_wire_by_kind": raw["coll_wire"],
        "arg_bytes": raw["arg_bytes"],
        "temp_bytes": raw["temp_bytes"],
        "out_bytes": raw["out_bytes"],
    }
    terms = {"compute": rec["compute_s"], "memory": rec["memory_s"],
             "collective": rec["collective_s"]}
    rec["dominant"] = max(terms, key=terms.get)
    rec["useful_flops_ratio"] = mf / raw["flops"] if raw["flops"] else 0.0
    bound = max(terms.values())
    rec["roofline_fraction"] = (mf / v5e.flops) / bound if bound else 0.0

    if verbose:
        print(f"== {arch} x {shape_name} [{rec['mesh']}, {rules.name}, "
              f"{method_tag}] compile={compile_s:.1f}s")
        print(f"   memory_analysis: args={_gb(rec['arg_bytes'])} "
              f"temps={_gb(rec['temp_bytes'])} out={_gb(rec['out_bytes'])}")
        print(f"   cost_analysis: flops/dev={rec['flops_per_dev']:.3e} "
              f"hbm/dev={_gb(rec['hbm_bytes_per_dev'])}")
        print(f"   roofline: compute={rec['compute_s']*1e3:.2f}ms "
              f"memory={rec['memory_s']*1e3:.2f}ms "
              f"collective={rec['collective_s']*1e3:.2f}ms "
              f"-> {rec['dominant']}-bound; "
              f"useful={rec['useful_flops_ratio']:.2f} "
              f"frac={rec['roofline_fraction']:.3f}")
        print(f"   collectives: { {k: v for k, v in rec['collective_counts'].items() if v} }")
    return rec


def _gb(x) -> str:
    return "n/a" if x is None else f"{x/2**30:.2f}GiB"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--use-kernels", action="store_true")
    ap.add_argument("--remat-policy", default="nothing",
                    choices=("nothing", "save_attn"))
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--direct", action="store_true",
                    help="full-depth unrolled lowering (slow, exact)")
    ap.add_argument("--json", help="append records to this JSON-lines file")
    args = ap.parse_args(argv)

    from repro.configs import cells
    if args.all:
        todo = [(a, s) for a, s, skip in cells() if not skip]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cfg = get_config(args.arch)
        if args.shape == "long_500k" and not cfg.sub_quadratic:
            print(f"SKIP {args.arch} x long_500k: pure full-attention arch "
                  "(see DESIGN.md §Arch-applicability)")
            return 0
        todo = [(args.arch, args.shape)]

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    failures = []
    for arch, shape in todo:
        for mp in meshes:
            try:
                rec = run_cell(arch, shape, multi_pod=mp,
                               use_kernels=args.use_kernels,
                               remat_policy=args.remat_policy,
                               microbatches=args.microbatches,
                               method="direct" if args.direct else "extrapolate")
                if args.json:
                    with open(args.json, "a") as f:
                        f.write(json.dumps(rec) + "\n")
            except Exception as e:
                failures.append((arch, shape, mp, repr(e)))
                print(f"FAIL {arch} x {shape} multi_pod={mp}: {e}")
                traceback.print_exc()
    if failures:
        print(f"\n{len(failures)} cell(s) failed:")
        for f in failures:
            print("  ", f)
        return 1
    print("\nAll requested cells lowered + compiled OK.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
