"""Three-term roofline analysis from a compiled dry-run artifact.

  compute term    = HLO_FLOPs / (chips x peak_FLOP/s)
  memory term     = HLO_bytes / (chips x HBM_bw)
  collective term = collective_wire_bytes / (chips x link_bw)

``compiled.cost_analysis()`` is per-partition (the compiled module is the
per-device SPMD program), so chips-normalisation is already folded in; we
verify this convention in tests/test_roofline.py. Collective bytes are not
in cost_analysis — we parse the post-optimization HLO text and sum wire
traffic per collective with ring-algorithm factors.

Hardware peaks come from one table keyed by the ``device_kind`` JAX
reports; a kind that is not in it is an error, never a default. The
dry-run models v5e pods, so it reads the v5e entry by name.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published peaks of one chip."""
    flops: float             # bf16 FLOP/s
    hbm_bw: float            # HBM bytes/s
    ici_bw: float            # chip-to-chip bytes/s per link
    hbm_bytes: float         # HBM capacity


V5E = "TPU v5 lite"

# Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
# HBM at 819 GB/s, 1,600 Gbit/s of interconnect over four links.
PEAKS: Dict[str, ChipPeaks] = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9, hbm_bytes=16e9),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip JAX reports as ``device_kind``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


_POD = chip_peaks(V5E)     # the chip the dry-run's pods are built from

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# result-type like  bf16[2,4096,5120]  (possibly inside a tuple)
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


@dataclasses.dataclass
class CollectiveStats:
    counts: Dict[str, int]
    wire_bytes: Dict[str, float]

    @property
    def total_wire_bytes(self) -> float:
        return sum(self.wire_bytes.values())


def _shape_bytes(type_str: str) -> float:
    """Sum byte sizes of every array shape in an HLO type string."""
    total = 0.0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str) -> int:
    m = _GROUPS_IOTA_RE.search(line)
    if m:  # iota format [num_groups,group_size]
        return max(int(m.group(2)), 1)
    m = _GROUPS_RE.search(line)
    if m:
        return max(len(m.group(1).split(",")), 1)
    return 1


_COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                     "all-to-all", "collective-permute")


def parse_collectives(hlo_text: str) -> CollectiveStats:
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVE_KINDS}
    wire: Dict[str, float] = {k: 0.0 for k in _COLLECTIVE_KINDS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if "=" not in stripped:
            continue
        lhs, rhs = stripped.split("=", 1)
        rhs = rhs.strip()
        kind = None
        for k in _COLLECTIVE_KINDS:
            # match the opcode at the start of the rhs expression,
            # e.g. "bf16[...] all-gather(...)" — and -start/-done forms
            if re.search(rf"\b{k}(-start)?\(", rhs):
                kind = k
                break
        if kind is None:
            continue
        out_bytes = _shape_bytes(rhs.split("(")[0])
        g = _group_size(rhs)
        ring = (g - 1) / g if g > 1 else 1.0
        if kind == "all-reduce":
            traffic = 2.0 * out_bytes * ring
        elif kind == "all-gather":
            traffic = out_bytes * ring
        elif kind == "reduce-scatter":
            traffic = out_bytes * (g - 1 if g > 1 else 1)
        elif kind == "all-to-all":
            traffic = out_bytes * ring
        else:  # collective-permute
            traffic = out_bytes
        counts[kind] += 1
        wire[kind] += traffic
    return CollectiveStats(counts=counts, wire_bytes=wire)


@dataclasses.dataclass
class Roofline:
    flops: float                  # per device
    hbm_bytes: float              # per device
    collective_bytes: float       # per device (wire)
    compute_s: float
    memory_s: float
    collective_s: float
    collectives: CollectiveStats
    model_flops: float = 0.0      # 6*N*D useful flops, per device
    peak_mem_bytes: float = 0.0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def bound_s(self) -> float:
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.flops if self.flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """useful-compute time / bound time — the score we hillclimb."""
        if self.bound_s <= 0:
            return 0.0
        return (self.model_flops / _POD.flops) / self.bound_s


def analyze(compiled, hlo_text: str, *, model_flops_per_device: float = 0.0,
            links_per_chip: float = 1.0,
            mem_scale: float = 1.0, coll_scale: float = 1.0) -> Roofline:
    """mem_scale / coll_scale: bf16-deployment normalisation for f32-lowered
    dry-runs (the CPU backend cannot lower bf16 dots without emulation
    artifacts). Serve cells deploy bf16 weights+caches -> 0.5; train cells
    keep f32 master params / f32 grad reductions -> see dryrun.run_cell."""
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    flops = float(cost.get("flops", 0.0))
    hbm = float(cost.get("bytes accessed", 0.0)) * mem_scale
    coll = parse_collectives(hlo_text)
    wire = coll.total_wire_bytes * coll_scale
    mem = compiled.memory_analysis()
    peak = 0.0
    for attr in ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes"):
        peak += float(getattr(mem, attr, 0.0) or 0.0)
    return Roofline(
        flops=flops,
        hbm_bytes=hbm,
        collective_bytes=wire,
        compute_s=flops / _POD.flops,
        memory_s=hbm / _POD.hbm_bw,
        collective_s=wire / (_POD.ici_bw * links_per_chip),
        collectives=coll,
        model_flops=model_flops_per_device,
        peak_mem_bytes=peak,
    )


def ssm_scan_correction(cfg, seq_len: int, global_batch: int,
                        n_devices: int, kind: str) -> Dict[str, float]:
    """Analytic per-device (flops, bytes) for the SSM/RWKV time recurrences.

    The recurrence is a ``lax.scan`` over time inside each layer; XLA's
    cost_analysis counts the body once, so full-sequence (train/prefill)
    lowerings under-count it by ~seq_len. This adds the analytic cost
    (sharding: batch over the 16-way data axis, channels over the 16-way
    model axis — matching the rule tables). Train multiplies by 4
    (fwd + remat recompute + ~2x bwd). Decode needs no correction."""
    if cfg.ssm is None or kind == "decode":
        return {"flops": 0.0, "bytes": 0.0}
    data_ax, model_ax = 16, 16
    b_dev = max(global_batch // data_ax, 1)
    s = seq_len
    n_ssm_layers = sum(1 for i in range(cfg.num_layers)
                       if not cfg.layer_is_attn(i))
    if n_ssm_layers == 0:
        return {"flops": 0.0, "bytes": 0.0}
    if cfg.ssm.kind == "mamba":
        d_in = cfg.ssm.expand * cfg.d_model // model_ax
        n = cfg.ssm.d_state
        flops_l = s * b_dev * d_in * n * 8.0
        bytes_l = s * b_dev * (16.0 * d_in + 8.0 * n)
    else:  # rwkv6
        hd = cfg.ssm.wkv_head_dim
        nh = max(cfg.d_model // hd // model_ax, 1)
        flops_l = s * b_dev * nh * hd * hd * 5.0
        bytes_l = s * b_dev * 4.0 * (cfg.d_model // model_ax) * 4.0
    mult = 4.0 if kind == "train" else 1.0
    return {"flops": flops_l * n_ssm_layers * mult,
            "bytes": bytes_l * n_ssm_layers * mult}


def flash_attention_correction(cfg, seq_len: int, global_batch: int,
                               n_devices: int, kind: str) -> Dict[str, float]:
    """Analytic per-device (flops, bytes) for Pallas flash-attention cells.

    In kernel mode the attention runs inside a pallas_call; the interpret
    lowering's grid loops are counted once by cost_analysis, so the
    attention cost is added analytically — at the kernel's TRUE cost:
    FLOPs 4*B*S*S_eff*H*D per layer (x0.5 causal, x~3.5 for train
    fwd+recompute+bwd) and HBM bytes at the flash ideal (linear q/k/v/out
    streams only, never S^2 score materialisation; bwd re-streams ~2.5x).

    Sharding matches the shard_map deployment in kernels/ops.py: batch over
    (pod, data); the query grid sequence-shards over model via the kernel's
    q_offset (K/V whole per shard); heads unsharded."""
    if kind == "decode" or cfg.attention_kind in ("none", "mla"):
        return {"flops": 0.0, "bytes": 0.0}
    data_ax, model_ax = 16, 16
    b_dev = max(global_batch // data_ax, 1)
    h_shard = cfg.num_heads
    seq_div = model_ax if seq_len % model_ax == 0 else 1
    s_q = seq_len / seq_div
    d = cfg.head_dim
    flops = 0.0
    bytes_ = 0.0
    for i in range(cfg.num_layers):
        if not cfg.layer_is_attn(i):
            continue
        eff = seq_len
        if cfg.attention_kind == "sliding" or (
                cfg.attention_kind == "local_global"
                and not cfg.layer_is_global_attn(i)):
            eff = min(seq_len, cfg.sliding_window)
        causal = 0.5 if eff == seq_len else 1.0
        flops += 4.0 * b_dev * h_shard * s_q * eff * d * causal
        # linear streams: q,out sharded slices + whole k,v per shard;
        # 4 bytes f32-equivalent (run_cell mem_scale x0.5 lands at bf16)
        bytes_ += 4.0 * b_dev * (2 * h_shard * s_q
                                 + 2 * cfg.num_kv_heads * seq_len) * d
    mult_f = 3.5 if kind == "train" else 1.0
    mult_b = 2.5 if kind == "train" else 1.0
    return {"flops": flops * mult_f, "bytes": bytes_ * mult_b}


def model_flops(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE), per device.

    D = tokens processed by the step: B*S for train/prefill, B for decode.
    Train includes the backward pass (the 6x already covers fwd+bwd;
    prefill/decode use 2*N*D)."""
    n_active = cfg.param_count(active_only=True)
    if shape.kind == "train":
        d = shape.global_batch * shape.seq_len
        mult = 6.0
    elif shape.kind == "prefill":
        d = shape.global_batch * shape.seq_len
        mult = 2.0
    else:
        d = shape.global_batch
        mult = 2.0
    return mult * n_active * d / n_devices
