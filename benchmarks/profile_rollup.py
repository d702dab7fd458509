"""Per-layer self-time rollup of a cProfile capture.

A raw ``pstats`` dump of a simulator run is a screenful of frames; the
question it usually has to answer is one number per layer: how much of
the event loop's CPU is the *simulator* itself (event queue, handlers,
root merge), how much is *planning* (``repro.sched``), how much the
*controllers* (admission gate, autoscaler, fair share), and how much
the shared *core* (backend pricing, profiling table). This module
digests a profile into exactly that — self-time (tottime) grouped by
the ``repro`` sub-package that owns each frame's file, with everything
outside the repo (numpy, stdlib, the benchmark driver itself) bucketed
as ``other``.

Self-time, not cumulative: cumulative time double-counts callers (the
sim layer *calls* the sched layer on every arrival), so fractions of
cumtime would sum past 1. Self-time fractions partition total CPU
exactly.

``run_sim.py --profile`` prints this rollup after its sweep.
"""
from __future__ import annotations

import os
from typing import Dict, List

# the repro sub-packages that get their own bucket; any other repro
# module (analysis, configs, ...) rolls into "repro-other"
LAYERS = ("sim", "sched", "control", "core")


def _layer_of(path: str) -> str:
    parts = path.replace(os.sep, "/").split("/")
    if "repro" not in parts:
        return "other"
    i = parts.index("repro")
    if i + 1 < len(parts) and parts[i + 1] in LAYERS:
        return parts[i + 1]
    return "repro-other"


def module_rollup(profile, top_n: int = 6) -> dict:
    """Digest a ``cProfile.Profile`` (or anything ``pstats`` accepts)
    into per-layer self-time fractions plus the top self-time frames.

    Returns ``{"total_cpu_s", "self_time_frac": {layer: frac},
    "top_self_time": [{"func", "layer", "tottime_s", "cumtime_s"}]}``
    with fractions over all sampled frames (they sum to ~1.0 up to
    rounding)."""
    import pstats
    st = pstats.Stats(profile)
    total = 0.0
    by_layer: Dict[str, float] = {}
    frames: List[tuple] = []
    for (fn, _line, name), (_cc, _nc, tt, ct, _callers) in st.stats.items():
        layer = _layer_of(fn)
        by_layer[layer] = by_layer.get(layer, 0.0) + tt
        total += tt
        frames.append((tt, ct, f"{os.path.basename(fn)}:{name}", layer))
    frames.sort(reverse=True)
    denom = max(total, 1e-9)
    return {
        "total_cpu_s": round(total, 3),
        "self_time_frac": {layer: round(t / denom, 4)
                           for layer, t in sorted(by_layer.items())},
        "top_self_time": [
            {"func": name, "layer": layer, "tottime_s": round(tt, 3),
             "cumtime_s": round(ct, 3)}
            for tt, ct, name, layer in frames[:top_n]],
    }


def format_rollup(rollup: dict) -> str:
    """One-line human rendering: layers by descending self-time share
    (name as the deterministic tie-break)."""
    parts = ", ".join(
        f"{layer} {frac:.1%}"
        for layer, frac in sorted(rollup["self_time_frac"].items(),
                                  key=lambda kv: (-kv[1], kv[0])))
    return f"{rollup['total_cpu_s']:.2f}s CPU self-time: {parts}"
