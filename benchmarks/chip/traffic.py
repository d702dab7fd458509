"""The one generator of request streams; each mix is a data file
``traffic/<name>.json`` that it reads.

A mix fixes a pool of ``pool_size`` requests, drawn once from the file's
``pool_seed``: items from ``items``, ``perf_req`` uniform between
``lo`` and ``hi`` (each a factor times ``full_cap`` or ``max_cap``, the
gateway's level-0 and deepest-level capacities), ``acc_req`` uniform in
``acc_req``. ``--seed`` orders the pool (a fresh permutation on each pass
through it) and sets the request ids, which the served path turns into
prompts. A window serves whole passes, so every seed sends the same work in
another order.

Only closed loops with one request outstanding are generated: the gateway
handles one request at a time.
"""
from __future__ import annotations

import dataclasses
import json
import os
from typing import Dict, Iterator, List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
RID_STRIDE = 1_000_000      # request ids of one seed: seed * RID_STRIDE + i


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    rid: int
    num_items: int
    perf_req: float
    acc_req: float


def load(name: str) -> Dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        spec = json.load(f)
    if spec.get("loop") != "closed" or spec.get("outstanding") != 1:
        raise ValueError(f"traffic {name!r}: only a closed loop with one "
                         "request outstanding is generated")
    return spec


def _bound(term, caps: Dict[str, float]) -> float:
    factor, cap = term
    return float(factor) * caps[cap]


def pool(spec: Dict, full_cap: float, max_cap: float) -> List[Dict]:
    """The mix's fixed pool of request parameters."""
    caps = {"full_cap": float(full_cap), "max_cap": float(max_cap)}
    rng = np.random.default_rng(spec["pool_seed"])
    lo = _bound(spec["perf_req"]["lo"], caps)
    hi = _bound(spec["perf_req"]["hi"], caps)
    a_lo, a_hi = spec["acc_req"]
    out = []
    for _ in range(spec["pool_size"]):
        perf = float(rng.uniform(lo, hi))
        acc = float(rng.uniform(a_lo, a_hi))
        items = int(rng.choice(spec["items"]))
        out.append({"num_items": items, "perf_req": perf, "acc_req": acc})
    return out


def passes(spec: Dict, seed: int, full_cap: float,
           max_cap: float) -> Iterator[List[RequestSpec]]:
    """Endless closed-loop stream for ``seed``, one pass through the pool at
    a time: the pool in a seeded order, reshuffled on every pass."""
    base = pool(spec, full_cap, max_cap)
    seed = int(seed) % (1 << 63)
    rng = np.random.default_rng(seed)
    i = 0
    while True:
        out = []
        for j in rng.permutation(len(base)):
            out.append(RequestSpec(rid=seed * RID_STRIDE + i, **base[j]))
            i += 1
        yield out
