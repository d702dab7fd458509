"""The comparison that decides ``correct``.

Plan checks, over every request of the window (exact):
  plan_mismatch  shares served otherwise than planned: a planned share not
                 served or served twice, a share served at another level or
                 with other items than planned, a batch of another size
                 than min(items, SHARE_ITEMS), a planned total that is not
                 the request's items;
  acc_claim_err  widest gap between the accuracy the gateway reports for a
                 request and the items-weighted accuracy of the levels
                 actually served (the configuration file's ladder);
  acc_shortfall  the most by which the accuracy of the levels served falls
                 short of a request's ``acc_req``, over the requests that
                 some plan can serve at both their ``perf_req`` and their
                 ``acc_req`` (``best_accuracy``): the accuracy guarantee.

Model checks, over a sample of the window's requests drawn from the seed,
against the plain float32 reference of each share's level, run over the
share's prompts and its served tokens:
  logit_err      widest max|program - reference| / max|reference| of a
                 share's prefill logits;
  token_gap      widest gap by which a served token's reference logit lies
                 below the reference's best logit at that position.

The control (``control=True``) puts the reference computed in float8 in
the program's place: its own prefill logits, and at every position the
token it puts first.
"""
from __future__ import annotations

import collections
import functools
import importlib
import itertools
from typing import Dict, List, Sequence

import numpy as np

SAMPLE_REQUESTS = 2


def prompts(rid: int, node_idx: int, n: int, vocab: int,
            prompt_len: int) -> np.ndarray:
    """The prompts the served path draws for one share (the input recipe
    of the share runner: a generator seeded with ``[rid, node]``)."""
    rng = np.random.default_rng([rid, node_idx])
    return rng.integers(0, vocab, (n, prompt_len), dtype=np.int32)


def best_accuracy(perf: np.ndarray, acc: Sequence[float], num_items: int,
                  perf_req: float) -> float:
    """The highest items-weighted accuracy of any plan that meets
    ``perf_req``: every node at one level of the ladder (``acc``), whole
    items, and no node's share longer than ``num_items / perf_req`` seconds
    at its level's throughput ``perf[level, node]`` (the gateway's table,
    items/s). -inf where no plan meets ``perf_req``."""
    levels, nodes = perf.shape
    if levels ** nodes > 1 << 20:
        raise ValueError(f"{levels} levels on {nodes} nodes: too many plans "
                         "to enumerate")
    combos = np.array(list(itertools.product(range(levels), repeat=nodes)))
    t = num_items / perf_req
    caps = np.floor(perf[combos, np.arange(nodes)] * t * (1 + 1e-12))
    a = np.asarray(acc, np.float64)[combos]
    order = np.argsort(-a, axis=1, kind="stable")
    caps = np.take_along_axis(caps, order, 1)
    a = np.take_along_axis(a, order, 1)
    # the most accurate nodes filled first, each up to its capacity
    x = np.clip(num_items - (np.cumsum(caps, 1) - caps), 0, caps)
    ok = caps.sum(1) >= num_items
    if not ok.any():
        return -np.inf
    return float(((x * a).sum(1) / num_items)[ok].max())


def plan_checks(records: Sequence, doc: Dict, perf: np.ndarray,
                share_items: int, decode_steps: int) -> Dict[str, float]:
    """``perf``: the gateway's throughput table, ``[level, node]`` in the
    order of the dispatches' nodes."""
    acc = [lv["accuracy"] for lv in doc["ladder"]]
    vocab = doc["vocab_size"]
    best = functools.lru_cache(maxsize=None)(
        lambda n, p: best_accuracy(np.asarray(perf, np.float64), acc, n, p))
    mismatch = 0
    acc_err, shortfall = 0.0, 0.0
    for rec in records:
        planned = {a.node: a for a in rec.dispatch.assignments if a.items > 0}
        served = collections.Counter(s.node for s in rec.shares)
        mismatch += sum(abs(served[n] - (n in planned))
                        for n in set(served) | set(planned))
        if sum(a.items for a in planned.values()) != rec.spec.num_items:
            mismatch += 1
        for s in rec.shares:
            a = planned.get(s.node)
            n = min(a.items, share_items) if a is not None else -1
            if (a is None or s.level != a.apx_level or s.items != a.items
                    or s.served != n
                    or np.shape(s.tokens) != (n, decode_steps)
                    or np.shape(s.logits) != (n, vocab)):
                mismatch += 1
        items = sum(s.items for s in rec.shares)
        mine = sum(s.items * acc[s.level] for s in rec.shares) / max(items, 1)
        acc_err = max(acc_err, abs(mine - rec.result.achieved_acc))
        spec = rec.spec
        if best(spec.num_items, spec.perf_req) >= spec.acc_req:
            shortfall = max(shortfall, spec.acc_req - mine)
    return {"plan_mismatch": float(mismatch), "acc_claim_err": acc_err,
            "acc_shortfall": shortfall}


def sample(records: Sequence, seed: int, k: int = SAMPLE_REQUESTS) -> List:
    rng = np.random.default_rng(int(seed) % (1 << 63))
    idx = sorted(rng.choice(len(records), size=min(k, len(records)),
                            replace=False))
    return [records[i] for i in idx]


def model_checks(shares: Sequence[Dict], doc: Dict, prompt_len: int,
                 control: bool = False) -> Dict[str, float]:
    """``shares``: dicts with ``level``, ``prompts`` (B, prompt_len),
    ``tokens`` (B, T) served and ``logits`` (B, V) the prefill logits."""
    ref = importlib.import_module(f"reference.{doc['reference']}")
    by_level = collections.defaultdict(list)
    for s in shares:
        by_level[s["level"]].append(s)
    logit_err, gap = 0.0, 0.0
    for level, group in sorted(by_level.items()):
        t = group[0]["tokens"].shape[1]
        blocks = [np.concatenate([s["prompts"], s["tokens"][:, :t - 1]], 1)
                  for s in group]
        refs = [np.asarray(x) for x in ref.logits(doc, level, blocks, last=t)]
        ctls = ([np.asarray(x) for x in ref.logits(doc, level, blocks,
                                                   last=t, fp8=True)]
                if control else [None] * len(group))
        for s, r, c in zip(group, refs, ctls):
            first = c[:, 0] if control else s["logits"]
            logit_err = max(logit_err, float(
                np.abs(first - r[:, 0]).max() / np.abs(r[:, 0]).max()))
            toks = c.argmax(-1) if control else s["tokens"]
            at = np.take_along_axis(r, toks[..., None], -1)[..., 0]
            gap = max(gap, float((r.max(-1) - at).max()))
    return {"logit_err": logit_err, "token_gap": gap}
