"""The five scheduling policies on the ClusterState -> Plan protocol.

Paper §III-C (Algorithm 1) + the comparison baselines (§II-A, §IV-B):

  * ``uniform``       — equal split, no approximation           [10]
  * ``uniform_apx``   — equal split, per-node approximation to reach the
                        per-node share of perf_req               [5]
  * ``asymmetric``    — capability-proportional split, no approx [3]
  * ``proportional``  — THE PAPER: prune levels, per-node targets
                        proportional to capability, subset-sum DP picks the
                        closest table entries, minimum approximation
  * ``exact_oracle``  — beyond-paper: exact enumeration maximising achieved
                        accuracy subject to sum(perf) >= perf_req; used to
                        measure Algorithm 1's optimality gap. Beyond
                        ``max_enum_nodes`` it tries dominated-level pruning
                        first and falls back to the paper heuristic only
                        when even the pruned grid exceeds its combo budget
                        (and says so in ``Plan.meta['fallback']``).

All policies consume only the immutable ClusterState snapshot — they are
platform-agnostic, exactly as in the paper, and can never mutate the live
ProfilingTable through a side channel.

Performance: this module is the per-request hot path (DistrEdge's point
that the distribution step must be cheap enough to run per request), so
the planners are vectorized and their level selections (the DP, the
oracle's enumeration) memoized against the snapshot's ``plan_key``; plan
assembly (:func:`_mk_plan`) runs on every call. See the module docstring
of :mod:`repro.sched.reference` (the retained pre-optimization
implementation these are proven bit-identical to) and
repro/sched/README.md §Performance.
"""
from __future__ import annotations

import dataclasses
import heapq
import types
from typing import Dict, Mapping, Optional, Tuple

import numpy as np

from repro.core.requests import Assignment, Dispatch, InferenceRequest
from repro.sched import reference
from repro.sched.plan import Plan
from repro.sched.policy import register_policy
from repro.sched.split import quantized_batch_split
from repro.sched.state import ClusterState


def _avail(state: ClusterState) -> np.ndarray:
    idx = state.avail_idx
    if len(idx) == 0:
        raise RuntimeError("no available nodes")
    return idx


def _mk_plan(state: ClusterState, request: InferenceRequest,
             avail_idx: np.ndarray, levels: np.ndarray, policy: str,
             shares: Optional[np.ndarray] = None,
             meta: Optional[Mapping[str, object]] = None) -> Plan:
    """Build a Plan from per-node levels: workload split proportional to
    the selected per-node throughput (Algorithm 1 lines 15-16), plus the
    predicted per-node finish times / makespan the gate decides on.

    Batch-aware pricing: when the snapshot carries a batch cap above 1,
    throughputs come from the batch curve at the cap (``eff_perf``) and
    per-node service times use the same engine-batch decomposition the
    node runtime realizes (``ClusterState.service_s``), so gate and
    queues agree on the timings batching will actually achieve; the
    assumed batch is recorded in ``Plan.meta``. With batching off this
    is byte-for-byte the pre-batching assembly."""
    batched = state.batched
    perfs = (state.eff_perf if batched else state.perf)[levels, avail_idx]
    perf_sum = perfs.sum()
    if shares is None:
        shares = (perfs / perf_sum if perf_sum > 0
                  else np.ones_like(perfs) / len(perfs))
    num_items = request.num_items
    if batched:
        # engine-batch-quantized split: multiples of max_batch per node,
        # one greedily-placed tail chunk (see repro.sched.split) — a
        # non-quantized split would pay a weight-streaming partial batch
        # on every node
        item_l = quantized_batch_split(state, avail_idx, levels, shares,
                                       num_items)
    else:
        # per-element double multiply + floor: same IEEE ops as the
        # reference's np.floor(num_items * shares) — plain-python loops
        # beat ufunc dispatch at these widths
        item_l = [int(num_items * s // 1) for s in shares.tolist()]
        # distribute the remainder to the fastest nodes; kind="stable" so
        # equal-perf nodes receive it in index order on every platform
        rem = num_items - sum(item_l)
        if rem > 0:
            order = np.argsort(-perfs, kind="stable").tolist()
            n_avail = len(order)
            for i in range(rem):
                item_l[order[i % n_avail]] += 1

    # one fused pass over plain-python values (ndarray scalar indexing per
    # node costs more than the whole loop); float results are identical to
    # the reference's per-field loops — same ops, same order
    names = state.names
    backlog = state.backlog_s
    now = state.now_s
    level_l = levels.tolist()
    perf_l = perfs.tolist()
    acc_l = state.accuracies.tolist()
    assignments = []
    service: dict = {}
    finish: dict = {}
    total_acc = 0.0
    for j, col in enumerate(avail_idx.tolist()):
        it, lv, pf, node = item_l[j], level_l[j], perf_l[j], names[col]
        assignments.append(Assignment(node=node, items=it,
                                      apx_level=lv, perf_alloc=pf))
        total_acc += it * acc_l[lv]
        if it == 0:
            continue                    # empty shares are never enqueued
        if batched:
            t = state.service_s(it, lv, col)
        else:
            t = it / max(pf, 1e-9)
        service[node] = t
        finish[node] = now + backlog.get(node, 0.0) + t
    assignments = tuple(assignments)
    if batched:
        meta = dict(meta or {})
        meta["assumed_batch"] = state.max_batch
    dispatch = Dispatch(request=request, assignments=assignments,
                        policy=policy)
    exec_makespan = max(service.values(), default=0.0)
    finish_s = max(finish.values(), default=now)
    return Plan(
        dispatch=dispatch, policy=policy, created_s=now,
        node_service_s=types.MappingProxyType(service),
        node_finish_s=types.MappingProxyType(finish),
        exec_makespan_s=exec_makespan,
        makespan_s=finish_s - now, finish_s=finish_s,
        alloc_perf=float(perf_sum),
        predicted_acc=total_acc / max(request.num_items, 1),
        feasible=bool(perf_sum >= request.perf_req * (1 - 1e-9)),
        meta=types.MappingProxyType(dict(meta or {})))


# ----------------------------------------------------------------------
@register_policy("uniform")
@dataclasses.dataclass(frozen=True)
class Uniform:
    """MoDNN-style equal split at full accuracy."""
    name: str = "uniform"

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        idx = _avail(state)
        levels = np.zeros(len(idx), dtype=int)
        shares = np.ones(len(idx)) / len(idx)
        return _mk_plan(state, request, idx, levels, self.name, shares)


@register_policy("uniform_apx")
@dataclasses.dataclass(frozen=True)
class UniformApx:
    """Equal split; each node approximates until its share of perf_req is
    met (aggressive — the paper's accuracy-violating baseline)."""
    name: str = "uniform_apx"
    margin: float = 0.02

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        idx = _avail(state)
        n = len(idx)
        per_node = (request.perf_req / n) * (
            1.0 + self.margin + n / max(request.num_items, 1))
        # first (least-approximate) level meeting the per-node share; the
        # deepest level when none does
        hit = state.available_eff_perf >= per_node        # (levels, n)
        levels = np.where(hit.any(axis=0), hit.argmax(axis=0),
                          state.num_levels - 1)
        shares = np.ones(n) / n
        return _mk_plan(state, request, idx, levels, self.name, shares)


@register_policy("asymmetric")
@dataclasses.dataclass(frozen=True)
class Asymmetric:
    """Legion-style capability-proportional split, no approximation."""
    name: str = "asymmetric"

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        idx = _avail(state)
        caps = (state.eff_perf if state.batched
                else state.perf)[0, idx]
        shares = caps / caps.sum()
        levels = np.zeros(len(idx), dtype=int)
        return _mk_plan(state, request, idx, levels, self.name, shares)


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class PlanSelection:
    """``Proportional.select``'s outcome: the available node columns, the
    level each serves at, and the DP memo line it came from (``key``;
    None when the snapshot has no ``plan_key``). Selection stays a method
    of its own so a test can substitute it:
    ``benchmarks/chip/tests/test_compare.py`` plans one level deeper than
    the DP chose and checks the accuracy guarantee catches it."""
    idx: np.ndarray
    levels: np.ndarray
    key: Optional[tuple] = None


@register_policy("proportional")
@dataclasses.dataclass(frozen=True)
class Proportional:
    """Algorithm 1 (faithful).

    Lines 3-5: prune disconnected boards.
    Lines 6-9: find the first (least-approximate) level index whose cluster
               throughput meets perf_req.
    Lines 10-11: delete deeper approximation rows.
    Lines 12-13: per-board targets proportional to row-0 capability.
    Line 14:   subset-sum style DP — start every board at the deepest
               remaining row and back-propagate row-by-row toward less
               approximation while the cluster still meets perf_req,
               preferring moves that keep each board closest to its target.
    Lines 15-16: split items proportional to the selected throughputs.

    The DP result is memoized on ``(plan_key, target)``: the level
    vector depends on the request only through the margin-adjusted
    throughput target, so steady-state traffic (recurring request
    classes against an unchanged cluster) skips the DP and pays only the
    O(n) plan assembly. Snapshots without a ``plan_key`` (hand-built
    ``from_table`` states) always run the DP.
    """
    name: str = "proportional"
    margin: float = 0.02
    _dp_cache: Dict = dataclasses.field(default_factory=dict,
                                        repr=False, compare=False)

    _DP_CACHE_MAX = 4096

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        sel = self.select(state, request)
        return _mk_plan(state, request, sel.idx, sel.levels, self.name)

    def select(self, state: ClusterState,
               request: InferenceRequest) -> PlanSelection:
        """Lines 3-14: the available nodes and their levels."""
        idx = _avail(state)
        n = len(idx)
        # headroom over perf_req: integer workload splits quantise the
        # makespan by O(n/items), so small batches need more margin
        target = request.perf_req * (
            1.0 + self.margin + n / max(request.num_items, 1))

        key = None
        pk = state.plan_key
        if pk is not None:
            key = (pk, target)
            levels = self._dp_cache.get(key)
            if levels is not None:
                return PlanSelection(idx=idx, levels=levels, key=key)

        pruned = state.available_eff_perf              # lines 3-5
        perf_vector = pruned.sum(axis=1)               # lines 6-7
        meets = np.flatnonzero(perf_vector >= target)  # line 8
        cutoff = int(meets[0]) if meets.size else state.num_levels - 1
        pruned = pruned[:cutoff + 1]                   # lines 10-11

        perf_b_req = target * pruned[0] / perf_vector[0]   # lines 12-13

        levels = _subset_sum_dp(pruned, perf_b_req, target)  # line 14
        if key is not None:
            if len(self._dp_cache) >= self._DP_CACHE_MAX:
                self._dp_cache.clear()
            levels.flags.writeable = False
            self._dp_cache[key] = levels
        return PlanSelection(idx=idx, levels=levels, key=key)



def _subset_sum_dp(pruned: np.ndarray, perf_b_req: np.ndarray,
                   perf_req: float) -> np.ndarray:
    """The paper's DP_alg, restructured around a priority queue.

    Reference semantics (``reference.subset_sum_dp_ref``): start at the
    deepest remaining row and repeatedly lift the candidate board that is
    first in stable (key, board) order — key = lift loss minus slack over
    the per-board target — whenever the cluster total stays >= perf_req.

    For a monotone ladder (deeper approximation never slower, the shape
    every profiling table here has) that rebuild-and-sort loop collapses
    to one heap walk: every lift loss is >= 0 so the cluster total only
    decreases, meaning a candidate that once failed the feasibility check
    can never pass it later (drop it for good), and a board's key only
    grows as it lifts (push its next step and the heap order stays
    correct). Identical output, O(lifts * log n) instead of
    O(rounds * n log n) — pinned against the reference by the seeded
    property test. Non-monotone tables (a lift that *gains* throughput
    breaks both invariants) take the reference path.

    The candidate re-checks get the enumeration-tensor treatment: every
    lift's loss and heap key is precomputed in two vectorized array
    expressions (same IEEE ops, same order as the per-iteration scalar
    reads they replace — bit-identical keys, so the pop order cannot
    move), and the dead-candidate drain carries an early cutoff — once
    ``total`` drops below what even the globally cheapest lift needs,
    every remaining heap entry is dead, so the walk stops instead of
    popping and re-checking each one.
    """
    m, n = pruned.shape
    levels = np.full(n, m - 1, dtype=int)
    total = pruned[m - 1].sum()
    if total < perf_req or m == 1:
        # infeasible even at the deepest remaining approximation:
        # best-effort max-throughput (no lifting)
        return levels
    if not np.all(pruned[1:] >= pruned[:-1]):
        return reference.subset_sum_dp_ref(pruned, perf_b_req, perf_req)

    # all candidate lifts at once: lifting node j from level l to l-1
    # loses loss_all[l-1][j] throughput and re-enters the heap keyed
    # key_all[l-1][j] (lift loss minus slack over the per-board target)
    loss_np = pruned[1:] - pruned[:-1]                    # (m-1, n)
    key_np = loss_np - (pruned[1:] - perf_b_req[None, :])
    min_loss = float(loss_np.min())
    loss_all = loss_np.tolist()
    key_all = key_np.tolist()
    heap = list(zip(key_all[m - 2], range(n), loss_all[m - 2]))
    heapq.heapify(heap)
    lvl = levels.tolist()               # scalar ndarray writes are slow
    while heap:
        _, j, loss = heapq.heappop(heap)
        if total - loss < perf_req:
            # total never grows: this candidate is dead forever — and
            # once even the cheapest lift anywhere cannot fit, so is
            # every other entry still in the heap
            if total - min_loss < perf_req:
                break
            continue
        lvl[j] -= 1
        total -= loss
        l = lvl[j]
        if l > 0:
            # detlint: ok[DET003] DP loss heap, not an event queue: slot 1 is the unique node index j, so ties are impossible
            heapq.heappush(heap, (key_all[l - 1][j], j,
                                  loss_all[l - 1][j]))
    return np.array(lvl, dtype=int)


def _first_at_least(values: np.ndarray, thresh: float,
                    chunk: int = 4096) -> int:
    """Index of the first entry ``>= thresh`` in ``values`` (-1 when
    none): one masked comparison + reduction per chunk, with the early
    running-best cutoff — the caller orders ``values`` so the first hit
    is already the global best, so the scan stops at the first chunk
    containing one instead of masking all O(m^n) entries."""
    n = len(values)
    for start in range(0, n, chunk):
        hit = values[start:start + chunk] >= thresh
        if hit.any():
            return start + int(hit.argmax())
    return -1


# ----------------------------------------------------------------------
@register_policy("exact_oracle")
@dataclasses.dataclass(frozen=True)
class ExactOracle:
    """Beyond-paper ORACLE: exact search over every (node -> level)
    assignment maximising achieved accuracy

        acc(L) = sum_i p_i(L) * acc(l_i) / sum_i p_i(L)

    subject to sum_i p_i(L) >= perf_req (best-effort max-perf when
    infeasible). Vectorised enumeration, O(m^n) — exact up to
    ``max_enum_nodes`` nodes (6^7 ~ 280k combos). Beyond that it prunes
    *dominated* levels first — level l is useless for node j when a
    less-approximate level has the identical throughput (saturated
    ladder rows), so substituting changes nothing but accuracy, upward —
    and still enumerates exactly when the pruned grid fits
    ``max_enum_combos`` (``Plan.meta['enum'] = 'dominated_pruned'``).
    Only past that budget does it fall back to the paper heuristic,
    recording
    ``Plan.meta['fallback'] = 'proportional'`` so optimality-gap numbers
    can't silently include heuristic rows (EXPERIMENTS.md §Perf).

    The enumeration tensors (combos, per-combo totals and weighted
    accuracies) depend only on the profiling view, so they are cached on
    ``ClusterState.plan_key`` — per plan, only the feasibility check and
    the arg-max selection run. That per-plan residue is fused: the cache
    also holds a *quality order* (``np.lexsort`` by weighted accuracy
    desc, total throughput desc, combo index asc — exactly the old
    mask → argmax tie-break chain) and the totals gathered into that
    order, so feasibility + argmax collapse to one chunked masked
    reduction over the ordered totals with an early running-best cutoff:
    the first entry meeting the throughput threshold *is* the optimum
    (everything before it is infeasible, everything after it is no
    better), so the scan stops at the first hit instead of touching all
    O(m^n) combos. The infeasible fallback (``argmax(total)``) is
    precomputed at cache-build time, making that path O(1) per plan.
    """
    name: str = "exact_oracle"
    max_enum_nodes: int = 7
    max_enum_combos: int = 6 ** 7
    _enum_cache: Dict = dataclasses.field(default_factory=dict,
                                          repr=False, compare=False)
    # one shared fallback planner, so heuristic plans on large fleets
    # reuse its DP memo instead of re-solving per request
    _fallback: Proportional = dataclasses.field(
        default_factory=Proportional, repr=False, compare=False)

    _ENUM_CACHE_MAX = 4          # entries are MB-scale tensors

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        idx = _avail(state)
        pruned = state.available_eff_perf
        acc = state.accuracies
        m, n = pruned.shape
        meta: Optional[Dict[str, object]] = None
        if n <= self.max_enum_nodes:
            cands = [np.arange(m)] * n
        else:
            cands = _non_dominated_levels(pruned)
            budget = self.max_enum_combos
            for c in cands:
                budget //= len(c)
            if budget == 0:             # prod(len(c)) > max_enum_combos
                fb = self._fallback.plan(state, request)
                return dataclasses.replace(
                    fb,
                    dispatch=Dispatch(request=fb.dispatch.request,
                                      assignments=fb.dispatch.assignments,
                                      policy=self.name),
                    policy=self.name,
                    meta=types.MappingProxyType(
                        {"fallback": "proportional",
                         "reason": f"n={n} > max_enum_nodes="
                                   f"{self.max_enum_nodes} and pruned grid"
                                   f" > max_enum_combos="
                                   f"{self.max_enum_combos}"}))
            meta = {"enum": "dominated_pruned", "n": n}

        combos, total_q, order, argmax_total = self._enumerate(
            state, pruned, acc, cands)
        # fused feasibility + weighted-accuracy argmax: the first combo
        # in quality order whose total meets the threshold is the
        # optimum (see the class docstring); infeasible grids take the
        # precomputed best-effort max-throughput combo
        pos = _first_at_least(total_q, request.perf_req * 1.02)
        best = int(order[pos]) if pos >= 0 else argmax_total
        levels = combos[best].astype(int)
        return _mk_plan(state, request, idx, levels, self.name, meta=meta)

    def _enumerate(self, state: ClusterState, pruned: np.ndarray,
                   acc: np.ndarray, cands) -> Tuple[np.ndarray, ...]:
        """(combos, totals in quality order, quality order, argmax of
        the raw totals), cached per profiling view — request-independent.

        The quality order ranks every combo by the exact tie-break chain
        the plan residue needs — weighted accuracy desc, total
        throughput desc, combo index asc (``np.lexsort`` is stable, so
        equal (wacc, total) pairs keep index order) — turning the
        per-plan selection into a first-hit scan over ``total_q``."""
        key = state.plan_key
        if key is not None:
            hit = self._enum_cache.get(key)
            if hit is not None:
                return hit
        n = pruned.shape[1]
        grids = np.meshgrid(*cands, indexing="ij")
        combos = np.stack([g.reshape(-1) for g in grids], axis=1)
        perfs = pruned[combos, np.arange(n)[None, :]]       # (combos, n)
        total = perfs.sum(axis=1)
        wacc = (perfs * acc[combos]).sum(axis=1) / total
        order = np.lexsort((-total, -wacc))
        total_q = np.ascontiguousarray(total[order])
        out = (combos, total_q, order, int(np.argmax(total)))
        if key is not None:
            if len(self._enum_cache) >= self._ENUM_CACHE_MAX:
                self._enum_cache.clear()
            self._enum_cache[key] = out
        return out


# ----------------------------------------------------------------------
@register_policy("accuracy_edf")
@dataclasses.dataclass(frozen=True)
class AccuracyEDF:
    """Deadline-driven accuracy selection (ROADMAP PR 3 follow-up).

    Earliest-deadline-first in the single-request planning frame: the
    request's ``latency_budget_s`` is the deadline, and the policy walks
    the accuracy ladder from the top (level 0, most accurate) picking
    the FIRST uniform level whose backlog-aware, batch-aware makespan
    still meets the budget — the highest accuracy the deadline can buy,
    with the workload split proportional to that level's per-node
    throughput. When even the deepest approximation misses the budget,
    the deepest-level plan ships as best effort (``Plan.meta['edf']``
    says which case happened; the admission gate will reject it anyway
    if it still misses).

    Unlike ``proportional`` (which targets ``perf_req``), this policy
    prices directly against the *deadline* — the two agree when
    ``perf_req`` implied the budget, and diverge exactly when queue
    backlog or batching changes what the deadline can afford.
    """
    name: str = "accuracy_edf"

    def plan(self, state: ClusterState, request: InferenceRequest) -> Plan:
        idx = _avail(state)
        n = len(idx)
        plan = None
        for m in range(state.num_levels):
            levels = np.full(n, m, dtype=int)
            plan = _mk_plan(state, request, idx, levels, self.name,
                            meta={"edf": "met_budget", "edf_level": m})
            if plan.meets_deadline:
                break
        else:
            # even the deepest ladder level misses: best-effort deepest
            plan = dataclasses.replace(
                plan, meta=types.MappingProxyType(
                    {**plan.meta, "edf": "best_effort"}))
        return plan


def _non_dominated_levels(pruned: np.ndarray) -> list:
    """Per-node candidate levels after dominated-level pruning: drop
    level l for node j when a less-approximate level has the *same*
    throughput (accuracy strictly decreases with depth, so the shallower
    twin is better on one objective and equal on the other — swapping
    never changes feasibility and never lowers the weighted accuracy).

    Equal throughput is required, not merely >=: the oracle maximises a
    perf-*weighted* accuracy ratio, and raising the weight of a
    below-average-accuracy node can lower the ratio even at higher
    per-node accuracy — a strictly-slower deep level can be the true
    optimum, so only exact duplicates are safe to remove."""
    m, n = pruned.shape
    keep = np.ones((m, n), dtype=bool)
    if m > 1:
        # level l duplicates a shallower level iff its throughput equals
        # some earlier row's (throughputs are checked per node)
        for l in range(1, m):
            keep[l] = ~(pruned[:l] == pruned[l]).any(axis=0)
    return [np.flatnonzero(keep[:, j]) for j in range(n)]
