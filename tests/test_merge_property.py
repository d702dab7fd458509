"""Run-draining merge equivalence (property-based) + vectorized-oracle
residue pinning.

PR 9 rebuilt the sharded root's hot loop (indexed head-heap + batched
run-draining) and the oracle/DP per-plan residue; both keep a verbatim
pre-optimization twin (``ShardedSimulator.run_reference``, the
``reference:`` planners), and these tests pin the optimized paths
against the twins on seeded churn/straggler traffic and randomized
profiling grids: the event streams and plans must be *identical*, not
merely close.

The merge/DP properties run under hypothesis when it is installed;
otherwise they fall back to a fixed seeded sweep over the same case
space, so the equivalence guarantee is exercised on every platform
(mirrors the guarded-import pattern of tests/test_property.py without
skipping the whole module).
"""
import dataclasses

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                   # pragma: no cover
    HAVE_HYPOTHESIS = False

from repro.configs import get_config
from repro.core.cluster import synthetic_fleet
from repro.core.profiling import NodeProfile, ProfilingTable
from repro.core.requests import InferenceRequest
from repro.core.variants import VariantPool
from repro.sched import ClusterState, get_policy, resolve_policy
from repro.sched.policies import _first_at_least, _subset_sum_dp
from repro.sched.reference import subset_sum_dp_ref
from repro.sim import ShardedSimulator
from repro.sim.scenarios import node_churn, straggler_storm

POOL = VariantPool(get_config("phi4-mini-3.8b"))
SCENARIOS = {"node-churn": node_churn,
             "straggler-storm": straggler_storm}


# ---- root merge: run-draining vs per-event reference ------------------
def _table_factory(profiles):
    return ProfilingTable(POOL, profiles, seq_len=512)


def _stream(sim, rep):
    """Everything the merge order can influence: every record field the
    golden digests hash, the full log, the event count, and the routing
    decisions (least-backlog routing sees mid-merge outstanding state,
    so a reordered merge shows up here even if records survive)."""
    records = []
    for rec in rep.records:
        records.append((rec.request.rid, rec.arrival_s, rec.dispatch_s,
                        rec.finish_s, rec.done, rec.rejected,
                        rec.redistributed,
                        rec.result.per_node_time if rec.done else None))
    return (records, rep.log, rep.n_events, rep.end_s,
            sorted(sim.routed_cell.items()), sim.rebalances)


def _check_merge_equivalence(seed, scenario_name, rebalance, gated):
    """THE tentpole property: across seeded churn/straggler scenarios at
    cells in {1, 4, 16}, the batched run-draining merge (``run``)
    produces an event stream — record list, log, ``n_events`` — **identical**
    to the per-event reference merge (``run_reference``), with
    rebalance ticks and admission/autoscale control loops in play."""
    profiles = synthetic_fleet(16, seed=seed % 97, num_standby=2)
    table = _table_factory([dataclasses.replace(p) for p in profiles])
    sc = SCENARIOS[scenario_name](table, seed=seed, horizon_s=0.8)
    kw = dict(scenario=sc.name, horizon_s=sc.horizon_s, seed=0,
              autoscale=True,
              admission=gated,
              rebalance_s=0.25 if rebalance else 0.0)
    for cells in (1, 4, 16):
        def sim():
            return ShardedSimulator(
                _table_factory, [dataclasses.replace(p) for p in profiles],
                sc.arrivals, sc.faults, cells=cells, **kw)
        fast, ref = sim(), sim()
        a = _stream(fast, fast.run())
        b = _stream(ref, ref.run_reference())
        assert a == b, f"cells={cells}"


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=10_000),
           scenario=st.sampled_from(sorted(SCENARIOS)),
           rebalance=st.booleans(),
           gated=st.booleans())
    @settings(max_examples=12, deadline=None)
    def test_run_draining_matches_per_event_reference(seed, scenario,
                                                      rebalance, gated):
        _check_merge_equivalence(seed, scenario, rebalance, gated)
else:
    @pytest.mark.parametrize("seed,scenario,rebalance,gated", [
        (11, "node-churn", False, False),
        (3, "node-churn", True, True),
        (4071, "node-churn", True, False),
        (7, "straggler-storm", False, True),
        (1234, "straggler-storm", True, False),
        (88, "straggler-storm", True, True),
    ])
    def test_run_draining_matches_per_event_reference(seed, scenario,
                                                      rebalance, gated):
        _check_merge_equivalence(seed, scenario, rebalance, gated)


def test_run_draining_overflow_diagnostics():
    """MAX_EVENTS overflow raises (not hangs) from the run-draining
    loop, and the message carries n_events, the cell count, and every
    cell's clock — same contract as the reference merge."""
    profiles = synthetic_fleet(8, seed=1)
    table = _table_factory([dataclasses.replace(p) for p in profiles])
    sc = node_churn(table, seed=1, horizon_s=0.5)
    for runner in ("run", "run_reference"):
        sim = ShardedSimulator(
            _table_factory, [dataclasses.replace(p) for p in profiles],
            sc.arrivals, sc.faults, cells=4, scenario=sc.name,
            horizon_s=sc.horizon_s, seed=0)
        sim.MAX_EVENTS = 10
        with pytest.raises(RuntimeError) as ei:
            getattr(sim, runner)()
        msg = str(ei.value)
        assert "MAX_EVENTS=10" in msg and "n_events=" in msg
        assert "cells=4" in msg
        for c in range(4):
            assert f"cell{c}=" in msg


# ---- oracle residue: vectorized first-hit scan vs reference -----------
def _grid_state(measured, avail=None):
    n = measured.shape[1]
    nodes = [NodeProfile(f"n{i}", chips=1,
                         available=(avail[i] if avail is not None
                                    else True))
             for i in range(n)]
    table = ProfilingTable(POOL, nodes, measured=measured)
    return ClusterState.from_table(table)


def _plans_identical(a, b):
    return (a.dispatch.assignments == b.dispatch.assignments
            and a.feasible == b.feasible
            and a.predicted_acc == b.predicted_acc
            and a.alloc_perf == b.alloc_perf
            and dict(a.node_service_s) == dict(b.node_service_s))


def test_oracle_vectorized_residue_matches_reference_enumeration():
    """Randomized grids (monotone and raw ladders, throughput ties,
    partial availability) x request mix spanning trivially-feasible,
    borderline, and infeasible thresholds: the fused quality-order
    first-hit residue must pick the *same* plan as the pre-PR
    mask -> argmax enumeration (the ``reference:`` twin) every time."""
    rng = np.random.default_rng(99)
    fast = get_policy("exact_oracle")
    ref = resolve_policy("reference:exact_oracle")
    m = len(POOL)
    checked = 0
    for trial in range(40):
        n = int(rng.integers(1, 8))
        measured = rng.uniform(20.0, 150.0, size=(m, n))
        if trial % 2:
            measured = np.sort(measured, axis=0)
        if n > 2 and rng.random() < 0.5:
            # exact per-node throughput ties across levels: exercises
            # the lexsort (-wacc, -total, index) tie-break chain
            measured[1] = measured[0]
        avail = [True] * n
        if n > 1 and rng.random() < 0.3:
            avail[int(rng.integers(n))] = False
        state = _grid_state(measured, avail)
        hi = float(measured.max(axis=0)[np.asarray(avail)].sum())
        for frac in (0.0, 0.4, 0.97, 1.5):   # feasible .. infeasible
            req = InferenceRequest(rid=trial, num_items=260,
                                   perf_req=frac * hi, acc_req=0.0)
            a = fast.plan(state, req)
            b = ref.plan(state, req)
            assert _plans_identical(a, b), (trial, frac)
            checked += 1
    assert checked == 160


def test_oracle_pruned_residue_matches_reference():
    """Dominated-pruned enumeration (forced via a tiny max_enum_nodes on
    a grid with duplicate ladder rows) flows through the same cached
    quality-order residue — and must still match the reference's *full*
    enumeration plan."""
    rng = np.random.default_rng(7)
    m = len(POOL)
    measured = np.sort(rng.uniform(20.0, 120.0, (m, 5)), axis=0)
    measured[2] = measured[1]             # level 2 dominated everywhere
    state = _grid_state(measured)
    fast = get_policy("exact_oracle", max_enum_nodes=2)
    ref = resolve_policy("reference:exact_oracle")
    for frac in (0.3, 0.8, 1.4):
        req = InferenceRequest(rid=0, num_items=260,
                               perf_req=float(measured[-1].sum() * frac),
                               acc_req=0.0)
        a = fast.plan(state, req)
        b = ref.plan(state, req)
        if frac <= 1.0:
            assert a.meta.get("enum") == "dominated_pruned"
        assert _plans_identical(a, b), frac


def test_first_at_least_chunked_scan():
    """The fused feasibility scan helper: hits at index 0, inside a
    chunk, exactly on a chunk boundary, in the last partial chunk, and
    the no-hit -1 — with a chunk size small enough to cross."""
    v = np.array([1.0, 3.0, 2.0, 5.0, 4.0, 7.0, 0.5])
    assert _first_at_least(v, 0.0, chunk=3) == 0
    assert _first_at_least(v, 2.5, chunk=3) == 1
    assert _first_at_least(v, 4.5, chunk=3) == 3   # chunk-boundary hit
    assert _first_at_least(v, 6.0, chunk=3) == 5   # last partial chunk
    assert _first_at_least(v, 99.0, chunk=3) == -1
    assert _first_at_least(np.array([]), 1.0) == -1


def _check_dp_equivalence(seed, n, frac):
    """The DP's precomputed lift tables + dead-heap early cutoff return
    bit-identical level vectors to the reference rebuild-and-sort loop
    on random monotone ladders across the feasibility range."""
    rng = np.random.default_rng(seed)
    m = int(rng.integers(2, 7))
    pruned = np.sort(rng.uniform(10.0, 200.0, size=(m, n)), axis=0)
    if n > 1 and rng.random() < 0.5:
        pruned[:, 1] = pruned[:, 0]       # tied columns
    target = frac * float(pruned[m - 1].sum())
    perf_b_req = target * pruned[0] / max(float(pruned[0].sum()), 1e-9)
    a = _subset_sum_dp(pruned, perf_b_req, target)
    b = subset_sum_dp_ref(pruned, perf_b_req, target)
    np.testing.assert_array_equal(a, b)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=10_000),
           n=st.integers(min_value=1, max_value=12),
           frac=st.floats(min_value=0.0, max_value=1.3))
    @settings(max_examples=150, deadline=None)
    def test_subset_sum_dp_vectorized_matches_reference(seed, n, frac):
        _check_dp_equivalence(seed, n, frac)
else:
    def test_subset_sum_dp_vectorized_matches_reference():
        rng = np.random.default_rng(2026)
        for _ in range(150):
            _check_dp_equivalence(int(rng.integers(0, 10_000)),
                                  int(rng.integers(1, 13)),
                                  float(rng.uniform(0.0, 1.3)))
