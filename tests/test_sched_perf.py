"""Fast-control-plane guarantees: the vectorized/memoized planners and
the incremental snapshot/backlog machinery must be *behavior-identical*
to the retained reference implementation — the PR's speedups only count
because every test here pins the serving-visible outputs.

Covers: the seeded plan-equivalence property test (optimized vs
``repro.sched.reference`` across random ClusterStates), stable remainder
tie-breaking, DP-memo hit/invalidation semantics, SnapshotCache
copy-on-write rules, fast-vs-legacy simulator metric identity, the
oracle's dominated-level pruning, the fleet scenarios, and a golden
check that the committed BENCH_3.json serving-metric cells reproduce.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from repro.configs import get_config
from repro.control import AdmissionController
from repro.core.cluster import SimBackend, cluster_nodes, synthetic_fleet
from repro.core.profiling import NodeProfile, ProfilingTable
from repro.core.requests import InferenceRequest
from repro.core.resource_manager import GatewayNode
from repro.core.variants import VariantPool
from repro.sched import (ClusterState, SnapshotCache, get_policy,
                         resolve_policy)
from repro.sim import FLEET_SIZES, OnlineSimulator, build_scenario

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ALL_POLICIES = ("uniform", "uniform_apx", "asymmetric", "proportional",
                "exact_oracle")


@pytest.fixture(scope="module")
def pool():
    return VariantPool(get_config("phi4-mini-3.8b"))


def _measured_table(pool, caps, avail=None):
    caps = np.asarray(caps, dtype=np.float64)
    speed = np.linspace(1.0, 2.1, len(pool))[:, None]
    nodes = [NodeProfile(f"n{i}", chips=1,
                         available=(avail[i] if avail is not None else True))
             for i in range(len(caps))]
    return ProfilingTable(pool, nodes, measured=caps[None, :] * speed)


def _plans_identical(a, b):
    return (a.dispatch.assignments == b.dispatch.assignments
            and a.policy == b.policy
            and a.makespan_s == b.makespan_s
            and a.exec_makespan_s == b.exec_makespan_s
            and a.finish_s == b.finish_s
            and a.predicted_acc == b.predicted_acc
            and a.alloc_perf == b.alloc_perf
            and a.feasible == b.feasible
            and dict(a.node_service_s) == dict(b.node_service_s)
            and dict(a.node_finish_s) == dict(b.node_finish_s))


# ---- plan equivalence -------------------------------------------------
def test_plans_identical_to_reference(pool):
    """Seeded property test: across random ClusterStates (heterogeneous
    caps, perf ties, partial availability, random backlogs) every
    optimized planner returns a Plan identical — assignments, levels,
    predicted makespan/accuracy, per-node finish times — to the retained
    reference implementation."""
    rng = np.random.default_rng(2024)
    checked = 0
    for trial in range(60):
        n = int(rng.integers(1, 14))
        caps = rng.uniform(10.0, 120.0, n)
        if n > 2 and rng.random() < 0.5:      # equal-perf nodes (ties)
            caps[int(rng.integers(n))] = caps[int(rng.integers(n))]
        avail = [True] * n
        if n > 1 and rng.random() < 0.3:
            avail[int(rng.integers(n))] = False
        table = _measured_table(pool, caps, avail)
        backlogs = {f"n{i}": float(rng.uniform(0.0, 0.5))
                    for i in range(n) if rng.random() < 0.5}
        state = ClusterState.from_table(
            table, now=float(rng.uniform(0.0, 10.0)), backlogs=backlogs)
        lo, hi = table.perf[0].sum(), table.perf[-1].sum()
        req = InferenceRequest(
            rid=trial, num_items=int(rng.choice([1, 13, 260, 520, 650])),
            perf_req=float(lo + rng.uniform(0.0, 1.0) * (hi - lo)),
            acc_req=87.0)
        for name in ALL_POLICIES:
            if name == "exact_oracle" and sum(avail) > 6:
                continue                      # full-enum cost; fallback
                #                               equivalence pinned below
            a = get_policy(name).plan(state, req)
            b = resolve_policy(f"reference:{name}").plan(state, req)
            assert _plans_identical(a, b), (name, trial)
            checked += 1
    assert checked >= 200


def test_oracle_fallback_identical_to_reference(pool):
    """Past max_enum_nodes with an unprunable (strictly monotone) table
    both implementations fall back to the proportional heuristic and
    must agree, fallback annotation included."""
    table = _measured_table(pool, [50.0 + 7.0 * i for i in range(11)])
    state = ClusterState.from_table(table)
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(table.perf[0].sum() * 1.2),
                           acc_req=87.0)
    a = get_policy("exact_oracle").plan(state, req)
    b = resolve_policy("reference:exact_oracle").plan(state, req)
    assert a.meta["fallback"] == b.meta["fallback"] == "proportional"
    assert a.dispatch.assignments == b.dispatch.assignments


def test_remainder_tiebreak_stable(pool):
    """Equal-perf nodes receive the workload remainder in index order —
    the platform-independent kind="stable" argsort semantics."""
    table = _measured_table(pool, [50.0, 50.0, 50.0])
    state = ClusterState.from_table(table)
    req = InferenceRequest(rid=0, num_items=100, perf_req=10.0,
                           acc_req=0.0)
    plan = get_policy("uniform").plan(state, req)
    items = [a.items for a in plan.dispatch.assignments]
    # 100 = 3*33 + 1: the single remainder item goes to the FIRST of the
    # equal-perf nodes, never a platform-dependent one
    assert items == [34, 33, 33]


# ---- memoization semantics -------------------------------------------
def test_dp_memo_hits_and_invalidates(pool):
    table = _measured_table(pool, [100.0, 70.0, 40.0])
    cache = SnapshotCache()
    pol = get_policy("proportional")
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(table.perf[0].sum() * 1.3),
                           acc_req=87.0)
    p1 = pol.plan(cache.snapshot(table, now=1.0), req)
    assert len(pol._dp_cache) == 1
    # same request class + unchanged table: a memo hit, identical plan
    p2 = pol.plan(cache.snapshot(table, now=2.0), req)
    assert len(pol._dp_cache) == 1
    assert [a.apx_level for a in p2.dispatch.assignments] == \
           [a.apx_level for a in p1.dispatch.assignments]
    # a cold instance agrees with the cached result
    p_cold = get_policy("proportional").plan(
        cache.snapshot(table, now=2.0), req)
    assert p_cold.dispatch.assignments == p2.dispatch.assignments
    # table mutation bumps the version: new key, freshly planned levels
    table.scale_node(0, 0.25)
    p3 = pol.plan(cache.snapshot(table, now=3.0), req)
    assert len(pol._dp_cache) == 2
    ref = resolve_policy("reference:proportional").plan(
        ClusterState.from_table(table, now=3.0), req)
    assert p3.dispatch.assignments == ref.dispatch.assignments


def test_from_table_snapshots_never_memoize(pool):
    """Hand-built snapshots carry no plan_key, so planning stays cold —
    a stale cache line can never be aliased."""
    table = _measured_table(pool, [100.0, 70.0])
    state = ClusterState.from_table(table)
    assert state.plan_key is None
    pol = get_policy("proportional")
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(table.perf[0].sum() * 1.2),
                           acc_req=87.0)
    pol.plan(state, req)
    pol.plan(state, req)
    assert len(pol._dp_cache) == 0


# ---- SnapshotCache copy-on-write rules -------------------------------
def test_snapshot_cache_cow(pool):
    table = _measured_table(pool, [100.0, 50.0])
    cache = SnapshotCache()
    s1 = cache.snapshot(table, now=0.0)
    s2 = cache.snapshot(table, now=1.0, backlogs={"n0": 0.4})
    # unchanged table: the heavy arrays and index caches are SHARED
    assert s2.perf is s1.perf
    assert s2.accuracies is s1.accuracies
    assert s2.avail_idx is s1.avail_idx
    assert s2.perf_version == s1.perf_version
    # per-snapshot values are not
    assert s2.now_s == 1.0 and s2.backlog_of("n0") == 0.4
    # snapshots stay immutable
    with pytest.raises(ValueError):
        s2.perf[0, 0] = 1.0
    # a table mutation invalidates: fresh copy, old snapshot untouched
    before = float(s1.perf[0, 0])
    table.scale_node(0, 0.5)
    s3 = cache.snapshot(table, now=2.0)
    assert s3.perf is not s1.perf
    assert s3.perf_version != s1.perf_version
    assert s1.perf[0, 0] == before
    assert s3.perf[0, 0] == pytest.approx(before * 0.5)
    # availability flip refreshes the mask + avail_idx, perf still shared
    table.nodes[1].available = False
    s4 = cache.snapshot(table, now=3.0)
    assert s4.perf is s3.perf
    assert s4.available == (True, False)
    assert s4.avail_idx.tolist() == [0]


def test_snapshot_cache_never_aliases_tables(pool):
    """One cache pointed at a different table — even at an equal version
    and node count — must refresh both the arrays and the memo token."""
    table_a = _measured_table(pool, [100.0, 50.0])
    table_b = _measured_table(pool, [70.0, 30.0])
    assert table_a.version == table_b.version
    cache = SnapshotCache()
    sa = cache.snapshot(table_a)
    sb = cache.snapshot(table_b)
    assert sb.perf is not sa.perf
    assert float(sb.perf[0, 0]) == float(table_b.perf[0, 0])
    assert sb.perf_version != sa.perf_version
    assert sb.plan_key != sa.plan_key


# ---- fast vs legacy control plane ------------------------------------
@pytest.mark.parametrize("scenario", ["steady", "straggler-storm"])
def test_fast_control_plane_matches_legacy(pool, scenario):
    """The incremental snapshot/backlog path + optimized planners must
    reproduce the pre-PR control plane's serving metrics exactly, even
    under execution noise, straggler EWMA decay, and admission control."""
    def run(legacy):
        table = ProfilingTable(pool, cluster_nodes(0), seq_len=512)
        sc = build_scenario(scenario, table, seed=3, horizon_s=8.0)
        policy = "reference:proportional" if legacy else "proportional"
        gn = GatewayNode(table, SimBackend(table, noise_std=0.05, seed=3),
                         policy=policy)
        return OnlineSimulator(gn, sc.arrivals, sc.faults,
                               scenario=sc.name, horizon_s=sc.horizon_s,
                               admission=AdmissionController(table),
                               legacy_control_plane=legacy).run()

    fast, legacy = run(False), run(True)
    sf, sl = fast.summary(), legacy.summary()
    assert sf.keys() == sl.keys()
    for k in sf:
        assert sf[k] == pytest.approx(sl[k], abs=1e-9), k
    assert len(fast.log) == len(legacy.log)
    assert fast.n_events == legacy.n_events > 0
    assert fast.wall_s > 0


# ---- oracle dominated-level pruning ----------------------------------
def test_oracle_dominated_pruning_enumerates_past_node_limit(pool):
    """Saturated (flat) approximation ladders — every level the same
    throughput — prune to one candidate per node, so the oracle stays
    *exact* beyond max_enum_nodes instead of falling back, and annotates
    the plan."""
    m = len(pool)
    n = 9
    # flat columns: approximating buys nothing, so levels 1.. duplicate
    # level 0's throughput and are dominated (equal perf, lower acc)
    caps = np.linspace(40.0, 120.0, n)
    measured = np.repeat(caps[None, :], m, axis=0)
    nodes = [NodeProfile(f"n{i}", chips=1) for i in range(n)]
    table = ProfilingTable(pool, nodes, measured=measured)
    state = ClusterState.from_table(table)
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(measured[0].sum() * 0.5),
                           acc_req=0.0)
    plan = get_policy("exact_oracle").plan(state, req)
    assert "fallback" not in plan.meta
    assert plan.meta["enum"] == "dominated_pruned"
    # the single non-dominated level per node is level 0
    assert all(a.apx_level == 0 for a in plan.dispatch.assignments)


def test_oracle_strictly_slower_deep_level_is_not_pruned(pool):
    """Strict-throughput domination is NOT sound for the perf-weighted
    accuracy objective (raising a below-average-accuracy node's weight
    can lower the ratio), so a strictly slower deep level must survive
    pruning — past max_enum_nodes such columns force the honest
    fallback rather than a silently-inexact enumeration."""
    m = len(pool)
    n = 9
    caps = np.linspace(40.0, 120.0, n)
    # strictly decreasing with depth: nothing is an exact duplicate
    measured = np.repeat(caps[None, :], m, axis=0) * np.linspace(
        1.0, 0.6, m)[:, None]
    from repro.sched.policies import _non_dominated_levels
    cands = _non_dominated_levels(measured)
    assert all(len(c) == m for c in cands)
    table = ProfilingTable(pool, [NodeProfile(f"n{i}", chips=1)
                                  for i in range(n)], measured=measured)
    state = ClusterState.from_table(table)
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(measured[0].sum() * 0.5),
                           acc_req=0.0)
    plan = get_policy("exact_oracle").plan(state, req)
    assert plan.meta["fallback"] == "proportional"


def test_oracle_pruned_enumeration_matches_full(pool):
    """On a table where pruning applies, forcing the pruned path (tiny
    max_enum_nodes) must find the same optimum the full enumeration
    does."""
    m = len(pool)
    rng = np.random.default_rng(5)
    n = 5
    measured = np.sort(rng.uniform(20.0, 120.0, (m, n)), axis=0)
    measured[2] = measured[1]            # duplicate row: level 2 dominated
    nodes = [NodeProfile(f"n{i}", chips=1) for i in range(n)]
    table = ProfilingTable(pool, nodes, measured=measured)
    state = ClusterState.from_table(table)
    req = InferenceRequest(rid=0, num_items=520,
                           perf_req=float(measured[-1].sum() * 0.55),
                           acc_req=0.0)
    full = get_policy("exact_oracle").plan(state, req)
    pruned = get_policy("exact_oracle", max_enum_nodes=2).plan(state, req)
    assert pruned.meta.get("enum") == "dominated_pruned"
    assert pruned.predicted_acc == pytest.approx(full.predicted_acc)
    assert pruned.alloc_perf == pytest.approx(full.alloc_perf)


# ---- fleet scenarios --------------------------------------------------
def test_fleet_scenario_smoke(pool):
    """fleet-64 builds and serves: heterogeneous 64-node table, churn
    faults, plans fan across the whole fleet."""
    table = ProfilingTable(pool, synthetic_fleet(64, seed=0), seq_len=512)
    assert table.num_nodes == 64
    sc = build_scenario("fleet-64", table, seed=0, horizon_s=1.0)
    assert sc.faults and len(sc.arrivals) > 50
    gn = GatewayNode(table, SimBackend(table, seed=0),
                     policy="proportional")
    rep = OnlineSimulator(gn, sc.arrivals, sc.faults, scenario=sc.name,
                          horizon_s=sc.horizon_s).run()
    s = rep.summary()
    assert s["completed"] == s["offered"] > 0
    done = [r for r in rep.records if r.done]
    assert max(len(r.result.per_node_time) for r in done) > 32


def test_fleet_sizes_consistent():
    assert FLEET_SIZES == {"fleet-64": 64, "fleet-256": 256,
                           "fleet-1024": 1024, "fleet-4096": 4096}
    fleet = synthetic_fleet(256, seed=1, num_standby=2)
    assert len(fleet) == 258
    assert sum(not n.available for n in fleet) == 2
    # deterministic for a seed
    again = synthetic_fleet(256, seed=1, num_standby=2)
    assert [(n.name, n.chips, n.capability) for n in fleet] == \
           [(n.name, n.chips, n.capability) for n in again]
    # heterogeneous: several distinct chip counts and capabilities
    assert len({n.chips for n in fleet}) >= 4
    assert len({n.capability for n in fleet}) >= 32


# ---- BENCH_3 golden cells --------------------------------------------
def _load_run_sim():
    spec = importlib.util.spec_from_file_location(
        "run_sim_bench", os.path.join(REPO_ROOT, "benchmarks",
                                      "run_sim.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("cell", [
    ("steady", "proportional", "none"),
    ("steady", "proportional", "full"),
    ("steady", "uniform_apx", "full"),
    ("diurnal", "exact_oracle", "none"),
])
def test_bench3_golden_cells_reproduce(cell):
    """The optimization only counts if the serving metrics are
    bit-stable: re-running a committed BENCH_3.json cell with the
    nightly sweep's shape must reproduce goodput/p99/shed exactly
    (within the anchor's own rounding)."""
    with open(os.path.join(REPO_ROOT, "BENCH_3.json")) as f:
        anchor = json.load(f)
    scenario, policy, control = cell
    committed = anchor["cells"][f"{scenario}/{policy}/{control}"]
    rs = _load_run_sim()
    row = rs.run_one(scenario, policy, control,
                     seed=anchor["seed"], horizon_s=anchor["horizon_s"],
                     noise_std=anchor["noise_std"],
                     num_standby=anchor["standby"],
                     admission_rate=0.0, verbose=False)
    assert round(row["goodput_rps"], 3) == pytest.approx(
        committed["goodput_rps"], abs=1e-9)
    assert round(row["p99_latency_s"], 5) == pytest.approx(
        committed["p99_latency_s"], abs=1e-9)
    assert round(row["shed_rate"], 4) == pytest.approx(
        committed["shed_rate"], abs=1e-9)
