"""Batch-aware node runtime invariants: the batch curve, batch-aware
plan pricing (fast == reference), the continuous-batching runtime
(plan-predicted == realized, never worse than sequential, mid-batch
fault re-distribution, formation window), the quantized split,
file-backed trace replay, and the accuracy_edf policy.
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.configs import get_config
from repro.control import AdmissionController
from repro.core.batching import BatchFormation
from repro.core.cluster import SimBackend, cluster_nodes
from repro.core.profiling import (REF_BATCH, NodeProfile, ProfilingTable,
                                  batched_service_s, variant_item_cost)
from repro.core.requests import InferenceRequest
from repro.core.resource_manager import GatewayNode
from repro.sched import ClusterState, get_policy, resolve_policy
from repro.sched.split import quantized_batch_split
from repro.sim import OnlineSimulator, build_scenario
from repro.sim.arrivals import TraceArrivals
from repro.sim.scenarios import trace as trace_scenario
from repro.core.variants import VariantPool

SHORT_SEQ = 8      # memory-bound serving regime: batching matters here


@pytest.fixture(scope="module")
def pool():
    return VariantPool(get_config("phi4-mini-3.8b"))


def _short_table(pool, num_standby=0):
    return ProfilingTable(pool, cluster_nodes(num_standby),
                          seq_len=SHORT_SEQ)


def _measured_table(pool, caps, avail=None, seq_len=128):
    caps = np.asarray(caps, dtype=np.float64)
    speed = np.linspace(1.0, 2.1, len(pool))[:, None]
    nodes = [NodeProfile(f"n{i}", chips=1,
                         available=(avail[i] if avail is not None else True))
             for i in range(len(caps))]
    return ProfilingTable(pool, nodes, measured=caps[None, :] * speed,
                          seq_len=seq_len)


def _run(pool, max_batch, *, scenario="overload", seq_len=SHORT_SEQ,
         policy="proportional", horizon=5.0, admission=True, seed=0,
         window=0.0):
    table = ProfilingTable(pool, cluster_nodes(0), seq_len=seq_len)
    sc = build_scenario(scenario, table, seed=seed, horizon_s=horizon)
    gn = GatewayNode(table, SimBackend(table, seed=seed), policy=policy,
                     max_batch=max_batch)
    adm = AdmissionController(table) if admission else None
    return OnlineSimulator(gn, sc.arrivals, sc.faults, scenario=sc.name,
                           horizon_s=sc.horizon_s, admission=adm,
                           formation_window_s=window).run()


# ---- cost model & batch curve ----------------------------------------
def test_amortization_constant_removed(pool):
    """variant_item_cost takes the batch explicitly: batch=1 streams the
    weights per item, batch=REF_BATCH reproduces the old folded cost."""
    cfg = pool.variants[0].config
    c1 = variant_item_cost(cfg, 128, batch=1)
    c8 = variant_item_cost(cfg, 128)              # default REF_BATCH
    assert c1["flops"] == c8["flops"]             # compute is per item
    assert c1["bytes"] > c8["bytes"]              # weights not amortized
    n_active = cfg.param_count(active_only=True)
    assert c1["bytes"] - c8["bytes"] == pytest.approx(
        2.0 * n_active * (1 - 1 / REF_BATCH))


def test_perf_matrix_is_ref_batch_column(pool):
    """The scalar perf matrix every batching-unaware consumer reads is
    exactly the batch curve's REF_BATCH column."""
    for table in (_short_table(pool),
                  _measured_table(pool, [100.0, 70.0, 40.0])):
        ref_idx = table.batch_grid.index(REF_BATCH)
        np.testing.assert_array_equal(table.perf,
                                      table.perf_b[:, :, ref_idx])


def test_throughput_monotone_in_batch(pool):
    """Node throughput is monotone non-decreasing in the engine batch —
    on the grid and at interpolated points."""
    table = _short_table(pool)
    for m in range(table.num_levels):
        for j in range(table.num_nodes):
            tps = [table.throughput(m, j, b) for b in range(1, 65)]
            assert all(b2 >= b1 - 1e-9 for b1, b2 in zip(tps, tps[1:])), (
                m, j)
            # grid points reproduce exactly
            for bi, b in enumerate(table.batch_grid):
                assert table.throughput(m, j, b) == table.perf_b[m, j, bi]


def test_batch_curve_tracks_table_mutations(pool):
    table = _short_table(pool)
    before = table.perf_b.copy()
    v0 = table.version
    table.scale_node(1, 0.5)
    assert table.version == v0 + 1
    np.testing.assert_allclose(table.perf_b[:, 1, :], before[:, 1, :] * 0.5)
    np.testing.assert_array_equal(table.perf_b[:, 0, :], before[:, 0, :])
    table.reprofile_node(1)
    np.testing.assert_array_equal(table.perf_b, before)
    # a same-valued re-profile column (the startup NETCOM gather) must
    # leave the curve bit-identical
    table.update_node(0, table.perf[:, 0].copy())
    np.testing.assert_array_equal(table.perf_b, before)


def test_batched_service_never_worse_at_saturating_batch(pool):
    """Serving k items through the curve at a saturating cap is never
    slower than the sequential (REF_BATCH scalar) model, for the share
    sizes the samplers draw."""
    table = _short_table(pool)
    grid = table.batch_grid
    for m in (0, 2, 5):
        for j in range(table.num_nodes):
            curve = table.perf_b[m, j]
            for k in (64, 130, 260, 650):
                seq = k / table.perf[m, j]
                bat = batched_service_s(k, curve, grid, 32)
                assert bat <= seq * (1 + 1e-9), (m, j, k)


# ---- batch-aware plan pricing ----------------------------------------
def test_batched_plans_identical_to_reference(pool):
    """Seeded property test, batched edition: with a batch cap on the
    snapshot every optimized planner prices identically to its
    reference twin (curve pricing, quantized split included)."""
    rng = np.random.default_rng(7)
    checked = 0
    for trial in range(40):
        n = int(rng.integers(1, 10))
        caps = rng.uniform(10.0, 120.0, n)
        avail = [True] * n
        if n > 1 and rng.random() < 0.3:
            avail[int(rng.integers(n))] = False
        table = _measured_table(pool, caps, avail)
        backlogs = {f"n{i}": float(rng.uniform(0.0, 0.5))
                    for i in range(n) if rng.random() < 0.5}
        state = ClusterState.from_table(
            table, now=float(rng.uniform(0.0, 10.0)), backlogs=backlogs,
            max_batch=int(rng.choice([2, 4, 8, 32, 48])))
        assert state.batched
        lo, hi = table.perf[0].sum(), table.perf[-1].sum()
        req = InferenceRequest(
            rid=trial, num_items=int(rng.choice([1, 13, 260, 650])),
            perf_req=float(lo + rng.uniform(0.0, 1.0) * (hi - lo)),
            acc_req=87.0)
        for name in ("uniform", "uniform_apx", "asymmetric",
                     "proportional", "exact_oracle"):
            if name == "exact_oracle" and sum(avail) > 6:
                continue
            a = get_policy(name).plan(state, req)
            b = resolve_policy(f"reference:{name}").plan(state, req)
            assert a.dispatch.assignments == b.dispatch.assignments, (
                name, trial)
            assert a.makespan_s == b.makespan_s, (name, trial)
            assert dict(a.node_service_s) == dict(b.node_service_s)
            assert a.meta["assumed_batch"] == b.meta["assumed_batch"] \
                == state.max_batch
            checked += 1
    assert checked >= 100


def test_quantized_split_shape(pool):
    """The batched split hands out engine-batch multiples with at most
    one tail chunk, and always sums to the request."""
    rng = np.random.default_rng(3)
    table = _measured_table(pool, [100.0, 70.0, 40.0, 20.0])
    for max_batch in (4, 8, 32):
        state = ClusterState.from_table(
            table, backlogs={"n0": 0.2}, max_batch=max_batch)
        idx = state.avail_idx
        shares = state.eff_perf[0, idx] / state.eff_perf[0, idx].sum()
        for items in (1, 13, 64, 260, 650):
            split = quantized_batch_split(
                state, idx, np.zeros(len(idx), dtype=int), shares, items)
            assert sum(split) == items
            tails = [s % max_batch for s in split if s % max_batch]
            assert len(tails) <= 1, (max_batch, items, split)


def test_quantized_split_survives_adversarial_shares(pool):
    """fp-guard regression: share vectors are only *intended* simplex
    points — fp error (or a buggy policy) can hand the split negative
    entries, sums above 1.0, NaN or inf. The guarded split must still
    conserve items with non-negative counts and at most one tail chunk;
    unguarded, an oversubscribed sum drove ``leftover`` negative and the
    function returned counts that did not sum to the request."""
    table = _measured_table(pool, [100.0, 70.0, 40.0])
    nan, inf = float("nan"), float("inf")
    adversarial = [
        [1.2, -0.3, 0.4],            # negative entry, sum > 1
        [0.7, 0.7, 0.7],             # oversubscribed: strips whole batches
        [nan, 0.5, 0.6],
        [inf, 0.2, 0.1],
        [-1.0, -1.0, -1.0],          # nothing placeable: greedy does it all
        [0.0, 0.0, 0.0],
        [2.0, 2.0, 2.0],
    ]
    for max_batch in (4, 32):
        state = ClusterState.from_table(table, max_batch=max_batch)
        idx = state.avail_idx
        levels = np.zeros(len(idx), dtype=int)
        for shares in adversarial:
            for items in (1, 13, 64, 650):
                split = quantized_batch_split(
                    state, idx, levels, np.asarray(shares), items)
                assert sum(split) == items, (shares, items, split)
                assert all(s >= 0 for s in split), (shares, items, split)
                tails = [s % max_batch for s in split if s % max_batch]
                assert len(tails) <= 1, (shares, items, split)


def test_unbatched_plan_unchanged_fields(pool):
    """max_batch=1 snapshots plan exactly as before the batch dimension
    existed: scalar pricing, no assumed_batch annotation."""
    table = _measured_table(pool, [100.0, 60.0])
    state = ClusterState.from_table(table)
    assert not state.batched
    plan = get_policy("proportional").plan(
        state, InferenceRequest(rid=0, num_items=520, perf_req=150.0,
                                acc_req=87.0))
    assert "assumed_batch" not in plan.meta
    for a in plan.dispatch.assignments:
        if a.items:
            assert plan.node_service_s[a.node] == pytest.approx(
                a.items / a.perf_alloc)


# ---- runtime: continuous batching ------------------------------------
def test_plan_predicted_matches_realized_batched(pool):
    """Plan-once, batched: under the noise-free backend every admitted,
    never-redistributed request's realized makespan matches the gate
    plan's batch-aware prediction within 5% (exact for solo tails; tail
    merges only shift the last engine batch)."""
    rep = _run(pool, 32, horizon=5.0)
    checked = 0
    for rec in rep.records:
        if not rec.admitted or not rec.done or rec.redistributed:
            continue
        realized = rec.finish_s - rec.dispatch_s
        # late side is the SLO-relevant one: a tail merge can finish a
        # request early (its tail rides a bigger, earlier batch), never
        # late beyond one engine batch
        assert realized <= rec.plan.makespan_s * 1.05 + 1e-9
        checked += 1
    assert checked >= 100
    assert rep.summary()["plan_makespan_err"] <= 0.05


def test_batched_never_worse_than_sequential(pool):
    """The batching A/B on the memory-bound regime: same trace, same
    policy — continuous batching at a saturating cap serves strictly
    more goodput than the sequential model, and (with no admission
    gate) every request finishes no later."""
    on = _run(pool, 32)
    off = _run(pool, 1)
    assert on.summary()["goodput_rps"] >= 1.5 * off.summary()["goodput_rps"]
    # drain comparison without a gate: identical request set
    on2 = _run(pool, 32, scenario="steady", admission=False, horizon=4.0)
    off2 = _run(pool, 1, scenario="steady", admission=False, horizon=4.0)
    assert len(on2.records) == len(off2.records)
    worse = sum(a.latency_s > b.latency_s + 1e-9
                for a, b in zip(on2.records, off2.records))
    assert worse == 0
    assert on2.end_s <= off2.end_s + 1e-9


def test_batch_one_runtime_identical_to_sequential_model(pool):
    """max_batch=1 IS the sequential model: bit-identical summaries and
    per-request timing against a GatewayNode built without any batching
    configuration at all."""
    rep_def = _run(pool, 1, scenario="steady", horizon=4.0)
    table = ProfilingTable(pool, cluster_nodes(0), seq_len=SHORT_SEQ)
    sc = build_scenario("steady", table, seed=0, horizon_s=4.0)
    gn = GatewayNode(table, SimBackend(table, seed=0),
                     policy="proportional")      # no max_batch argument
    rep_off = OnlineSimulator(gn, sc.arrivals, sc.faults,
                              scenario=sc.name, horizon_s=sc.horizon_s,
                              admission=AdmissionController(table)).run()
    a, b = rep_def.summary(), rep_off.summary()
    assert a == b
    for ra, rb in zip(rep_def.records, rep_off.records):
        assert ra.finish_s == rb.finish_s
        assert ra.queue_wait_s == rb.queue_wait_s


def test_fast_vs_legacy_identity_with_batching(pool):
    """The legacy control plane (per-share backlog recompute, from_table
    snapshots) and the incremental one must agree on every serving
    metric with batching enabled — the O(1) sensors stay correct under
    batched service times."""
    for max_batch in (1, 32):
        reps = []
        for legacy in (False, True):
            table = ProfilingTable(pool, cluster_nodes(0),
                                   seq_len=SHORT_SEQ)
            sc = build_scenario("node-churn", table, seed=2,
                                horizon_s=4.0)
            policy = ("reference:proportional" if legacy
                      else "proportional")
            gn = GatewayNode(table, SimBackend(table, seed=2),
                             policy=policy, max_batch=max_batch)
            reps.append(OnlineSimulator(
                gn, sc.arrivals, sc.faults, scenario=sc.name,
                horizon_s=sc.horizon_s,
                admission=AdmissionController(table),
                legacy_control_plane=legacy).run())
        fast, legacy = (r.summary() for r in reps)
        mism = [k for k in fast if abs(fast[k] - legacy[k]) > 1e-9]
        assert not mism, (max_batch, mism)


def test_mid_batch_disconnect_redistributes(pool):
    """A node dying mid-engine-batch aborts the op and re-DISTRIBUTEs
    every riding request over the survivors (paper Fig. 9, batched)."""
    table = _short_table(pool)
    reqs = [InferenceRequest(rid=i, num_items=520, perf_req=1.0,
                             acc_req=0.0, arrival_s=0.001 * i)
            for i in range(4)]
    sc = trace_scenario(table, [(r.arrival_s, r) for r in reqs])
    victim = table.nodes[0].name
    from repro.sim.simulator import TimedFault
    gn = GatewayNode(table, SimBackend(table, seed=0),
                     policy="proportional", max_batch=32)
    sim = OnlineSimulator(gn, sc.arrivals,
                          [TimedFault(time=0.0015, kind="disconnect",
                                      node=victim)],
                          horizon_s=1.0)
    assert sim.batching.enabled
    rep = sim.run()
    assert sim.nodes[victim].active is None
    assert not sim.nodes[victim].queue
    assert sum(r.redistributed for r in rep.records) >= 1
    assert all(r.done for r in rep.records)
    for rec in rep.records:
        if rec.redistributed:
            assert victim not in rec.per_node_time


def test_formation_window_joins_small_shares(pool):
    """With a formation window, small shares arriving within it ride one
    engine batch (join-on-arrival); without it the first launches alone
    and finishes first."""
    nodes = [NodeProfile("solo", chips=1)]
    table = ProfilingTable(pool, nodes, seq_len=SHORT_SEQ)
    reqs = [InferenceRequest(rid=0, num_items=2, perf_req=0.0,
                             acc_req=0.0, arrival_s=0.0),
            InferenceRequest(rid=1, num_items=2, perf_req=0.0,
                             acc_req=0.0, arrival_s=0.01)]
    trace = [(r.arrival_s, r) for r in reqs]

    def run(window):
        table_w = ProfilingTable(pool, [NodeProfile("solo", chips=1)],
                                 seq_len=SHORT_SEQ)
        sc = trace_scenario(table_w, trace)
        gn = GatewayNode(table_w, SimBackend(table_w, seed=0),
                         policy="uniform", max_batch=8)
        return OnlineSimulator(gn, sc.arrivals, sc.faults, horizon_s=1.0,
                               formation_window_s=window).run()

    held = run(0.05)
    eager = run(0.0)
    r0h, r1h = held.records
    assert r0h.finish_s >= 0.05                       # held for joiners
    assert r0h.finish_s == pytest.approx(r1h.finish_s)   # one batch
    r0e, r1e = eager.records
    assert r0e.finish_s < r1e.finish_s                # launched alone
    assert r0e.finish_s < 0.05


def test_batch_formation_policy():
    f = BatchFormation(max_batch=8, window_s=0.5)
    assert not f.ready(0, 99.0)
    assert f.ready(8, 0.0) and f.ready(12, 0.0)
    assert not f.ready(3, 0.4)
    assert f.ready(3, 0.5)
    assert f.take(12) == 8 and f.take(3) == 3
    assert BatchFormation().max_batch == 1
    assert not BatchFormation(max_batch=1).enabled


# ---- trace replay -----------------------------------------------------
def test_trace_arrivals_from_file_csv_and_jsonl(pool, tmp_path):
    csv_path = tmp_path / "serving_log.csv"
    csv_path.write_text(
        "arrival_s,num_items,seq_len,slo_class,perf_req\n"
        "0.0,260,64,degradable,100.0\n"
        "0.5,130,,strict,\n"
        "0.25,520,128,degradable,200.0\n")
    tr = TraceArrivals.from_file(str(csv_path))
    arr = tr.generate()
    assert [t for t, _ in arr] == [0.0, 0.25, 0.5]     # sorted
    r0 = arr[0][1]
    assert (r0.num_items, r0.seq_len, r0.perf_req) == (260, 64, 100.0)
    assert r0.latency_budget_s == pytest.approx(1.5 * 260 / 100.0)
    r_strict = arr[2][1]
    assert r_strict.slo_class == "strict"
    assert r_strict.seq_len == 128                     # default
    assert r_strict.latency_budget_s == float("inf")   # no perf contract

    jsonl_path = tmp_path / "serving_log.jsonl"
    jsonl_path.write_text("\n".join(
        json.dumps({"arrival_s": t, "num_items": r.num_items,
                    "seq_len": r.seq_len, "slo_class": r.slo_class,
                    "perf_req": r.perf_req, "rid": r.rid})
        for t, r in arr) + "\n")
    arr_j = TraceArrivals.from_file(str(jsonl_path)).generate()
    assert [(t, r.rid, r.num_items, r.slo_class) for t, r in arr_j] == \
        [(t, r.rid, r.num_items, r.slo_class) for t, r in arr]


def test_trace_scenario_spec_runs_in_simulator(pool, tmp_path):
    path = tmp_path / "log.csv"
    table = _short_table(pool)
    cap = table.perf[0].sum()
    path.write_text("arrival_s,num_items,perf_req\n" + "".join(
        f"{0.01 * i},260,{cap * 0.8}\n" for i in range(20)))
    sc = build_scenario(f"trace:{path}", table)
    assert sc.horizon_s == pytest.approx(0.19)
    gn = GatewayNode(table, SimBackend(table, seed=0),
                     policy="proportional", max_batch=32)
    rep = OnlineSimulator(gn, sc.arrivals, sc.faults, scenario=sc.name,
                          horizon_s=sc.horizon_s).run()
    assert len(rep.records) == 20
    assert all(r.done for r in rep.records)


def test_trace_file_unknown_column_rejected(tmp_path):
    bad = tmp_path / "bad.csv"
    bad.write_text("arrival_s,num_items,oops\n0.0,1,2\n")
    with pytest.raises(AssertionError, match="unknown column"):
        TraceArrivals.from_file(str(bad))


# ---- accuracy_edf policy ---------------------------------------------
def test_accuracy_edf_picks_highest_accuracy_meeting_deadline(pool):
    table = _measured_table(pool, [100.0])
    state = ClusterState.from_table(table)
    pol = get_policy("accuracy_edf")
    service = [100.0 / float(table.perf[m, 0])
               for m in range(table.num_levels)]
    # budget between level-1 and level-0 service: level 1 is the highest
    # accuracy that still meets the deadline
    budget = (service[0] + service[1]) / 2
    plan = pol.plan(state, InferenceRequest(
        rid=0, num_items=100, perf_req=0.0, acc_req=0.0,
        deadline_s=budget))
    assert plan.meta["edf_level"] == 1
    assert plan.meets_deadline
    # an infinite budget buys full accuracy
    easy = pol.plan(state, InferenceRequest(
        rid=1, num_items=100, perf_req=0.0, acc_req=0.0))
    assert easy.meta["edf_level"] == 0
    # an impossible budget ships the deepest level as best effort
    hard = pol.plan(state, InferenceRequest(
        rid=2, num_items=100, perf_req=0.0, acc_req=0.0,
        deadline_s=service[-1] / 2))
    assert hard.meta["edf"] == "best_effort"
    assert hard.meta["edf_level"] == table.num_levels - 1
    assert not hard.meets_deadline


def test_accuracy_edf_is_batch_and_backlog_aware(pool):
    table = _measured_table(pool, [100.0, 80.0])
    req = InferenceRequest(rid=0, num_items=260, perf_req=0.0,
                           acc_req=0.0, deadline_s=2.0)
    pol = get_policy("accuracy_edf")
    idle = pol.plan(ClusterState.from_table(table), req)
    busy = pol.plan(ClusterState.from_table(
        table, backlogs={"n0": 1.2, "n1": 1.2}), req)
    assert busy.meta["edf_level"] >= idle.meta["edf_level"]
    assert busy.meets_deadline
    # batched snapshots price the curve: in the memory-bound (short-seq)
    # regime a deeper engine batch buys higher accuracy at one deadline
    short = _measured_table(pool, [100.0, 80.0], seq_len=SHORT_SEQ)
    tight = dataclasses.replace(req, deadline_s=0.9)
    seq_plan = pol.plan(ClusterState.from_table(short), tight)
    bat_plan = pol.plan(ClusterState.from_table(short, max_batch=32),
                        tight)
    assert bat_plan.meta["assumed_batch"] == 32
    assert bat_plan.meta["edf_level"] <= seq_plan.meta["edf_level"]


def test_accuracy_edf_in_online_loop(pool):
    """accuracy_edf runs end-to-end through gate + simulator and admits
    with zero admitted-violation rate on the overload scenario."""
    rep = _run(pool, 32, policy="accuracy_edf", horizon=3.0)
    s = rep.summary()
    assert s["completed"] > 50
    assert s["deadline_violation_rate"] == 0.0
    assert s["plan_makespan_err"] <= 0.05


# ---- snapshot plumbing ------------------------------------------------
def test_snapshot_carries_batch_views(pool):
    table = _short_table(pool)
    gn = GatewayNode(table, SimBackend(table, seed=0), max_batch=32)
    gn.startup()
    s1 = gn.snapshot()
    assert s1.max_batch == 32 and s1.batched
    assert s1.perf_b is not None and not s1.perf_b.flags.writeable
    assert s1.plan_key[-1] == 32
    # COW: the curve copy is shared across snapshots until a mutation
    s2 = gn.snapshot()
    assert s2.perf_b is s1.perf_b
    assert s2.eff_perf is s1.eff_perf
    table.scale_node(0, 0.9)
    s3 = gn.snapshot()
    assert s3.perf_b is not s1.perf_b
    assert float(s3.eff_perf[0, 0]) == pytest.approx(
        float(s1.eff_perf[0, 0]) * 0.9)
    # hand-built snapshots default to batching off
    assert ClusterState.from_table(table).max_batch == 1
