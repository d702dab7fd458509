"""Slab event loop equivalence (property-based).

PR 10 rebuilt the per-event hot path (slab-backed event queue, fused
dispatch); each keeps a verbatim twin (``events_reference.EventQueue``,
``OnlineSimulator._handle_reference``), and these tests pin the
optimized stack against the twins: the event streams must be
*identical*, not merely close. A last test holds warm planners to fresh
ones, so the selection memos cannot carry state between snapshots.

Like tests/test_merge_property.py, the properties run under hypothesis
when installed and fall back to a fixed seeded sweep over the same case
space otherwise.
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
from repro.core.profiling import ProfilingTable
from repro.core.requests import InferenceRequest
from repro.core.variants import VariantPool
from repro.sched import get_policy
from repro.sched.state import SnapshotCache
from repro.sim import ShardedSimulator
from repro.sim import events_reference
from repro.sim.events import SeqCounter, SlabEventQueue
from repro.sim.scenarios import (node_churn, noisy_neighbor,
                                 straggler_storm, tenant_skew)

POOL = VariantPool(get_config("phi4-mini-3.8b"))
SCENARIOS = {"node-churn": node_churn,
             "straggler-storm": straggler_storm,
             "tenant-skew": tenant_skew,
             "noisy-neighbor": noisy_neighbor}


# ---- queue: slab storage vs reference tuple heap ----------------------
def _drain(q):
    out = []
    while q:
        out.append(q.pop_parts())
    return out


def _check_queue_equivalence(seed, n_ops):
    """Identical op sequences applied to the slab queue and the retained
    reference queue yield identical pop streams — across counter pushes,
    pre-sequenced ``push_chunk`` bulk loads, interleaved pops (freelist
    recycling), timestamp ties (seq tie-break), and slab growth."""
    rng = np.random.default_rng(seed)
    slab = SlabEventQueue(SeqCounter())
    ref = events_reference.EventQueue(SeqCounter())
    chunk_seq = 1_000_000          # disjoint from the counters' range
    i = 0
    while i < n_ops:
        op = rng.random()
        # coarse time grid so same-timestamp ties are common — ordering
        # must then fall to seq alone, never to slot/payload
        t = float(rng.integers(0, 12)) / 4.0
        if op < 0.45:
            slab.push(t, f"k{i}", i=i)
            ref.push(t, f"k{i}", i=i)
            i += 1
        elif op < 0.65:
            items = []
            for _ in range(int(rng.integers(1, 9))):
                tc = float(rng.integers(0, 12)) / 4.0
                items.append((tc, chunk_seq, f"c{i}", {"i": i}))
                chunk_seq += 1
                i += 1
            slab.push_chunk(items)
            ref.push_chunk(list(items))
        elif op < 0.9 and slab:
            assert slab.peek_key() == ref.peek_key()
            assert slab.pop_parts() == ref.pop_parts()
        elif slab:
            a, b = slab.pop(), ref.pop()
            assert (a.time, a.seq, a.kind, a.payload) == \
                   (b.time, b.seq, b.kind, b.payload)
        assert len(slab) == len(ref)
    assert _drain(slab) == _drain(ref)


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=10_000),
           n_ops=st.integers(min_value=1, max_value=600))
    @settings(max_examples=30, deadline=None)
    def test_slab_queue_matches_reference(seed, n_ops):
        _check_queue_equivalence(seed, n_ops)
else:
    @pytest.mark.parametrize("seed,n_ops", [
        (0, 40), (1, 600), (7, 257), (42, 513), (99, 130), (2026, 300),
    ])
    def test_slab_queue_matches_reference(seed, n_ops):
        _check_queue_equivalence(seed, n_ops)


def test_slab_queue_grows_and_recycles():
    """Pushing past the initial slab capacity grows the slabs; a
    steady-state push/pop cycle afterwards recycles slots without
    growing again."""
    q = SlabEventQueue()
    n = SlabEventQueue._INITIAL_CAPACITY + 10
    for i in range(n):
        q.push(float(i), "e", i=i)
    grown = len(q._kind)
    assert grown >= n
    for i in range(n):
        assert q.pop_parts()[3] == {"i": i}
    for i in range(3 * n):          # steady state: no further growth
        q.push(float(i), "e", i=i)
        q.pop_parts()
    assert len(q._kind) == grown
    assert not q


# ---- event loop: slab+fused stack vs reference stack -----------------
def _table_factory(profiles):
    return ProfilingTable(POOL, profiles, seq_len=512)


def _stream(sim, rep):
    """Everything the event loop can influence: the digest-hashed record
    fields, the full log, the event count, and the routing decisions."""
    records = []
    for rec in rep.records:
        records.append((rec.request.rid, rec.arrival_s, rec.dispatch_s,
                        rec.finish_s, rec.done, rec.rejected,
                        rec.redistributed,
                        rec.result.per_node_time if rec.done else None))
    return (records, rep.log, rep.n_events, rep.end_s,
            sorted(sim.routed_cell.items()), sim.rebalances)


def _check_stack_equivalence(seed, scenario_name, max_batch, fair, gated):
    """THE tentpole property: across seeded churn/straggler/tenant
    scenarios x batching x fair-share at cells in {1, 4, 16}, the slab
    queue + fused dispatch stack produces an event stream byte-identical
    to the retained reference stack (tuple-heap queue, pre-fusion
    ``_handle`` chain)."""
    profiles = synthetic_fleet(16, seed=seed % 97, num_standby=2)
    table = _table_factory([dataclasses.replace(p) for p in profiles])
    sc = SCENARIOS[scenario_name](table, seed=seed, horizon_s=0.8)
    kw = dict(scenario=sc.name, horizon_s=sc.horizon_s, seed=0,
              autoscale=True, admission=gated, max_batch=max_batch,
              fairshare=fair, rebalance_s=0.25)
    for cells in (1, 4, 16):
        def sim(reference_stack):
            return ShardedSimulator(
                _table_factory, [dataclasses.replace(p) for p in profiles],
                sc.arrivals, sc.faults, cells=cells,
                reference_stack=reference_stack, **kw)
        fast, ref = sim(False), sim(True)
        a = _stream(fast, fast.run())
        b = _stream(ref, ref.run())
        assert a == b, f"cells={cells}"


if HAVE_HYPOTHESIS:
    @given(seed=st.integers(min_value=0, max_value=10_000),
           scenario=st.sampled_from(sorted(SCENARIOS)),
           max_batch=st.sampled_from([1, 32]),
           fair=st.booleans(),
           gated=st.booleans())
    @settings(max_examples=10, deadline=None)
    def test_fused_stack_matches_reference_stack(seed, scenario,
                                                 max_batch, fair, gated):
        _check_stack_equivalence(seed, scenario, max_batch, fair, gated)
else:
    @pytest.mark.parametrize("seed,scenario,max_batch,fair,gated", [
        (11, "node-churn", 1, False, False),
        (3, "node-churn", 32, False, True),
        (7, "straggler-storm", 1, False, True),
        (88, "straggler-storm", 32, True, False),
        (5, "tenant-skew", 32, True, True),
        (1234, "noisy-neighbor", 1, True, False),
    ])
    def test_fused_stack_matches_reference_stack(seed, scenario,
                                                 max_batch, fair, gated):
        _check_stack_equivalence(seed, scenario, max_batch, fair, gated)


# ---- warm planners: memos leak nothing between snapshots -------------
def _plan_fields(p):
    return (p.policy, p.dispatch.assignments, dict(p.node_service_s),
            dict(p.node_finish_s), p.exec_makespan_s, p.makespan_s,
            p.finish_s, p.created_s, p.alloc_perf, p.predicted_acc,
            p.feasible, dict(p.meta))


@pytest.mark.parametrize("policy_name", ["uniform", "uniform_apx",
                                         "asymmetric", "proportional",
                                         "exact_oracle"])
@pytest.mark.parametrize("max_batch", [1, 32])
def test_plan_replay_is_bit_identical_to_cold_assembly(policy_name,
                                                       max_batch):
    """A warm policy instance plans exactly as a fresh one, field for
    field: the selection memos it filled on one snapshot (the DP memo,
    the oracle's enumeration cache) must not leak into the next one,
    whether that moves the clock and perf_req, a backlog the batched
    split reads (an available node's), or one it never reads (an
    unavailable node's)."""
    cache = SnapshotCache()
    profiles = synthetic_fleet(6, seed=5)
    down, read = profiles[2].name, profiles[0].name
    profiles[2].available = False
    table = ProfilingTable(POOL, profiles, seq_len=512)
    hi = float(np.asarray(table.perf)[0].sum())
    warm = get_policy(policy_name)
    warm.plan(cache.snapshot(table, backlogs={read: 0.1, down: 0.7},
                             max_batch=max_batch),
              InferenceRequest(rid=0, num_items=260, perf_req=0.4 * hi,
                               acc_req=0.0))
    req = InferenceRequest(rid=1, num_items=260, perf_req=0.41 * hi,
                           acc_req=0.0)
    for backlogs in ({read: 0.1, down: 0.7},     # clock + perf_req moved
                     {read: 0.3, down: 0.7},     # read backlog moved
                     {read: 0.1, down: 4.2}):    # unread backlog moved
        state = cache.snapshot(table, now=3.5, backlogs=backlogs,
                               max_batch=max_batch)
        cold = get_policy(policy_name)           # fresh instance: no memo
        assert _plan_fields(warm.plan(state, req)) == \
            _plan_fields(cold.plan(state, req))
