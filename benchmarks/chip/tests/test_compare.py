"""The accuracy guarantee: which requests are held to their ``acc_req``,
and that the gateway's plans meet it on those, at the cells' own
configurations (planning is analytic, so the CPU plans as the chip does)."""
import dataclasses
import itertools
import types

import compare
import entry
import harness
import numpy as np
import pytest
import traffic
from conftest import BENCH

from repro.sched import policies

ACC = (90.0, 80.0)
PERF = np.array([[10.0, 20.0],        # level 0 on nodes a, b (items/s)
                 [20.0, 40.0]])       # level 1


def test_best_accuracy_by_hand():
    # 30 items at 30 items/s: 1 s; both at level 0 serve 10 + 20 at 90 %
    assert compare.best_accuracy(PERF, ACC, 30, 30.0) == pytest.approx(90.0)
    # 40 items at 40 items/s: both at level 0 hold only 10 + 20; a at 1
    # and b at 0 hold 20 + 20, 85 %; a at 0 and b at 1 give 10 at 90 % and
    # 30 at 80 %, 82.5 %
    assert compare.best_accuracy(PERF, ACC, 40, 40.0) == pytest.approx(85.0)
    # 60 items at 60 items/s: only both at level 1, 20 + 40
    assert compare.best_accuracy(PERF, ACC, 60, 60.0) == pytest.approx(80.0)
    # nothing serves 70 items/s
    assert compare.best_accuracy(PERF, ACC, 70, 70.0) == -np.inf
    # whole items: 3 items at 2.5 items/s leave a 1.2 s makespan, which
    # holds 1 item on a at level 0 and 2 on b
    assert compare.best_accuracy(PERF, ACC, 3, 2.5) == pytest.approx(90.0)


def _plans(config, mix, seeds):
    doc = harness.load_json(f"{BENCH}/configs/{config}.json")
    acc = [lv["accuracy"] for lv in doc["ladder"]]
    out = []
    for seed in seeds:
        gn = entry.build_gateway(entry.get_config(doc["arch"]),
                                 policy=doc["policy"])
        perf = np.asarray(gn.table.perf)
        for r in next(traffic.passes(traffic.load(mix), seed, perf[0].sum(),
                                     perf[-1].sum())):
            res = gn.handle(entry.Event(kind="workload",
                                        request=entry.InferenceRequest(
                                            rid=r.rid, num_items=r.num_items,
                                            perf_req=r.perf_req,
                                            acc_req=r.acc_req)))
            best = compare.best_accuracy(perf, acc, r.num_items, r.perf_req)
            out.append((r, res.achieved_acc, best))
    return out


@pytest.mark.parametrize("config", ["phi4-mini-3.8b", "phi4-mini-3.8b-4chip"])
def test_acc_strict_holds_every_request_and_meets_it(config):
    for r, got, best in _plans(config, "acc-strict", range(4)):
        assert best >= r.acc_req and got >= r.acc_req


def test_fig7_plans_meet_every_acc_req_some_plan_can():
    plans = _plans("phi4-mini-3.8b", "fig7-mix", (1, 2**31 + 11))
    held = [(r, got) for r, got, best in plans if best >= r.acc_req]
    assert len(held) >= 0.9 * len(plans)
    assert all(got >= r.acc_req for r, got in held)


def test_plans_do_not_depend_on_the_order():
    """A request's plan is the same whatever came before it, so every seed
    is held to the same requests."""
    by = {}
    for r, got, _ in _plans("phi4-mini-3.8b", "fig7-mix", (3, 4, 5)):
        by.setdefault((r.num_items, r.perf_req, r.acc_req), set()).add(got)
    assert all(len(v) == 1 for v in by.values())


def test_pool_items_cover_the_mix():
    spec = traffic.load("fig7-mix")
    items = {p["num_items"] for p in traffic.pool(spec, 1.0, 2.0)}
    assert items == set(spec["items"])
    assert len(list(itertools.islice(traffic.passes(spec, 0, 1.0, 2.0),
                                     1))[0]) == spec["pool_size"]


def _records(config, seed):
    """The window's records of one pass of fig7-mix, with every share
    served as planned (levels, items, shapes), without the engines."""
    doc = harness.load_json(f"{BENCH}/configs/{config}.json")
    gn = entry.build_gateway(entry.get_config(doc["arch"]),
                             policy=doc["policy"])
    perf = np.asarray(gn.table.perf)
    out = []
    for r in next(traffic.passes(traffic.load("fig7-mix"), seed,
                                 perf[0].sum(), perf[-1].sum())):
        res = gn.handle(entry.Event(kind="workload",
                                    request=entry.InferenceRequest(
                                        rid=r.rid, num_items=r.num_items,
                                        perf_req=r.perf_req,
                                        acc_req=r.acc_req)))
        d = gn.dispatches[-1]
        shares = []
        for a in d.assignments:
            if a.items:
                n = min(a.items, entry.SHARE_ITEMS)
                shares.append(types.SimpleNamespace(
                    node=a.node, level=a.apx_level, items=a.items, served=n,
                    tokens=np.zeros((n, entry.DECODE_STEPS), np.int32),
                    logits=np.zeros((n, doc["vocab_size"]), np.float32)))
        out.append(harness.Record(spec=r, dispatch=d, result=res,
                                  shares=shares, plan_s=0.0, run_s=0.0))
    return doc, perf, out


def _plan_numbers(seed):
    doc, perf, recs = _records("phi4-mini-3.8b", seed)
    numbers = compare.plan_checks(recs, doc, perf, entry.SHARE_ITEMS,
                                  entry.DECODE_STEPS)
    checks = harness.judge(numbers, doc["limits"])
    return numbers, all(c["value"] <= c["limit"] for c in checks.values())


def test_sound_plans_pass():
    numbers, ok = _plan_numbers(2**31 + 1)
    assert ok and numbers["acc_shortfall"] == 0, numbers


def test_plans_approximating_deeper_fall_short(monkeypatch):
    """Each plan one level deeper than the policy chose: faster, and short
    of an acc_req that some plan meets."""
    orig = policies.Proportional.select

    def deeper(self, state, request):
        sel = orig(self, state, request)
        return dataclasses.replace(sel, key=None, levels=np.minimum(
            np.asarray(sel.levels) + 1, state.num_levels - 1))
    monkeypatch.setattr(policies.Proportional, "select", deeper)
    numbers, ok = _plan_numbers(2**31 + 1)
    assert not ok
    assert numbers["acc_shortfall"] > 0
    assert numbers["plan_mismatch"] == 0 and numbers["acc_claim_err"] < 1e-9
