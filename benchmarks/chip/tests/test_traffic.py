"""Both traffic files: deterministic in the seed, one pool in another order
for another seed, and the plans the gateway makes for them."""
import itertools

import entry
import pytest
import traffic

MIXES = ("fig7-mix", "acc-strict")
CAPS = (11880.5, 24568.3)


@pytest.mark.parametrize("mix", MIXES)
def test_deterministic_in_seed(mix):
    spec = traffic.load(mix)
    a = list(itertools.islice(traffic.passes(spec, 2**31 + 5, *CAPS), 3))
    b = list(itertools.islice(traffic.passes(spec, 2**31 + 5, *CAPS), 3))
    assert a == b


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_reorder_one_pool(mix):
    """Every pass of every seed is the whole pool, in another order and
    with other request ids (so other prompts)."""
    spec = traffic.load(mix)

    def work(seed):
        ps = list(itertools.islice(traffic.passes(spec, seed, *CAPS), 2))
        return [[(r.num_items, r.perf_req, r.acc_req) for r in p]
                for p in ps], {r.rid for p in ps for r in p}
    (a1, a2), ids1 = work(1)
    (b1, _), ids2 = work(2**31 + 3)
    assert len(a1) == spec["pool_size"]
    assert a1 != b1 and a1 != a2
    assert sorted(a1) == sorted(a2) == sorted(b1)
    assert len(ids1) == 2 * spec["pool_size"] and not ids1 & ids2


def _levels(mix, arch, seeds):
    cfg = entry.get_config(arch)
    spec = traffic.load(mix)
    out = []
    for seed in seeds:
        gn = entry.build_gateway(cfg)
        caps = gn.table.perf[0].sum(), gn.table.perf[-1].sum()
        for r in next(traffic.passes(spec, seed, *caps)):
            gn.handle(entry.Event(kind="workload", request=entry.InferenceRequest(
                rid=r.rid, num_items=r.num_items, perf_req=r.perf_req,
                acc_req=r.acc_req)))
            out.append({a.apx_level for a in gn.dispatches[-1].assignments
                        if a.items > 0})
    return out


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "rwkv6-1.6b"])
def test_acc_strict_plans_serve_level_0(arch):
    assert all(lv == {0} for lv in _levels("acc-strict", arch, range(100)))


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "rwkv6-1.6b"])
def test_fig7_plans_mix_levels(arch):
    levels = _levels("fig7-mix", arch, range(3))
    mixed = sum(len(lv) >= 2 for lv in levels)
    assert mixed >= 0.75 * len(levels)          # most plans mix levels
    assert len(set().union(*levels)) >= 5       # and reach most of the ladder
