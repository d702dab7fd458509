"""The reader of ``share_overlap`` (how many of a request's shares run at
once) on hand-made records and on a smoke-config run on one device."""
import contextlib
import importlib
import types

import entry
import harness
import jax
import pytest
from conftest import smoke_doc

from repro.configs import get_smoke_config
from repro.serving.spans import Span

MS = 1_000_000


def _read(records):
    ctx = types.SimpleNamespace(records=records)
    return importlib.import_module("metrics.share_overlap").read(ctx)


def _share():
    return types.SimpleNamespace(spans=(Span("runner.share", 0, MS, None),),
                                 compiles=0)


def _record(*shares, run_s=MS * 1e-9):
    return types.SimpleNamespace(shares=list(shares), run_s=run_s)


@pytest.mark.parametrize("run_ms, want", [(1, 4.0), (4, 1.0)])
def test_share_overlap_on_hand_made_records(run_ms, want):
    """Four 1-ms shares in a 1-ms request ran at once; in a 4-ms request,
    one after another."""
    four = [_share() for _ in range(4)]
    assert _read([_record(*four, run_s=run_ms * MS * 1e-9)]) \
        == pytest.approx(want)


def test_share_overlap_is_the_mean_over_requests():
    four = [_share() for _ in range(4)]
    recs = [_record(*four), _record(*four, run_s=4 * MS * 1e-9)]
    assert _read(recs) == pytest.approx(2.5)


def test_share_overlap_finds_nothing_without_spans():
    """No records, or the program before spans: None rather than raising."""
    bare = types.SimpleNamespace(served=8, build_s=0.0, prefill_s=0.2)
    assert _read([]) is None
    assert _read([_record(bare)]) is None


def test_share_overlap_on_a_one_device_smoke_run():
    """Every share on one device runs one after another: just under 1."""
    arch = "phi4-mini-3.8b"
    cfg, doc = get_smoke_config(arch), smoke_doc(arch)
    gn = entry.build_gateway(cfg, policy=doc["policy"])
    nodes = [n.name for n in gn.table.nodes]
    runner = entry.ShareRunner(cfg, entry.place_nodes(nodes,
                                                      jax.devices()[:1]))
    req = entry.InferenceRequest(rid=3, num_items=390, perf_req=1.0,
                                 acc_req=0.0)
    gn.handle(entry.Event(kind="workload", request=req))
    runner.run(gn.dispatches[-1])                   # warm-up

    def serve():
        spec = types.SimpleNamespace(rid=3, num_items=390, perf_req=1.0,
                                     acc_req=0.0)
        return harness.serve(gn, runner, spec,
                             lambda name: contextlib.nullcontext())
    window = [serve(), serve()]
    runner.close()
    assert 0.5 < _read(window) <= 1.0
