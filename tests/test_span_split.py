"""``scripts/span_split.py``: the per-request split of a dump by the
program's spans, on hand-made rows and on rows of a smoke-config run."""
import importlib.util
import os
import time
import types

import jax
import pytest

from repro.configs import get_smoke_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MS = 1_000_000


def _tool():
    spec = importlib.util.spec_from_file_location(
        "span_split", os.path.join(ROOT, "scripts", "span_split.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _row(rid, run_ms, spans, traced=False, level=0, compiles=0):
    return {"rid": rid, "traced": traced, "plan_s": 0.0, "run_s": run_ms / 1e3,
            "shares": [{"node": "a", "level": level, "device": "d",
                        "compiles": compiles,
                        "spans": [[n, s * MS, e * MS, p] for n, s, e, p in spans]}]}


SHARE = [("runner.share", 0, 90, None), ("runner.build", 0, 10, 0),
         ("runner.prompts", 10, 11, 0), ("engine.compile", 11, 42, 0),
         ("engine.aot_prefill", 11, 12, 3), ("engine.compile_prefill", 12, 40, 3),
         ("engine.aot_decode", 40, 41, 3), ("engine.prefill", 42, 70, 0),
         ("engine.decode", 70, 85, 0), ("runner.fetch", 85, 89, 0)]


def test_per_request_splits_the_untimed_residue():
    got = _tool().per_request(_row(1, 100, SHARE, compiles=2))
    # 100 ms run less 10 build, 28 prefill, 15 decode
    assert got["runner_untimed_s"] == pytest.approx(0.047)
    # of it 28 + 4 + 1 + 1 + 1 ms in named spans
    assert got["between spans"] == pytest.approx(0.012)
    assert got["engine.compile_prefill"] == pytest.approx(0.028)
    assert got["compiles"] == 2


def test_split_keeps_traced_apart_and_names_what_grew():
    tool = _tool()
    slow = [list(s) for s in SHARE]
    slow[2] = ["runner.prompts", 10, 511, 0]         # prompts took 500 ms more
    rows = [_row(1, 100, SHARE), _row(2, 100, SHARE), _row(3, 100, SHARE),
            _row(4, 600, [tuple(s) for s in slow]),
            _row(5, 120, SHARE, traced=True)]
    lines = tool.split(rows)
    assert lines[0].startswith("window: 4 requests")
    assert any(line.startswith("traced: 1 requests") for line in lines)
    first_slow = next(line for line in lines if line.startswith("slow:"))
    assert "rid 4" in first_slow
    assert "grew: runner.prompts +0.5000" in first_slow


def test_rows_of_a_smoke_run_split_without_remainder_below_zero():
    from repro.core.requests import Assignment, Dispatch, InferenceRequest
    from repro.launch.serve import ShareRunner, place_nodes
    tool = _tool()
    runner = ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                         place_nodes(["n0", "n1"], jax.devices()[:1]))
    req = InferenceRequest(rid=11, num_items=6, perf_req=1.0, acc_req=0.0)
    d = Dispatch(request=req, policy="test", assignments=(
        Assignment(node="n0", items=3, apx_level=0, perf_alloc=0.0),
        Assignment(node="n1", items=3, apx_level=1, perf_alloc=0.0)))
    t0 = time.perf_counter()
    shares = runner.run(d)
    run_s = time.perf_counter() - t0
    runner.close()
    rec = types.SimpleNamespace(spec=types.SimpleNamespace(rid=11),
                                plan_s=0.0, run_s=run_s, shares=shares)
    got = tool.per_request(tool._row(rec, traced=False))
    assert got["runner.release"] > 0                 # level 1 took level 0's place
    assert got["engine.aot_decode"] > 0
    assert "engine.compile_prefill" not in got       # compile runs no prefill
    assert 0 <= got["between spans"] < got["runner_untimed_s"] < run_s
