"""A run with the timed path broken underneath comes out not correct.

Each test skips the harness's look for a chip (it calls ``harness.run``
with the CPU's devices and the smoke config) and drives the rest of a run:
warm-up, the window, the comparison. The faults a served cell can have:
a token or an answer altered where it is produced, half of the batch left
out, a share served at another level than planned. (A step that returns
its state unchanged is a training fault, and no cell has an exchange
between chips to leave out. Plans that approximate deeper than a request
needs are in ``test_compare.py``: the smoke ladder has one accuracy.) The
control, the reference in float8 in the program's
place, comes out not correct through the same comparison.
"""
import dataclasses
import json
import os
import time

import entry
import harness
import jax
import numpy as np
import pytest
from conftest import smoke_doc

from repro.configs import get_smoke_config
from repro.launch import serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return harness.find_cell(json.load(f), name)


def _run(arch="phi4-mini-3.8b", cell=None, seed=2**31 + 7, control=False):
    c = cell or _cell("phi4-mini.fig7-mix")
    return harness.run(c, smoke_doc(arch), get_smoke_config(arch), seed=seed,
                       seconds=1.0, trace=False, devices=jax.devices(),
                       t_start=time.perf_counter(), control=control)


def _patch_serve(monkeypatch, change):
    orig = entry.ShareRunner._serve

    def broken(self, rid, a):
        return change(orig(self, rid, a))
    monkeypatch.setattr(entry.ShareRunner, "_serve", broken)


def test_sound_run_is_correct():
    out = _run()
    assert out["correct"], out["checks"]
    assert list(out)[-1] == "checks"
    assert out["attempted"] % 24 == 0           # whole passes of the pool


def test_control_is_not_correct():
    out = _run(control=True)
    assert out["correct"], out["checks"]
    ctl = out["control"]
    assert not ctl["correct"]
    assert ctl["checks"]["logit_err"]["value"] > \
        3 * out["checks"]["logit_err"]["value"]
    assert set(ctl["weights_differing"].values()) == {0}
    assert list(out)[-2:] == ["control", "checks"]


def test_altered_token(monkeypatch):
    def change(r):
        r.tokens = np.array(r.tokens)
        r.tokens[0, 1] = (r.tokens[0, 1] + 1) % r.logits.shape[1]
        return r
    _patch_serve(monkeypatch, change)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]


def test_altered_answer(monkeypatch):
    def change(r):
        r.logits = r.logits * 1.25
        return r
    _patch_serve(monkeypatch, change)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["logit_err"]["value"] > \
        out["checks"]["logit_err"]["limit"]


def test_half_the_batch_left_out(monkeypatch):
    monkeypatch.setattr(serve, "SHARE_ITEMS", entry.SHARE_ITEMS // 2)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["plan_mismatch"]["value"] > 0


def test_other_level_served_under_the_planned_name(monkeypatch):
    orig = entry.ShareRunner._engine

    def wrong(self, device, level):
        return orig(self, device, (level + 1) % len(self.pool))
    monkeypatch.setattr(entry.ShareRunner, "_engine", wrong)
    out = _run()
    assert not out["correct"]
    assert out["checks"]["logit_err"]["value"] > \
        out["checks"]["logit_err"]["limit"]


@pytest.mark.parametrize("seed", [1, 2**31 + 99])
def test_rwkv_altered_token(monkeypatch, seed):
    def change(r):
        r.tokens = np.array(r.tokens)
        r.tokens[-1, -1] = (r.tokens[-1, -1] + 7) % r.logits.shape[1]
        return r
    _patch_serve(monkeypatch, change)
    # no cell serves rwkv6 yet; the fault is read on its configuration
    # under the acc-strict mix
    cell = dataclasses.replace(_cell("phi4-mini.acc-strict"),
                               name="rwkv6-1.6b.acc-strict")
    out = _run("rwkv6-1.6b", cell, seed)
    assert not out["correct"]
    assert out["checks"]["token_gap"]["value"] > \
        out["checks"]["token_gap"]["limit"]
