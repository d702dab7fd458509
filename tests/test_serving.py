"""Serving engine tests: prefill/decode consistency against the full
forward pass, ring-buffer invariants, generation."""
import logging
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, get_smoke_config
from repro.models import forward, init_params
from repro.serving.engine import BatchScheduler, Engine, EngineConfig


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_consistency(arch, rng):
    cfg = get_smoke_config(arch).scaled(dtype="float32")
    params = init_params(cfg, rng)
    B, S = 2, 12
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    embeds = None
    total = S
    if cfg.frontend_stub:
        embeds = jax.random.normal(
            rng, (B, cfg.stub_embed_len, cfg.d_model), jnp.float32)
        total += cfg.stub_embed_len
    eng = Engine(cfg, params, EngineConfig(max_len=total + 8))

    logits_full, _ = forward(cfg, params, toks, embeds)
    l_pref, caches, lengths, _ = eng.prefill(toks, embeds)
    np.testing.assert_allclose(np.asarray(l_pref),
                               np.asarray(logits_full[:, -1]),
                               atol=2e-4, rtol=2e-4)

    # two decode steps, each checked against the growing full forward
    cur = toks
    for _ in range(2):
        nxt = jnp.argmax(l_pref, axis=-1).astype(jnp.int32)
        cur = jnp.concatenate([cur, nxt[:, None]], axis=1)
        full, _ = forward(cfg, params, cur, embeds)
        l_pref, caches, lengths = eng.decode(caches, lengths, nxt)
        np.testing.assert_allclose(np.asarray(l_pref),
                                   np.asarray(full[:, -1]),
                                   atol=5e-4, rtol=5e-4)


def test_sliding_window_ring_buffer(rng):
    """Prompt longer than the window: decode must still match the full
    forward (ring-buffer roll invariant: slot p%w holds position p)."""
    cfg = get_smoke_config("mixtral-8x7b").scaled(dtype="float32",
                                                  sliding_window=8)
    params = init_params(cfg, rng)
    B, S = 1, 13            # S > window
    toks = jax.random.randint(rng, (B, S), 0, cfg.vocab_size)
    eng = Engine(cfg, params, EngineConfig(max_len=24))
    l_pref, caches, lengths, _ = eng.prefill(toks)
    full, _ = forward(cfg, params, toks)
    np.testing.assert_allclose(np.asarray(l_pref), np.asarray(full[:, -1]),
                               atol=2e-4, rtol=2e-4)
    nxt = jnp.argmax(l_pref, axis=-1).astype(jnp.int32)
    cur = jnp.concatenate([toks, nxt[:, None]], axis=1)
    full2, _ = forward(cfg, params, cur)
    l_dec, *_ = eng.decode(caches, lengths, nxt)
    np.testing.assert_allclose(np.asarray(l_dec), np.asarray(full2[:, -1]),
                               atol=5e-4, rtol=5e-4)


def test_generate_deterministic(rng):
    cfg = get_smoke_config("qwen3-32b").scaled(dtype="float32")
    params = init_params(cfg, rng)
    toks = jax.random.randint(rng, (2, 8), 0, cfg.vocab_size)
    eng = Engine(cfg, params, EngineConfig(max_len=32))
    g1 = eng.generate(toks, num_steps=5)
    g2 = eng.generate(toks, num_steps=5)
    assert g1.shape == (2, 5)
    np.testing.assert_array_equal(g1, g2)


def test_batch_scheduler_left_pads():
    sched = BatchScheduler(batch_size=3)
    for p in ([1, 2, 3], [4, 5], [6]):
        sched.add(np.asarray(p, np.int32))
    batch = sched.next_batch()
    assert batch.shape == (3, 3)
    np.testing.assert_array_equal(batch[1], [0, 4, 5])
    assert sched.next_batch() is None


def test_pad_caches_ring_slot_invariant():
    """The docstring's ring-buffer contract, checked on the raw buffer:
    after ``pad_caches`` a sliding-window KV cache must hold position p in
    slot p % window for each of the last ``window`` prefill positions."""
    from repro.serving.engine import _pad_kv
    from repro.models.attention import KVCache

    L, B, KV, D = 2, 1, 1, 4
    for S, w in ((13, 8), (16, 8), (8, 8), (9, 4), (5, 8)):
        # encode the absolute position p into every element of slot p
        x = jnp.broadcast_to(
            jnp.arange(S, dtype=jnp.float32)[None, None, :, None, None],
            (L, B, S, KV, D))
        out = _pad_kv(KVCache(k=x, v=x), max_len=32, seq_len=S, window=w)
        eff_w = min(w, 32)
        if S >= eff_w:
            assert out.k.shape[2] == eff_w
            for p in range(S - eff_w, S):
                slot = np.asarray(out.k)[:, :, p % eff_w]
                np.testing.assert_array_equal(
                    slot, np.full((L, B, KV, D), p, np.float32),
                    err_msg=f"S={S} w={w}: slot {p % eff_w} != position {p}")
        else:
            # shorter-than-window prompts are zero-padded, identity layout
            for p in range(S):
                np.testing.assert_array_equal(
                    np.asarray(out.k)[:, :, p],
                    np.full((L, B, KV, D), p, np.float32))


def test_batch_scheduler_fifo_order_across_batches():
    """Prompts drain in arrival (FIFO) order across successive batches,
    each left-padded to its own batch's max length."""
    sched = BatchScheduler(batch_size=2)
    prompts = [np.arange(1, n + 1, dtype=np.int32) for n in (3, 1, 2, 4, 2)]
    for p in prompts:
        sched.add(p)
    seen = []
    while (batch := sched.next_batch()) is not None:
        assert batch.shape[0] <= 2
        for row in batch:
            seen.append(row[row != 0].tolist())
    assert seen == [p.tolist() for p in prompts]
    assert sched.next_batch() is None


def test_share_runner_builds_bf16_params_on_its_device():
    """The share runner serves each share of a real plan on the device its
    node is placed on, with weights in the config's dtype (bf16)."""
    from repro.configs import get_config
    from repro.core.resource_manager import Event
    from repro.launch.serve import (DECODE_STEPS, SHARE_ITEMS, ShareRunner,
                                    build_gateway, demo_requests,
                                    place_nodes)
    gn = build_gateway(get_config("phi4-mini-3.8b"))
    device = jax.devices()[-1]
    runner = ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                         place_nodes([n.name for n in gn.table.nodes],
                                     [device]))
    req = demo_requests(gn, 1)[0]
    gn.handle(Event(kind="workload", request=req))
    shares = runner.run(gn.dispatches[-1])
    assert {s.node for s in shares} == {
        a.node for a in gn.dispatches[-1].assignments if a.items}
    for s in shares:
        assert s.served == min(s.items, SHARE_ITEMS)
        assert s.tokens.shape == (s.served, DECODE_STEPS)
        assert 0 <= s.tokens.min() and s.tokens.max() < 256
        assert s.logits.shape == (s.served, 256)
        assert np.isfinite(s.logits).all()
    (level, eng), = runner.resident.values()
    assert level == shares[-1].level
    leaves = jax.tree_util.tree_leaves(eng.params)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype(jnp.bfloat16)}
    assert all(leaf.devices() == {device} for leaf in leaves)
    runner.close()
    assert eng.params is None and not runner.resident


# --- spans and the compile counter (repro.serving.spans) ---

def _one_share_dispatch(node, items, level=0, rid=7):
    from repro.core.requests import Assignment, Dispatch, InferenceRequest
    req = InferenceRequest(rid=rid, num_items=items, perf_req=1.0,
                           acc_req=0.0)
    return Dispatch(request=req, policy="test", assignments=(Assignment(
        node=node, items=items, apx_level=level, perf_alloc=0.0),))


@pytest.fixture(scope="module")
def served_twice():
    """A smoke-config runner on one device serving a share of a batch shape
    no other test serves, then the same share again."""
    from repro.launch.serve import ShareRunner, place_nodes
    runner = ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                         place_nodes(["n0", "n1"], jax.devices()[:1]))
    d = _one_share_dispatch("n0", items=3)
    first, = runner.run(d)
    again, = runner.run(d)
    other, = runner.run(_one_share_dispatch("n1", items=3, level=1))
    runner.close()
    return first, again, other


def test_share_spans_nest_on_one_thread(served_twice):
    for r in served_twice:
        assert r.spans[0].name == "runner.share" and r.spans[0].parent is None
        for i, s in enumerate(r.spans[1:], 1):
            p = r.spans[s.parent]
            assert s.parent < i
            assert p.start_ns <= s.start_ns <= s.end_ns <= p.end_ns
        top = {s.name for s in r.spans if s.parent == 0}
        assert {"runner.prompts", "engine.compile", "engine.prefill",
                "engine.decode", "runner.fetch"} <= top
        # siblings do not overlap: one thread, one span at a time
        kids = sorted((s for s in r.spans if s.parent == 0),
                      key=lambda s: s.start_ns)
        assert all(a.end_ns <= b.start_ns for a, b in zip(kids, kids[1:]))
    first, again, other = served_twice
    assert [s.name for s in first.spans].count("runner.build") == 1
    assert "runner.build" not in [s.name for s in again.spans]
    assert "runner.build" in [s.name for s in other.spans]   # level 1 in
    assert first.rid == again.rid == 7


def test_share_seconds_are_their_spans(served_twice):
    from repro.launch.serve import DECODE_STEPS

    def dur(r, name):
        s, = [s for s in r.spans if s.name == name]
        return (s.end_ns - s.start_ns) * 1e-9
    for r in served_twice:
        names = [s.name for s in r.spans]
        assert r.build_s == (dur(r, "runner.build")
                             if "runner.build" in names else 0.0)
        assert r.prefill_s == dur(r, "engine.prefill")
        assert r.decode_step_s == dur(r, "engine.decode") / DECODE_STEPS
        assert r.compile_s == {"prefill": dur(r, "engine.aot_prefill"),
                               "decode": dur(r, "engine.aot_decode")}
        assert r.prefill_s > 0 and r.decode_step_s > 0


def test_compile_holds_the_two_aot_compiles_only(served_twice):
    """``engine.compile`` runs no prefill: its children are the two
    programs' compiles, in order, and nothing else."""
    for r in served_twice:
        compile_i, = [i for i, s in enumerate(r.spans)
                      if s.name == "engine.compile"]
        assert r.spans[compile_i].parent == 0
        assert [s.name for s in r.spans if s.parent == compile_i] == [
            "engine.aot_prefill", "engine.aot_decode"]
        assert "engine.compile_prefill" not in [s.name for s in r.spans]


class _CompileLog(logging.Handler):
    """Names of the served programs JAX lowers or compiles while
    ``jax_log_compiles`` is on (where a program was lowered before, for
    another device, JAX logs only its compile)."""
    PROGRAM = re.compile(
        r"(?:Compiling|XLA compilation of) jit\((prefill|decode_step)\)")

    def __init__(self):
        super().__init__()
        self.programs = []

    def emit(self, record):
        m = self.PROGRAM.search(record.getMessage())
        if m:
            self.programs.append(m.group(1))


def _serve_engine(eng, tokens, steps):
    """A share's engine work: one prefill, then ``steps`` greedy steps.
    Returns the prefill's logits and the (B, steps) tokens."""
    logits, caches, lengths, _ = eng.prefill(tokens)
    first, out = logits, []
    for _ in range(steps):
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(tok)
        logits, caches, lengths = eng.decode(caches, lengths, tok)
    return np.asarray(first, np.float32), np.stack(
        [np.asarray(t) for t in out], axis=1)


@pytest.mark.parametrize("device_index", [0, 1])
def test_compile_leaves_the_served_calls_nothing_to_compile(device_index):
    """After ``Engine.compile(tokens)`` a share's prefill and decode steps
    on the engine's device compile neither program: the abstract decode
    arguments carry that device, as the served arrays do. A shape served
    without ``compile`` shows that the log names both programs."""
    from repro.serving.engine import init_params_on
    if len(jax.devices()) <= device_index:
        pytest.skip(f"the host has no device {device_index}")
    device = jax.devices()[device_index]
    cfg = get_smoke_config("phi4-mini-3.8b")
    params = init_params_on(cfg, jax.random.PRNGKey(5), device)
    eng = Engine(cfg, params, EngineConfig(max_len=72), device=device)
    compiled, fresh = (jax.device_put(jnp.ones((5, 37), jnp.int32), device),
                       jax.device_put(jnp.ones((3, 37), jnp.int32), device))
    eng.compile(compiled)
    log = _CompileLog()
    logger = logging.getLogger("jax")
    logger.addHandler(log)
    try:
        with jax.log_compiles(True):
            _serve_engine(eng, compiled, 3)
            after_compile = list(log.programs)
            _serve_engine(eng, fresh, 3)
    finally:
        logger.removeHandler(log)
    eng.release()
    assert after_compile == []
    assert set(log.programs) == {"prefill", "decode_step"}


def test_share_is_a_plain_prefill_and_decode(served_twice):
    """A served share's logits and tokens are exactly those of
    ``Engine.prefill`` and ``DECODE_STEPS`` greedy decode steps, with no
    ``compile`` first, on the same prompts and weights."""
    from repro.launch.serve import (CACHE_LEN, DECODE_STEPS, ShareRunner,
                                    place_nodes)
    from repro.serving.engine import init_params_on
    first = served_twice[0]
    device = jax.devices()[0]
    runner = ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                         place_nodes(["n0", "n1"], [device]))
    cfg = runner.pool[first.level].config
    tokens = runner._prompts(first.rid, first.node, first.served,
                             cfg.vocab_size)
    params = init_params_on(cfg, jax.random.fold_in(
        jax.random.PRNGKey(0), first.level), device)
    eng = Engine(cfg, params, EngineConfig(max_len=CACHE_LEN), device=device)
    logits, toks = _serve_engine(eng, tokens, DECODE_STEPS)
    eng.release()
    np.testing.assert_array_equal(first.logits, logits)
    np.testing.assert_array_equal(first.tokens, toks)


def test_compiles_counts_new_shapes_only(served_twice):
    first, again, _ = served_twice
    assert first.compiles >= 1
    assert again.compiles == 0


def test_record_without_collector_appends_nothing():
    from repro.serving import spans
    ran = []
    with spans.record("outside"):
        ran.append(1)
    assert ran == [1]
    with spans.collect("root") as got:
        with spans.record("inner"):
            ran.append(2)
    assert [s.name for s in got] == ["root", "inner"]
    assert [s.parent for s in got] == [None, 0]
    with spans.record("after"):         # the collector closed with its block
        ran.append(3)
    assert ran == [1, 2, 3] and len(got) == 2


def test_spans_of_another_thread_are_not_collected():
    import threading
    from repro.serving import spans

    def elsewhere():
        with spans.record("other.thread"):
            pass
    with spans.collect("root") as got:
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join(timeout=10)
    assert not t.is_alive()
    assert [s.name for s in got] == ["root"]


def test_compile_counter_counts_a_persistent_cache_load_once(tmp_path):
    """A load from the persistent cache is one program, though JAX records
    both its backend-compile event and a cache hit for it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.serving import spans
    f = jax.jit(lambda x: jnp.tanh(x) * 3.0)
    x = jnp.ones((7, 5))
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        cc.reset_cache()
        n0 = spans.compiles()
        f.lower(x).compile()
        n1 = spans.compiles()
        f.lower(x).compile()                 # in memory: nothing compiles
        n2 = spans.compiles()
        jax.clear_caches()
        f.lower(x).compile()                 # loaded from the cache
        n3 = spans.compiles()
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert (n1 - n0, n2 - n1, n3 - n2) == (1, 0, 1)


def test_programs_carry_their_function_names():
    from repro.serving.engine import _init_program, _programs
    cfg = get_smoke_config("phi4-mini-3.8b")
    key = jax.random.PRNGKey(0)
    init = _init_program(cfg)
    assert init.lower(key).as_text().startswith("module @jit_init_params")
    params = init(key)
    prefill, decode = _programs(cfg, False, True)
    tokens = jnp.ones((2, 16), jnp.int32)
    assert prefill.lower(params, tokens, None).as_text().startswith(
        "module @jit_prefill")
    eng = Engine(cfg, params, EngineConfig(max_len=32))
    logits, caches, lengths, _ = eng.prefill(tokens)
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    assert decode.lower(params, caches, lengths, tok).as_text().startswith(
        "module @jit_decode_step")


def test_a_swap_releases_outside_build(served_twice):
    """``build_s`` is the weight draw alone: freeing the resident level is
    ``runner.release``, a span of its own just before ``runner.build``."""
    first, again, other = served_twice
    assert "runner.release" not in [s.name for s in first.spans]
    assert "runner.release" not in [s.name for s in again.spans]
    rel, = [s for s in other.spans if s.name == "runner.release"]
    build, = [s for s in other.spans if s.name == "runner.build"]
    assert rel.parent == build.parent == 0
    assert rel.end_ns <= build.start_ns
    assert other.build_s == build.seconds


# --- shares on several devices at once (ShareRunner.run) ---

class _Dev(tuple):
    """A stand-in device: only its ``id`` is read where no engine is
    built."""

    @property
    def id(self):
        return self[0]


def _dispatch(shares, rid=11):
    """A dispatch of ``(node, items, level)`` shares."""
    from repro.core.requests import Assignment, Dispatch, InferenceRequest
    req = InferenceRequest(rid=rid, num_items=sum(s[1] for s in shares),
                           perf_req=1.0, acc_req=0.0)
    return Dispatch(request=req, policy="test", assignments=tuple(
        Assignment(node=n, items=i, apx_level=lv, perf_alloc=0.0)
        for n, i, lv in shares))


def _stand_in_runner(monkeypatch, device_of, serve):
    """A runner over stand-in devices (``device_of``: node -> device id)
    whose ``_serve`` is ``serve(runner, rid, assignment)``."""
    from repro.launch.serve import ShareRunner
    monkeypatch.setattr(ShareRunner, "_serve", serve)
    return ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                       {n: _Dev((j,)) for n, j in device_of.items()})


def test_shares_on_four_devices_run_at_once(monkeypatch):
    """Each share waits until all four are in flight: served one after
    another, the barrier breaks."""
    import threading
    barrier = threading.Barrier(4, timeout=20)
    threads = {}

    def serve(runner, rid, a):
        threads[a.node] = threading.get_ident()
        barrier.wait()
        return a.node
    runner = _stand_in_runner(monkeypatch, {f"n{j}": j for j in range(4)},
                              serve)
    try:
        got = runner.run(_dispatch([(f"n{j}", 8, 0) for j in range(4)]))
    finally:
        runner.close()
    assert got == ["n0", "n1", "n2", "n3"]
    assert len(set(threads.values())) == 4
    assert threading.get_ident() not in threads.values()


def test_shares_on_one_device_run_inline_in_level_order(monkeypatch):
    import threading
    calls = []

    def serve(runner, rid, a):
        calls.append((a.node, a.apx_level, threading.get_ident()))
        return a.node
    runner = _stand_in_runner(monkeypatch, {f"n{j}": 3 for j in range(4)},
                              serve)
    assert runner._pool is None
    got = runner.run(_dispatch([("n0", 8, 2), ("n1", 8, 0), ("n2", 0, 0),
                                ("n3", 8, 1)]))
    assert got == ["n1", "n3", "n0"]            # by level; n2 has no items
    assert [c[:2] for c in calls] == [("n1", 0), ("n3", 1), ("n0", 2)]
    assert {c[2] for c in calls} == {threading.get_ident()}


def test_results_come_back_in_device_then_level_order(monkeypatch):
    """Shares on three devices, two per device at different levels: each
    device's shares run in level order on one thread, and the results come
    back in (device, level) order, as when every share ran in turn."""
    import threading
    order = {}

    def serve(runner, rid, a):
        order.setdefault(runner.placement[a.node].id, []).append(
            (a.apx_level, threading.get_ident()))
        return (runner.placement[a.node].id, a.apx_level, a.node, rid)
    device_of = {"a": 2, "b": 0, "c": 1, "d": 2, "e": 0, "f": 1}
    runner = _stand_in_runner(monkeypatch, device_of, serve)
    shares = [("a", 8, 3), ("b", 8, 5), ("c", 4, 1), ("d", 8, 0),
              ("e", 2, 1), ("f", 8, 4)]
    try:
        got = runner.run(_dispatch(shares, rid=5))
    finally:
        runner.close()
    want = sorted((device_of[n], lv, n, 5) for n, _, lv in shares)
    assert got == want
    for dev, seen in order.items():
        assert [lv for lv, _ in seen] == sorted(lv for lv, _ in seen)
        assert len({t for _, t in seen}) == 1   # one thread per device


def test_a_failing_share_raises_from_run_and_the_runner_goes_on(
        monkeypatch):
    """An error in one device's share comes out of ``run`` after the other
    devices' shares have finished, and the runner serves the next
    dispatch."""
    served, fail = [], {"n2"}

    def serve(runner, rid, a):
        if a.node in fail:
            raise ValueError(f"share {a.node} failed")
        served.append((rid, a.node))
        return a.node
    runner = _stand_in_runner(monkeypatch, {f"n{j}": j for j in range(4)},
                              serve)
    try:
        with pytest.raises(ValueError, match="share n2 failed"):
            runner.run(_dispatch([(f"n{j}", 8, 0) for j in range(4)], rid=1))
        assert sorted(served) == [(1, "n0"), (1, "n1"), (1, "n3")]
        fail.clear()
        got = runner.run(_dispatch([(f"n{j}", 8, 0) for j in range(4)],
                                   rid=2))
    finally:
        runner.close()
    assert got == ["n0", "n1", "n2", "n3"]


_SPREAD_VS_ONE = r"""
import jax, numpy as np
from repro.configs import get_smoke_config
from repro.launch.serve import ShareRunner, place_nodes
from repro.core.requests import Assignment, Dispatch, InferenceRequest

devices = jax.devices()
assert len(devices) == 4, devices
nodes = ["n0", "n1", "n2", "n3"]
d = Dispatch(request=InferenceRequest(rid=9, num_items=16, perf_req=1.0,
                                      acc_req=0.0),
             policy="test", assignments=tuple(
    Assignment(node=n, items=4, apx_level=lv, perf_alloc=0.0)
    for n, lv in zip(nodes, (0, 0, 1, 1))))
cfg = get_smoke_config("phi4-mini-3.8b")
out = []
for devs in (devices, devices[:1]):
    runner = ShareRunner(cfg, place_nodes(nodes, devs))
    out.append(runner.run(d))
    runner.close()
spread, alone = out
assert len({s.device for s in spread}) == 4, [s.device for s in spread]
assert len({s.device for s in alone}) == 1
assert [(s.node, s.level, s.served) for s in spread] == \
    [(s.node, s.level, s.served) for s in alone]
for s, r in zip(spread, alone):
    np.testing.assert_array_equal(s.tokens, r.tokens)
    np.testing.assert_array_equal(s.logits, r.logits)
print("SAME", [(s.node, s.level, s.device) for s in spread])
"""


def test_shares_on_four_devices_equal_shares_on_one():
    """One dispatch served with its four nodes on four CPU devices, then
    all on one: the same shares in the same order, tokens and logits
    equal bit for bit (a process of its own: the device count is fixed at
    JAX's start)."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.path.join(root, "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", _SPREAD_VS_ONE], env=env,
                          capture_output=True, text=True, timeout=240,
                          cwd=root)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SAME" in proc.stdout


def test_a_compile_on_another_thread_is_not_the_shares():
    """A program compiled on another thread while a share is in flight
    counts in ``compiles()`` but not in the share's ``compiles``."""
    import threading
    from repro.launch.serve import ShareRunner, place_nodes
    from repro.serving import spans
    runner = ShareRunner(get_smoke_config("phi4-mini-3.8b"),
                         place_nodes(["n0"], jax.devices()[:1]))
    d = _one_share_dispatch("n0", items=2, rid=4)
    runner.run(d)                               # compiles the share's shape
    prompts = runner._prompts

    x, elsewhere = jnp.ones((3, 11)), []

    def compile_elsewhere():
        n = spans.thread_compiles()
        jax.jit(lambda x: jnp.sin(x) * 5.0 - 2.0).lower(x).compile()
        elsewhere.append(spans.thread_compiles() - n)

    def prompts_while_another_thread_compiles(*args):
        t = threading.Thread(target=compile_elsewhere)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
        return prompts(*args)
    runner._prompts = prompts_while_another_thread_compiles
    before = spans.compiles()
    again, = runner.run(d)
    process = spans.compiles() - before
    runner.close()
    assert elsewhere == [1]
    assert process == 1
    assert again.compiles == 0


def test_compile_counters_add_up_across_threads():
    """Threads compiling at once, with a short switch interval: each
    thread's count is its own programs, and the process count is their
    sum (a lost update would break either)."""
    import sys
    import threading
    from repro.serving import spans
    n_threads, per_thread = 12, 3
    counted = [None] * n_threads

    def work(i):
        n = spans.thread_compiles()
        for j in range(per_thread):
            scale = float(1000 * i + j + 1)
            jax.jit(lambda x: jnp.cos(x) * scale).lower(
                jax.ShapeDtypeStruct((i + 2, j + 3), jnp.float32)).compile()
        counted[i] = spans.thread_compiles() - n
    interval = sys.getswitchinterval()
    before = spans.compiles()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=work, args=(i,))
                   for i in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    assert counted == [per_thread] * n_threads
    assert spans.compiles() - before == n_threads * per_thread
