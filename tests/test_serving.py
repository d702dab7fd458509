"""Serving engine tests: prefill/decode consistency against the full
forward pass, ring-buffer invariants, generation."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.models.moe as moe_mod
from repro.configs import ARCH_NAMES, get_smoke_config
from repro.models import forward, init_params
from repro.serving.engine import BatchScheduler, Engine, EngineConfig


@pytest.fixture(autouse=True)
def _no_moe_drops(monkeypatch):
    """Decode never drops tokens but the batched dense path can (capacity);
    disable drops so the consistency comparison is exact."""
    monkeypatch.setattr(moe_mod, "capacity",
                        lambda t, e, k, factor=None: max(64, t * k))


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
    l_pref, caches, lengths = eng.prefill(toks, embeds)
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
    l_pref, caches, lengths = eng.prefill(toks)
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
