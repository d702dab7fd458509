"""The expert layer against a plain every-expert loop, on the CPU at smoke
sizes: one chip's share of the experts, the shares of a whole layer added
up, routing that sends every token to one expert, the published
group-limited sigmoid routing, and the parameter count of the cut."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config
from repro.models import moe as moe_mod
from repro.models import transformer as tfm
from repro.models.layers import init_from_specs

SHARES = 8          # the smoke layer's 64 experts over 8 chips, a group each


def _layer(arch, **moe):
    """The smoke config of ``arch`` in float32 (its MoE fields replaced by
    ``moe``) and random weights of one expert layer holding every expert."""
    cfg = get_smoke_config(arch).scaled(dtype="float32")
    cfg = cfg.scaled(moe=dataclasses.replace(cfg.moe, **moe))
    whole = cfg.scaled(moe=dataclasses.replace(cfg.moe, held_experts=0))
    p = init_from_specs(jax.random.PRNGKey(1),
                        moe_mod.moe_param_specs(whole), jnp.float32)
    if "router_bias" in p:
        p["router_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(2), p["router_bias"].shape)
    return cfg, p


def _tokens(cfg, b=2, s=16, seed=3):
    return jax.random.normal(jax.random.PRNGKey(seed), (b, s, cfg.d_model))


def _close(got, want, err_msg=""):
    """Equal up to float32 rounding, on the scale of the largest output."""
    want = np.asarray(want)
    np.testing.assert_allclose(np.asarray(got, np.float64), want, rtol=1e-5,
                               atol=1e-5 * np.abs(want).max(), err_msg=err_msg)


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _np_route(cfg, p, x):
    """The published selection, token by token in numpy float64: the
    chosen experts (as a set) and their weights, by expert."""
    m = cfg.moe
    logits = np.asarray(x, np.float64) @ np.asarray(p["w_router"], np.float64)
    out = []
    for lg in logits:
        if m.scoring == "softmax":
            pr = np.exp(lg - lg.max())
            pr /= pr.sum()
            chosen = np.argsort(-pr)[:m.top_k]
            w = pr[chosen]
        else:
            sc = 1.0 / (1.0 + np.exp(-lg))
            choice = sc + np.asarray(p["router_bias"], np.float64)
            size = m.num_experts // m.n_group
            gscore = [np.sort(choice[g * size:(g + 1) * size])[-2:].sum()
                      for g in range(m.n_group)]
            kept = np.argsort(gscore)[::-1][:m.topk_group]
            allowed = [e for g in kept for e in range(g * size, (g + 1) * size)]
            chosen = sorted(allowed, key=lambda e: -choice[e])[:m.top_k]
            w = sc[chosen]
        w = w / w.sum() * m.router_scale
        out.append(dict(zip((int(e) for e in chosen), w)))
    return out


def _expert(p, e, x):
    g = x @ np.asarray(p["we_gate"][e], np.float64)
    u = x @ np.asarray(p["we_up"][e], np.float64)
    return (_silu(g) * u) @ np.asarray(p["we_down"][e], np.float64)


def _shared(p, x):
    g = x @ np.asarray(p["ws_gate"], np.float64)
    u = x @ np.asarray(p["ws_up"], np.float64)
    return (_silu(g) * u) @ np.asarray(p["ws_down"], np.float64)


def _loop(cfg, p, x, experts=None):
    """Every expert of ``experts`` (default all) over every token, times its
    weight where the token is routed to it, plus the shared expert."""
    b, s, d = x.shape
    x2 = np.asarray(x, np.float64).reshape(b * s, d)
    routes = _np_route(cfg, p, x2)
    out = _shared(p, x2) if "ws_gate" in p else np.zeros_like(x2)
    for e in (range(cfg.moe.num_experts) if experts is None else experts):
        gate = np.array([r.get(e, 0.0) for r in routes])
        out += gate[:, None] * _expert(p, e, x2)
    return out.reshape(b, s, d)


def _share(cfg, p, j, held):
    """Chip ``j``'s layer: it holds experts ``[j*held, (j+1)*held)``. The
    layer holds ``[0, held)``, so the router's outputs are rotated by whole
    groups to put chip ``j``'s experts first; routing is unchanged."""
    shift = j * held
    q = dict(p, w_router=jnp.roll(p["w_router"], -shift, axis=1),
             **{k: p[k][shift:shift + held]
                for k in ("we_gate", "we_up", "we_down")})
    if "router_bias" in p:
        q["router_bias"] = jnp.roll(p["router_bias"], -shift)
    return q


def test_shares_add_up_to_the_whole_layer():
    """Each chip's share is its own experts' part of the loop; the shares,
    with the shared expert counted once, add up to the loop over every
    expert, and so does the layer that holds them all."""
    cfg, p = _layer("deepseek-v3-ep32", held_experts=8)
    held = cfg.moe.held
    assert cfg.moe.num_experts == SHARES * held
    assert cfg.moe.num_experts // cfg.moe.n_group == held   # whole groups
    x = _tokens(cfg)
    whole = _loop(cfg, p, x)
    total = -(SHARES - 1) * _shared(p, np.asarray(x, np.float64).reshape(
        -1, cfg.d_model)).reshape(x.shape)
    for j in range(SHARES):
        got, _ = moe_mod.moe_apply(cfg, _share(cfg, p, j, held), x)
        want = _loop(cfg, p, x, range(j * held, (j + 1) * held))
        _close(got, want, f"share {j}")
        total = total + np.asarray(got, np.float64)
    _close(total, whole)
    full = cfg.scaled(moe=dataclasses.replace(cfg.moe, held_experts=0))
    got, _ = moe_mod.moe_apply(full, p, x)
    _close(got, whole)


@pytest.mark.parametrize("arch", ["deepseek-v3-ep32", "mixtral-8x7b"])
def test_skewed_routing_drops_nothing(arch):
    """Every token routed to expert 0 (and the same next ones): the layer
    still equals the loop, where a capacity of 1.25 T·k / E rows an
    expert would have dropped most of them. The counts say how many rows
    were routed to held experts and how many the grouped matmuls got."""
    cfg, p = _layer(arch)
    m = cfg.moe
    x = _tokens(cfg, b=2, s=32)
    t = x.shape[0] * x.shape[1]
    favour = jnp.zeros((m.num_experts,)).at[:m.top_k].set(
        20.0 - jnp.arange(m.top_k))
    if m.scoring == "sigmoid":
        p["w_router"] = p["w_router"] * 1e-3
        p["router_bias"] = favour
    else:
        p["w_router"] = p["w_router"] * 1e-3 + favour[None] / jnp.sum(x[0, 0])
        x = jnp.broadcast_to(x[:1, :1], x.shape)    # one token, many times
    assert all(list(r) == list(range(m.top_k))
               for r in _np_route(cfg, p, np.asarray(x).reshape(t, -1)))
    assert t * m.top_k / m.num_experts * 1.25 < t     # capacity would drop
    got, counts = moe_mod.moe_apply(cfg, _share(cfg, p, 0, m.held), x)
    experts = range(m.held)
    _close(got, _loop(cfg, p, x, experts))
    assert np.asarray(counts).tolist() == [t * min(m.top_k, m.held),
                                           t * min(m.top_k, m.held)]


def test_sigmoid_group_limited_topk_is_the_published_selection():
    """The program's routing against a plain numpy selection: sigmoid
    scores plus the correction bias choose, groups score their top 2, the
    best ``topk_group`` groups are kept, the top-k is chosen within them;
    the weights are the unbiased scores, renormalised, times the scale."""
    cfg, p = _layer("deepseek-v3-ep32")
    m = cfg.moe
    assert (m.scoring, m.n_group, m.topk_group, m.top_k) == ("sigmoid", 8, 4, 8)
    x = np.asarray(_tokens(cfg, b=4, s=32), np.float32).reshape(-1, cfg.d_model)
    gates, idx = moe_mod.route(cfg, p, x)
    want = _np_route(cfg, p, x)
    for row, (g, i) in enumerate(zip(np.asarray(gates), np.asarray(idx))):
        assert set(i.tolist()) == set(want[row]), row
        np.testing.assert_allclose(g, [want[row][e] for e in i.tolist()],
                                   rtol=1e-5)
    # the bias moves the choice and not the weights: with it, some token
    # chooses otherwise than by its scores alone
    plain = _np_route(cfg, dict(p, router_bias=0 * p["router_bias"]), x)
    assert any(set(a) != set(b) for a, b in zip(want, plain))


def test_published_routing_of_deepseek_v3():
    m = get_config("deepseek-v3-671b").moe
    assert (m.scoring, m.n_group, m.topk_group, m.router_scale) == (
        "sigmoid", 8, 4, 2.5)
    assert m.held == 256
    for arch in ("deepseek-v3-671b", "deepseek-v3-ep32"):
        assert get_smoke_config(arch).moe.d_ff_expert >= 256


def test_param_count_of_the_cut():
    """deepseek-v3-ep32 counts what the chip holds: 8 of 256 experts a
    layer, with the router's 256 outputs; by hand at the published widths,
    and equal to the program's parameter tree."""
    cfg = get_config("deepseek-v3-ep32")
    d, h, v = 7168, 128, 16160
    mla = (d * 1536 + 1536 * h * (128 + 64) + d * (512 + 64)
           + 512 * h * (128 + 128) + h * 128 * d + 1536 + 512)
    expert = 3 * d * 2048
    dense = 2 * d + mla + 3 * d * 18432
    moe = 2 * d + mla + (8 + 1) * expert + d * 256 + 256
    want = 2 * v * d + dense + 8 * moe + d
    assert cfg.param_count() == want
    assert 5.4e9 < want < 5.6e9
    held = sum(int(np.prod(a.shape)) for a in
               jax.tree_util.tree_leaves(tfm.abstract_params(cfg)))
    assert held == want
    # the top-k experts a token passes through: here all 8 held
    assert cfg.param_count(active_only=True) == want
    assert get_config("deepseek-v3-671b").param_count() > 100 * want


def test_share_runner_carries_the_expert_counts():
    """A served share of the cut carries the prefill's expert rows: the
    grouped matmuls are given T·min(k, held) rows in each expert layer, of
    which those routed to held experts are counted; a dense model counts
    none, and its prefill program returns nothing extra."""
    import jax
    from repro.core.requests import Assignment, Dispatch, InferenceRequest
    from repro.launch import serve
    from repro.models import model as model_lib

    out = {}
    for arch in ("deepseek-v3-ep32", "phi4-mini-3.8b"):
        cfg = get_smoke_config(arch)
        runner = serve.ShareRunner(cfg, {"n0": jax.devices()[0]})
        req = InferenceRequest(rid=5, num_items=8, perf_req=1.0, acc_req=0.0)
        share, = runner.run(Dispatch(request=req, policy="test", assignments=(
            Assignment(node="n0", items=8, apx_level=0, perf_alloc=0.0),)))
        runner.close()
        out[arch] = (cfg, share)
    cfg, s = out["deepseek-v3-ep32"]
    m = cfg.moe
    tokens = serve.SHARE_ITEMS * serve.PROMPT_LEN
    layers = cfg.num_layers - cfg.num_dense_layers
    assert s.expert_slots == tokens * min(m.top_k, m.held) * layers
    # about T·k·held/E routed here, far fewer than the rows given
    expected = tokens * m.top_k * m.held / m.num_experts * layers
    assert 0.5 * expected < s.expert_rows < 2 * expected
    cfg, s = out["phi4-mini-3.8b"]
    assert (s.expert_rows, s.expert_slots) == (0, 0)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    assert len(model_lib.prefill(cfg, params, jnp.ones((1, 8), jnp.int32))) == 2
