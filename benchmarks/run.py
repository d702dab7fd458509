"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the figure's
headline metric for that row). Run:  PYTHONPATH=src python -m benchmarks.run
"""
from __future__ import annotations

import time

import numpy as np

from repro.configs import get_config
from repro.core.cluster import DEFAULT_NODES, SimBackend
from repro.core.profiling import NodeProfile, ProfilingTable
from repro.core.requests import InferenceRequest
from repro.core.resource_manager import Event, GatewayNode
from repro.core.variants import VariantPool
from repro.sched import ClusterState, get_policy

ARCH = "phi4-mini-3.8b"


def _table(nodes=DEFAULT_NODES, seq_len=512) -> ProfilingTable:
    pool = VariantPool(get_config(ARCH))
    return ProfilingTable(
        pool, [NodeProfile(n.name, n.chips, n.capability) for n in nodes],
        seq_len=seq_len)


def _timed(fn, *args, reps=20):
    fn(*args)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    return out, (time.perf_counter() - t0) / reps * 1e6


def _print(name: str, us: float, derived: str):
    print(f"{name},{us:.1f},{derived}")


# ----------------------------------------------------------------------
def bench_fig2_strategies() -> None:
    """Paper Fig. 2: one demanding request, 4 strategies -> (perf, acc)."""
    table = _table()
    backend = SimBackend(table)
    per_node_cap = table.perf[-1].min() * table.num_nodes
    lo = table.perf[0].sum()
    req = InferenceRequest(
        rid=0, num_items=650,
        perf_req=min(0.97 * per_node_cap,
                     lo + 0.5 * (table.perf[-1].sum() - lo)),
        acc_req=89.0)
    state = ClusterState.from_table(table)
    for policy in ("uniform", "uniform_apx", "asymmetric", "proportional"):
        pol = get_policy(policy)
        (plan, us) = _timed(lambda: pol.plan(state, req))
        d = plan.dispatch
        r = backend.execute(d)
        levels = "|".join(str(a.apx_level) for a in d.assignments)
        shares = "|".join(str(a.items) for a in d.assignments)
        _print(f"fig2_{policy}", us,
               f"perf={r.achieved_perf:.0f};acc={r.achieved_acc:.2f};"
               f"levels={levels};items={shares}")


def bench_fig7_workload_sweep() -> None:
    """Paper Fig. 7: 4 batch sizes x 3 (perf|acc) requirements x policies."""
    table = _table()
    backend = SimBackend(table)
    state = ClusterState.from_table(table)
    lo = table.perf[0].sum()
    cap = table.perf[-1].min() * table.num_nodes
    for items in (260, 390, 520, 650):
        for j, (pf, af) in enumerate([(0.3, 90.5), (0.6, 89.0), (0.9, 87.5)]):
            req = InferenceRequest(rid=0, num_items=items,
                                   perf_req=lo + pf * (cap * 0.97 - lo),
                                   acc_req=af)
            for policy in ("uniform", "uniform_apx", "asymmetric",
                           "proportional"):
                pol = get_policy(policy)
                (plan, us) = _timed(lambda: pol.plan(state, req), reps=5)
                r = backend.execute(plan.dispatch)
                _print(f"fig7_b{items}_r{j}_{policy}", us,
                       f"perf={r.achieved_perf:.0f}/{req.perf_req:.0f};"
                       f"acc={r.achieved_acc:.2f}/{req.acc_req:.1f}")


def bench_fig8_violations() -> None:
    """Paper Fig. 8: average violation rates over the varying workload."""
    rng = np.random.default_rng(0)
    for policy in ("uniform", "uniform_apx", "asymmetric", "proportional",
                   "exact_oracle"):
        table = _table()
        backend = SimBackend(table)
        gn = GatewayNode(table, backend, policy=policy)
        gn.startup()
        lo = table.perf[0].sum()
        cap = table.perf[-1].min() * table.num_nodes
        t0 = time.perf_counter()
        for i in range(24):
            req = InferenceRequest(
                rid=i, num_items=int(rng.choice([260, 390, 520, 650])),
                perf_req=rng.uniform(lo * 1.02, cap * 0.95),
                acc_req=rng.uniform(87.0, 90.0))
            gn.handle(Event(kind="workload", request=req))
        us = (time.perf_counter() - t0) / 24 * 1e6
        s = gn.summary()
        _print(f"fig8_{policy}", us,
               f"perf_viol={s['perf_violation_rate']:.3f};"
               f"acc_viol={s['acc_violation_rate']:.3f};"
               f"mean_acc={s['mean_acc']:.2f}")


def bench_fig9_availability() -> None:
    """Paper Fig. 9: progressive node disconnection, batch = 650 images."""
    for policy in ("uniform", "uniform_apx", "asymmetric", "proportional"):
        table = _table()
        backend = SimBackend(table)
        gn = GatewayNode(table, backend, policy=policy)
        gn.startup()
        req = InferenceRequest(rid=0, num_items=650,
                               perf_req=table.perf[2].sum() * 0.85,
                               acc_req=86.0)
        out = []
        us = 0.0
        for k, victim in enumerate([None, "slice-d", "slice-c", "slice-b"]):
            if victim:
                gn.handle(Event(kind="disconnect", node=victim))
            t0 = time.perf_counter()
            r = gn.handle(Event(kind="workload", request=req))
            us = (time.perf_counter() - t0) * 1e6
            out.append(f"n{4-k}:perf={r.achieved_perf:.0f}"
                       f"acc={r.achieved_acc:.1f}")
        _print(f"fig9_{policy}", us, ";".join(out))


def bench_dispatch_latency() -> None:
    """Algorithm 1 cost vs cluster size (the GN's online decision path)."""
    for n_nodes in (4, 8, 16, 64, 256):
        rng = np.random.default_rng(n_nodes)
        nodes = [NodeProfile(f"n{i}", chips=int(rng.integers(8, 128)),
                             capability=float(rng.uniform(0.6, 1.0)))
                 for i in range(n_nodes)]
        table = _table(nodes)
        lo = table.perf[0].sum()
        req = InferenceRequest(rid=0, num_items=10_000, perf_req=lo * 1.5,
                               acc_req=88.0)
        state = ClusterState.from_table(table)
        pol = get_policy("proportional")
        (_, us) = _timed(lambda: pol.plan(state, req), reps=10)
        _print(f"dispatch_latency_n{n_nodes}", us, f"nodes={n_nodes}")


def bench_kernels() -> None:
    """Interpret-mode wall time (CPU) per kernel + analytic work terms.
    These time the Pallas interpreter, not a chip: ``chip_smoke.py`` runs
    the compiled kernels on a TPU."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import decode_attention
    from repro.kernels.flash_attention import flash_attention

    rng = jax.random.PRNGKey(0)
    B, H, KV, S, D = 1, 8, 4, 512, 64
    q = jax.random.normal(rng, (B, H, S, D), jnp.float32)
    k = jax.random.normal(rng, (B, KV, S, D), jnp.float32)
    v = jax.random.normal(rng, (B, KV, S, D), jnp.float32)
    fa = jax.jit(lambda q, k, v: flash_attention(q, k, v, interpret=True,
                                                 block_q=128, block_k=128))
    (_, us) = _timed(lambda: jax.block_until_ready(fa(q, k, v)), reps=3)
    flops = 4 * B * H * S * S * D / 2
    _print("kernel_flash_attention_interp", us, f"flops={flops:.2e}")

    qd = jax.random.normal(rng, (B, KV, H // KV, D), jnp.float32)
    mask = jnp.ones((B, S), bool)
    da = jax.jit(lambda q, k, v, m: decode_attention(q, k, v, m,
                                                     interpret=True,
                                                     block_k=128))
    kc, vc = jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2)  # cache layout
    (_, us) = _timed(lambda: jax.block_until_ready(da(qd, kc, vc, mask)),
                     reps=3)
    bytes_ = 2 * B * KV * S * D * 4
    _print("kernel_decode_attention_interp", us, f"kv_bytes={bytes_:.2e}")


def bench_heterogeneity_sweep() -> None:
    """Beyond-paper: how the proportional policy's advantage over the
    baselines scales with cluster heterogeneity (capability spread)."""
    rng = np.random.default_rng(1)
    for spread in (1.0, 1.5, 2.0, 3.0, 5.0):
        # 4 nodes, capabilities log-spaced over [1/spread, 1]
        caps = np.geomspace(1.0 / spread, 1.0, 4)
        nodes = [NodeProfile(f"n{i}", chips=64, capability=float(c))
                 for i, c in enumerate(caps)]
        table = _table(nodes)
        backend = SimBackend(table)
        state = ClusterState.from_table(table)
        lo = table.perf[0].sum()
        cap = table.perf[-1].min() * 4
        results = {}
        for policy in ("uniform_apx", "proportional"):
            accs, met = [], 0
            for i in range(12):
                perf = rng.uniform(lo * 1.02, max(cap * 0.95, lo * 1.05))
                req = InferenceRequest(rid=i, num_items=520, perf_req=perf,
                                       acc_req=0.0)
                r = backend.execute(
                    get_policy(policy).plan(state, req).dispatch)
                accs.append(r.achieved_acc)
                met += r.meets_perf
            results[policy] = (np.mean(accs), met)
        adv = results["proportional"][0] - results["uniform_apx"][0]
        _print(f"hetero_spread_{spread}", 0.0,
               f"acc_advantage={adv:.2f};prop_met={results['proportional'][1]}/12;"
               f"uapx_met={results['uniform_apx'][1]}/12")


def main() -> None:
    print("name,us_per_call,derived")
    bench_fig2_strategies()
    bench_fig7_workload_sweep()
    bench_fig8_violations()
    bench_fig9_availability()
    bench_dispatch_latency()
    bench_heterogeneity_sweep()
    bench_kernels()


if __name__ == "__main__":
    main()
