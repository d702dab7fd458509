"""End-to-end serving driver: the full paper system with REAL JAX inference.

Reduced-scale replica of the production deployment: the Gateway Node
profiles a heterogeneous cluster, receives a request trace with per-request
(perf | accuracy) constraints, runs Algorithm 1, and each Local Node share
executes real batched prefill+decode through the serving engine with the
dispatched accuracy variant. A node disconnect mid-trace exercises the
fault path (paper Fig. 9).

Run:  PYTHONPATH=src python examples/serve_cluster.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.core.cluster import DEFAULT_NODES, SimBackend
from repro.core.profiling import NodeProfile, ProfilingTable
from repro.core.requests import InferenceRequest
from repro.core.resource_manager import Event, GatewayNode
from repro.core.variants import VariantPool
from repro.serving.engine import (BatchScheduler, Engine, EngineConfig,
                                  init_params_on)


def main():
    arch = "phi4-mini-3.8b"
    # dispatch decisions use the FULL config's profiling table (production
    # scale); the Local-Node engines run the reduced smoke variants so the
    # whole pipeline executes for real on CPU.
    pool_full = VariantPool(get_config(arch))
    pool = VariantPool(get_smoke_config(arch))

    nodes = [NodeProfile(n.name, n.chips, n.capability)
             for n in DEFAULT_NODES]
    table = ProfilingTable(pool_full, nodes, seq_len=512)
    gn = GatewayNode(table, SimBackend(table), policy="proportional")
    gn.startup()
    print("gateway profiled", len(nodes), "worker groups; policy=proportional")

    # engines per (node, variant) built lazily — a real fleet keeps one
    # engine per group and hot-swaps variant weights on dispatch change
    rng = jax.random.PRNGKey(0)
    engines = {}

    def engine_for(node: str, level: int) -> Engine:
        key = (node, level)
        if key not in engines:
            vcfg = pool[level].config
            # bf16 serving weights, drawn on the device; one seed per level
            params = init_params_on(vcfg, jax.random.PRNGKey(level),
                                    jax.devices()[0])
            engines[key] = Engine(vcfg, params, EngineConfig(max_len=48))
        return engines[key]

    trace_rng = np.random.default_rng(7)
    lo = table.perf[0].sum()
    cap = table.perf[-1].min() * table.num_nodes
    n_requests = 5
    for i in range(n_requests):
        if i == 3:
            gn.handle(Event(kind="disconnect", node="slice-d"))
            print("\n!! slice-d disconnected — GN re-enters Distribute")
        req = InferenceRequest(
            rid=i, num_items=int(trace_rng.choice([260, 390, 520])),
            perf_req=trace_rng.uniform(lo * 1.02, cap * 0.95),
            acc_req=trace_rng.uniform(87.5, 90.0))
        res = gn.handle(Event(kind="workload", request=req))
        d = gn.dispatches[-1]
        print(f"\nR{i}: {req.num_items} seqs, perf>={req.perf_req:.0f}, "
              f"acc>={req.acc_req:.1f} -> "
              f"perf={res.achieved_perf:.0f} acc={res.achieved_acc:.2f} "
              f"{'OK' if res.meets_perf and res.meets_acc else 'VIOLATION'}")
        # Local Node Inference state: run each share for real (first 4 seqs
        # of each share on CPU; a real group runs them all)
        for a in d.assignments:
            if a.items == 0:
                continue
            eng = engine_for(a.node, a.apx_level)
            sched = BatchScheduler(batch_size=4)
            for s in range(min(a.items, 4)):
                sched.add(np.arange(1 + s % 7, dtype=np.int32) + 1)
            batch = sched.next_batch()
            t0 = time.time()
            out = eng.generate(jnp.asarray(batch), num_steps=6)
            dt = time.time() - t0
            print(f"   {a.node}: level {a.apx_level} "
                  f"({pool[a.apx_level].config.d_ff}-wide) "
                  f"{a.items} seqs -> sample tokens {out[0][:4].tolist()} "
                  f"({dt*1e3:.0f}ms real)")
    print("\nsummary:", {k: round(v, 4) for k, v in gn.summary().items()})


if __name__ == "__main__":
    main()
