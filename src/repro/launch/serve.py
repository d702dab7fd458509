"""Serving launcher: the paper's full system — heterogeneous worker groups,
profiling, Gateway dispatch (Algorithm 1), accuracy-configured variants.

  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --policy proportional --requests 6

The gateway plans every request with the analytic profiling table
(SimBackend). With ``--smoke`` each node's share is then served by a real
engine through :class:`ShareRunner`: at the smoke config on the CPU (kernels
interpreted), at the full published config on a TPU. Node j's engine sits
on device j mod the device count.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_NAMES, ModelConfig, get_config, get_smoke_config
from repro.core.cluster import DEFAULT_NODES, SimBackend
from repro.sched import registered_policies
from repro.core.profiling import NodeProfile, ProfilingTable
from repro.core.requests import Dispatch, InferenceRequest
from repro.core.resource_manager import Event, GatewayNode
from repro.core.variants import VariantPool
from repro.launch.compile_cache import enable_compile_cache
from repro.serving import spans
from repro.serving.engine import Engine, EngineConfig, init_params_on

# Each share runs one engine batch of at most SHARE_ITEMS prompts of
# PROMPT_LEN tokens (the profiling table's seq_len), then DECODE_STEPS
# greedy decode steps.
SHARE_ITEMS = 8
PROMPT_LEN = 512
DECODE_STEPS = 4
# decode cache slots: the decode kernel tiles the cache in multiples of 128
CACHE_LEN = -(-(PROMPT_LEN + DECODE_STEPS) // 128) * 128


def build_gateway(cfg, *, policy: str = "proportional",
                  nodes=DEFAULT_NODES, seq_len: int = PROMPT_LEN,
                  noise_std: float = 0.0, seed: int = 0) -> GatewayNode:
    pool = VariantPool(cfg)
    node_profiles = [NodeProfile(n.name, n.chips, n.capability) for n in nodes]
    table = ProfilingTable(pool, node_profiles, seq_len=seq_len)
    backend = SimBackend(table, noise_std=noise_std, seed=seed)
    gn = GatewayNode(table, backend, policy=policy)
    gn.startup()
    return gn


def demo_requests(gn: GatewayNode, n: int, seed: int = 0) -> List[InferenceRequest]:
    """Paper §IV-B style scenario generator: perf_req between full-accuracy
    capacity and max-approximation capacity; acc_req in a feasible band."""
    rng = np.random.default_rng(seed)
    full_cap = gn.table.perf[0].sum()
    max_cap = gn.table.perf[-1].sum()
    out = []
    for i in range(n):
        perf = rng.uniform(0.9 * full_cap, 0.95 * max_cap)
        acc = rng.uniform(86.0, 90.5)
        items = int(rng.choice([260, 390, 520, 650]))
        out.append(InferenceRequest(rid=i, num_items=items,
                                    perf_req=perf, acc_req=acc))
    return out


@dataclasses.dataclass
class ShareResult:
    """One node's share of one request, as its engine served it. The
    seconds are the durations of the share's spans (``ShareRunner``)."""
    node: str
    level: int
    device: str
    items: int                 # items the plan gave the node
    served: int                # items the engine ran: min(items, SHARE_ITEMS)
    logits: np.ndarray         # (served, vocab) prefill logits, float32
    tokens: np.ndarray         # (served, decode_steps) greedy tokens
    build_s: float             # ``runner.build``: drawing the level's weights; 0 when resident
    compile_s: Dict[str, float]   # per program (``engine.aot_*``); near 0 when compiled
    prefill_s: float           # ``engine.prefill``
    decode_step_s: float       # ``engine.decode`` / DECODE_STEPS
    rid: Optional[int] = None  # the request's id
    spans: Tuple[spans.Span, ...] = ()   # ``runner.share`` first
    compiles: int = 0          # programs compiled or loaded during the share
    # configs with experts: rows the prefill routed to held experts, and
    # rows its grouped matmuls were given, summed over the expert layers
    expert_rows: int = 0
    expert_slots: int = 0


class ShareRunner:
    """Serves each share of a dispatch on a real engine.

    ``placement`` maps each node to the device its engine sits on (see
    :func:`place_nodes`); several nodes may share a device. A device holds
    one level at a time (one full-width level fills most of a 16 GB chip):
    the engine for the level the plan assigns is built there, after the
    previous level's weights are freed. Shares that share a device run in
    level order, so that a request swaps each level in at most once.

    The shares of a dispatch that sit on different devices run at the same
    time, each device's shares in order on a host thread of their own (one
    worker per device the placement uses), so that every share's spans
    still time its own device work. A dispatch on one device runs on the
    calling thread.

    Each share runs one engine batch of ``min(items, SHARE_ITEMS)`` prompts
    of ``PROMPT_LEN`` tokens, then ``DECODE_STEPS`` greedy decode steps.
    Prompts are drawn from ``(rid, node)`` and weights from the level, so the
    same dispatch gives the same inputs under any placement.
    """

    def __init__(self, cfg: ModelConfig, placement: Dict[str, jax.Device]):
        self.pool = VariantPool(cfg)
        self.placement = dict(placement)
        self._node_idx = {name: j for j, name in enumerate(self.placement)}
        self.ecfg = EngineConfig(max_len=CACHE_LEN)
        self.resident: Dict[jax.Device, Tuple[int, Engine]] = {}
        devices = set(self.placement.values())
        self._pool = (concurrent.futures.ThreadPoolExecutor(
            len(devices), thread_name_prefix="share-runner")
            if len(devices) > 1 else None)

    def _engine(self, device: jax.Device, level: int) -> Engine:
        """The engine for ``level`` on ``device``. Swapping it in is
        ``runner.release`` (freeing the resident level), then
        ``runner.build`` (drawing the new weights: ``build_s``)."""
        cur = self.resident.get(device)
        if cur is not None and cur[0] == level:
            return cur[1]
        if cur is not None:
            with spans.record("runner.release"):
                cur[1].release()
        vcfg = self.pool[level].config
        with spans.record("runner.build"):
            rng = jax.random.fold_in(jax.random.PRNGKey(0), level)
            params = jax.block_until_ready(init_params_on(vcfg, rng, device))
        eng = Engine(vcfg, params, self.ecfg, device=device)
        self.resident[device] = (level, eng)
        return eng

    def _prompts(self, rid: int, node: str, n: int, vocab: int) -> np.ndarray:
        rng = np.random.default_rng([rid, self._node_idx[node]])
        return rng.integers(0, vocab, (n, PROMPT_LEN), dtype=np.int32)

    def run(self, d: Dispatch) -> List[ShareResult]:
        """Serve every share with items; results in (device, level) order.
        An error in any share is raised here once every device is done."""
        rid = d.request.rid
        shares = [a for a in d.assignments if a.items > 0]
        shares.sort(key=lambda a: (self.placement[a.node].id, a.apx_level))
        groups: Dict[jax.Device, list] = {}
        for a in shares:
            groups.setdefault(self.placement[a.node], []).append(a)
        with spans.record("runner.run"):
            if len(groups) <= 1:
                return [self._serve(rid, a) for a in shares]
            futures = [self._pool.submit(self._serve_all, rid, group)
                       for group in groups.values()]
            concurrent.futures.wait(futures)
            return [r for f in futures for r in f.result()]

    def _serve_all(self, rid: int, shares) -> List[ShareResult]:
        return [self._serve(rid, a) for a in shares]

    def _serve(self, rid: int, a) -> ShareResult:
        device = self.placement[a.node]
        compiled = spans.thread_compiles()
        with spans.collect("runner.share") as got:
            eng = self._engine(device, a.apx_level)
            n = min(a.items, SHARE_ITEMS)
            with spans.record("runner.prompts"):
                tokens = jax.device_put(
                    self._prompts(rid, a.node, n, eng.cfg.vocab_size), device)
            eng.compile(tokens)

            with spans.record("engine.prefill"):
                logits, caches, lengths, counts = eng.prefill(tokens)
                jax.block_until_ready((logits, caches))

            first = logits
            out = []
            with spans.record("engine.decode"):
                for _ in range(DECODE_STEPS):
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    out.append(tok)
                    logits, caches, lengths = eng.decode(caches, lengths, tok)
                jax.block_until_ready((out, logits))

            with spans.record("runner.fetch"):
                first = np.asarray(first, np.float32)
                out = np.stack([np.asarray(t) for t in out], axis=1)
                rows, slots = (0, 0) if counts is None else np.asarray(counts)
        got = tuple(got)
        return ShareResult(
            node=a.node, level=a.apx_level, device=str(device), items=a.items,
            served=n, logits=first, tokens=out,
            build_s=spans.seconds(got, "runner.build"),
            compile_s={"prefill": spans.seconds(got, "engine.aot_prefill"),
                       "decode": spans.seconds(got, "engine.aot_decode")},
            prefill_s=spans.seconds(got, "engine.prefill"),
            decode_step_s=spans.seconds(got, "engine.decode") / DECODE_STEPS,
            rid=rid, spans=got, compiles=spans.thread_compiles() - compiled,
            expert_rows=int(rows), expert_slots=int(slots))

    def close(self):
        """Stop the device threads and free every resident engine's
        weights."""
        if self._pool is not None:
            self._pool.shutdown()
        for _, eng in self.resident.values():
            eng.release()
        self.resident.clear()


def place_nodes(nodes: Sequence[str],
                devices: Sequence[jax.Device]) -> Dict[str, jax.Device]:
    """Node j on device j mod the device count."""
    return {name: devices[j % len(devices)] for j, name in enumerate(nodes)}


def serving_config(arch: str) -> ModelConfig:
    """The config engines serve: full width on a TPU, the smoke config on
    the CPU."""
    if jax.devices()[0].platform == "tpu":
        return get_config(arch)
    return get_smoke_config(arch)


def format_share(r: ShareResult) -> str:
    comp = " ".join(f"{k}={v:.3f}s" for k, v in r.compile_s.items())
    return (f"     {r.node} level={r.level} on {r.device}: "
            f"served {r.served}/{r.items} items, build={r.build_s:.3f}s "
            f"compile[{comp}] prefill={r.prefill_s:.4f}s "
            f"decode_step={r.decode_step_s:.4f}s compiles={r.compiles}")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_NAMES, default="phi4-mini-3.8b")
    ap.add_argument("--policy", choices=tuple(registered_policies()),
                    default="proportional")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--smoke", action="store_true",
                    help="serve each share on a real engine: the smoke "
                         "config on the CPU, the full config on a TPU")
    ap.add_argument("--disconnect", action="store_true",
                    help="disconnect a node mid-trace (paper Fig. 9)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    gn = build_gateway(cfg, policy=args.policy)
    reqs = demo_requests(gn, args.requests)
    runner = None
    if args.smoke:
        enable_compile_cache()
        devices = jax.devices()
        runner = ShareRunner(serving_config(args.arch), place_nodes(
            [n.name for n in gn.table.nodes], devices))
        print(f"serving shares on {len(devices)} {devices[0].device_kind} "
              f"device(s), at most {SHARE_ITEMS} items per share")

    print(f"policy={args.policy} arch={args.arch}")
    print(f"{'rid':>3} {'items':>6} {'perf_req':>10} {'acc_req':>7} "
          f"{'perf':>10} {'acc':>6} {'ok':>5}")
    for i, r in enumerate(reqs):
        if args.disconnect and i == len(reqs) // 2:
            victim = gn.table.nodes[1].name
            gn.handle(Event(kind="disconnect", node=victim))
            print(f"-- node {victim} disconnected --")
        res = gn.handle(Event(kind="workload", request=r))
        print(f"{r.rid:3d} {r.num_items:6d} {r.perf_req:10.1f} "
              f"{r.acc_req:7.2f} {res.achieved_perf:10.1f} "
              f"{res.achieved_acc:6.2f} "
              f"{'y' if res.meets_perf and res.meets_acc else 'N':>5}")
        if runner is not None:
            for share in runner.run(gn.dispatches[-1]):
                print(format_share(share))
    if runner is not None:
        runner.close()
    print("summary:", {k: round(v, 4) for k, v in gn.summary().items()})


if __name__ == "__main__":
    main()
