"""One run of one cell: set-up and warm-up, the measured window, the
optional traced phase, then the comparison that decides ``correct``.

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``, the configuration file, the traffic file
(``traffic.py`` reads it), the plain reference (``reference/<name>.py``,
named by the configuration file) and one reader per per-layer metric
(``metrics/<name>.py``). The program is driven only through ``entry``.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

import compare
import entry
import peaks as peaks_mod
import trace_reduce
import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
TRACE_REQUESTS = 2          # requests served under the profiler, after the window


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    traffic: str
    config_file: str
    per_layer: List[Dict]
    end_to_end: List[Dict]


def find_cell(bench: Dict, name: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]

    def mine(m):
        return name in m.get("workloads", [name])
    return Cell(name=name, chips=w["chips"], traffic=w["traffic"],
                config_file=os.path.join(entry.ROOT, conf["file"]),
                per_layer=[m for m in bench["per_layer"] if mine(m)],
                end_to_end=[m for m in bench["end_to_end"] if mine(m)])


def _mismatches(cfg, want: Dict, where: str = "") -> List[str]:
    """Dotted names of ``cfg`` (``"moe.top_k"``) whose values are not
    ``want``'s."""
    out = []
    for key, value in want.items():
        got = cfg
        for part in key.split("."):
            got = getattr(got, part)
        if got != value:
            out.append(f"{where}{key}: program {got!r}, file {value!r}")
    return out


def check_served_config(doc: Dict, cfg) -> None:
    """The program has to serve what the configuration file states: the
    model's sizes and every level of its ladder, as the file's reference
    reads them (``served_config``, ``served_ladder``: dotted names of the
    program's config and the values they must hold)."""
    ref = importlib.import_module(f"reference.{doc['reference']}")
    bad = _mismatches(cfg, ref.served_config(doc))
    pool = entry.VariantPool(cfg)
    if len(pool) != len(doc["ladder"]):
        bad.append(f"ladder: program {len(pool)} levels, file "
                   f"{len(doc['ladder'])}")
    for v, lv in zip(pool.variants, doc["ladder"]):
        bad += _mismatches(v.config, ref.served_ladder(doc, v.level),
                           f"level {v.level} ")
        if abs(v.accuracy - lv["accuracy"]) > 1e-9:
            bad.append(f"level {v.level} accuracy: program {v.accuracy}, "
                       f"file {lv['accuracy']}")
    if bad:
        raise SystemExit("the program's config differs from "
                         f"{doc['arch']}'s file: " + "; ".join(bad))


@dataclasses.dataclass
class Record:
    spec: traffic.RequestSpec
    dispatch: object
    result: object
    shares: list
    plan_s: float
    run_s: float
    gc_s: float = 0.0           # Python's garbage collection inside the request

    @property
    def latency_s(self) -> float:
        return self.plan_s + self.run_s

    @property
    def tokens(self) -> int:
        return sum(s.served * (entry.PROMPT_LEN + np.shape(s.tokens)[1])
                   for s in self.shares)


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader sees."""
    records: List[Record]
    traced: List[Record]
    trace: Optional[trace_reduce.TraceSummary]
    doc: Dict
    peaks: peaks_mod.Peaks
    chips: int
    prompt_len: int = entry.PROMPT_LEN
    decode_steps: int = entry.DECODE_STEPS


class GcClock:
    """Seconds Python's garbage collector has run since it was installed."""

    def __init__(self):
        self.total = 0.0
        self._start = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._start = time.perf_counter()
        else:
            self.total += time.perf_counter() - self._start

    def remove(self):
        gc.callbacks.remove(self)


def serve(gn, runner, spec: traffic.RequestSpec, annotate,
          gc_clock: Optional[GcClock] = None) -> Record:
    req = entry.InferenceRequest(rid=spec.rid, num_items=spec.num_items,
                                 perf_req=spec.perf_req, acc_req=spec.acc_req)
    gc0 = gc_clock.total if gc_clock else 0.0
    t0 = time.perf_counter()
    with annotate("bench.plan"):
        result = gn.handle(entry.Event(kind="workload", request=req))
    t1 = time.perf_counter()
    d = gn.dispatches[-1]
    with annotate("bench.run"):
        shares = runner.run(d)
    t2 = time.perf_counter()
    return Record(spec=spec, dispatch=d, result=result, shares=shares,
                  plan_s=t1 - t0, run_s=t2 - t1,
                  gc_s=(gc_clock.total - gc0) if gc_clock else 0.0)


def warmup_dispatches(cfg, doc: Dict, spec: Dict, nodes: Sequence[str],
                      full_cap: float, max_cap: float) -> List:
    """One dispatch per level the mix reaches, with one share of each batch
    size the mix's plans give on each chip that level is planned on.
    Plans are the gateway's own, on a scratch gateway."""
    gn = entry.build_gateway(cfg, policy=doc["policy"])
    idx = {n: j for j, n in enumerate(nodes)}
    reach = set()
    for i, p in enumerate(traffic.pool(spec, full_cap, max_cap)):
        gn.handle(entry.Event(kind="workload", request=entry.InferenceRequest(
            rid=i, **p)))
        for a in gn.dispatches[-1].assignments:
            if a.items > 0:
                reach.add((a.apx_level, doc["layout"][idx[a.node]],
                           min(a.items, entry.SHARE_ITEMS)))
    out = []
    for level in sorted({lv for lv, _, _ in reach}):
        for n in sorted({n for lv, _, n in reach if lv == level}):
            chips = sorted({c for lv, c, m in reach if (lv, m) == (level, n)})
            node_on = {c: nodes[doc["layout"].index(c)] for c in chips}
            req = entry.InferenceRequest(rid=0, num_items=n * len(chips),
                                         perf_req=1.0, acc_req=0.0)
            out.append(entry.Dispatch(request=req, policy="warmup",
                                      assignments=tuple(entry.Assignment(
                                          node=node_on[c], items=n,
                                          apx_level=level, perf_alloc=0.0)
                                          for c in chips)))
    return out


def judge(numbers: Dict[str, float], limits: Dict) -> Dict[str, Dict]:
    return {k: {"value": float(v), "limit": float(limits[k])}
            for k, v in numbers.items()}


def _checks_line(checks: Dict[str, Dict]) -> None:
    for k, v in checks.items():
        log(f"check {k} = {v['value']!r} (limit {v['limit']!r})")


def weights_differing(doc: Dict, runner) -> Dict[int, int]:
    """Elements in which the weights the reference draws differ from the
    ones each chip serves at its resident level (0 expected), over every
    group of parameters the reference names."""
    ref = importlib.import_module(f"reference.{doc['reference']}")
    out = {}
    for level, eng in runner.resident.values():
        bad = sum(int(np.sum(drawn != served)) for _, drawn, served in
                  ref.weights(doc, level).against(eng.params))
        out[int(level)] = out.get(int(level), 0) + bad
    return out


def run(cell: Cell, doc: Dict, cfg, *, seed: int, seconds: float,
        trace: bool, devices: Sequence, t_start: float,
        control: bool = False) -> Dict:
    """One run. ``control`` also reads the control (the reference in
    float8, in the program's place) on the same sample, judges it by the
    same limits, and counts the served weights that differ from the
    reference's; the benchmark's own runs leave it off."""
    import jax

    spec = traffic.load(cell.traffic)
    device_peaks = peaks_mod.peaks_for(devices[0].device_kind) \
        if devices[0].platform == "tpu" else None
    check_served_config(doc, cfg)
    gn = entry.build_gateway(cfg, policy=doc["policy"])
    nodes = [n.name for n in gn.table.nodes]
    layout = doc["layout"]
    if len(layout) != len(nodes) or max(layout) >= cell.chips:
        raise SystemExit(f"layout {layout} does not fit {len(nodes)} nodes "
                         f"on {cell.chips} chips")
    used = devices[:cell.chips]
    runner = entry.ShareRunner(cfg, {n: used[c] for n, c in
                                     zip(nodes, layout)})
    full_cap = float(gn.table.perf[0].sum())
    max_cap = float(gn.table.perf[-1].sum())
    annotate = jax.profiler.TraceAnnotation

    with annotate("bench.warmup"):
        for d in warmup_dispatches(cfg, doc, spec, nodes, full_cap, max_cap):
            runner.run(d)
    setup_s = time.perf_counter() - t_start
    log(f"setup_s {setup_s:.3f}")

    passes = traffic.passes(spec, seed, full_cap, max_cap)
    records: List[Record] = []
    gc_clock = GcClock()
    t0 = time.perf_counter()
    longest = 0.0
    while True:
        # whole passes of the pool, so every seed serves the same requests:
        # another pass starts while it is expected to end within `seconds`
        tp = time.perf_counter()
        records.extend(serve(gn, runner, r, annotate, gc_clock)
                       for r in next(passes))
        now = time.perf_counter()
        longest = max(longest, now - tp)
        if now - t0 + longest > seconds:
            break
    window_s = now - t0
    gc_clock.remove()
    log(f"requests in window: {len(records)} "
        f"({len(records) // spec['pool_size']} passes) in {window_s:.3f} s")
    slow = sorted(records, key=lambda r: -r.latency_s)[:3]
    log("slowest requests (latency, gc, level builds s): " + ", ".join(
        f"({r.latency_s:.4f}, {r.gc_s:.4f}, "
        f"{sum(s.build_s for s in r.shares):.4f})" for r in slow))

    summary, traced = None, []
    if trace:
        tdir = tempfile.mkdtemp(prefix="bench-trace-")
        try:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
            try:
                with annotate(trace_reduce.WINDOW_SPAN):
                    for r in next(passes)[:TRACE_REQUESTS]:
                        traced.append(serve(gn, runner, r, annotate))
            finally:
                jax.profiler.stop_trace()
            summary = trace_reduce.summarize(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)
        log(f"trace: window {summary.window_s:.4f} s, busy per chip "
            f"{[round(b, 4) for b in summary.busy_s]}")

    mem = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in used]
    differing = weights_differing(doc, runner) if control else None
    runner.close()
    del runner
    gc.collect()

    # the comparison, once the window has closed and the program's state
    # is freed
    limits = doc["limits"]
    perf = np.asarray(gn.table.perf)
    numbers = compare.plan_checks(records, doc, perf, entry.SHARE_ITEMS,
                                  entry.DECODE_STEPS)
    idx = {n: j for j, n in enumerate(nodes)}
    sampled = [{"level": s.level, "tokens": np.asarray(s.tokens),
                "logits": np.asarray(s.logits),
                "prompts": compare.prompts(r.spec.rid, idx[s.node], s.served,
                                           doc["vocab_size"],
                                           entry.PROMPT_LEN)}
               for r in compare.sample(records, seed) for s in r.shares]
    t_ref = time.perf_counter()
    numbers.update(compare.model_checks(sampled, doc, entry.PROMPT_LEN))
    log(f"reference: {len(sampled)} shares, "
        f"{sum(len(s['tokens']) * s['tokens'].shape[1] for s in sampled)} "
        f"served tokens, {time.perf_counter() - t_ref:.1f} s")
    checks = judge(numbers, limits)
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    failed = 0
    for r in records:
        one = compare.plan_checks([r], doc, perf, entry.SHARE_ITEMS,
                                  entry.DECODE_STEPS)
        failed += bool(one["plan_mismatch"] or one["acc_shortfall"])
    ctl = None
    if control:
        # the control in the program's place: the plan checks are the
        # program's own, the model checks the float8 reference's
        ctl_numbers = dict(numbers, **compare.model_checks(
            sampled, doc, entry.PROMPT_LEN, control=True))
        ctl_checks = judge(ctl_numbers, limits)
        ctl = {"correct": all(c["value"] <= c["limit"]
                              for c in ctl_checks.values()),
               "checks": ctl_checks, "weights_differing": differing}
        log(f"control: correct {ctl['correct']}, weights differing "
            f"{differing}, " + ", ".join(
                f"{k} {v['value']!r}" for k, v in ctl_checks.items()))

    lat = [r.latency_s for r in records]
    metrics: Dict[str, Dict] = {}
    if not trace:
        values = {
            "tokens_per_s": sum(r.tokens for r in records) / window_s,
            "request_p50_s": float(np.percentile(lat, 50)),
            "request_p95_s": float(np.percentile(lat, 95)),
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = Context(records=records, traced=traced, trace=summary,
                      doc=doc, peaks=device_peaks, chips=cell.chips)
        for m in cell.per_layer:
            reader = importlib.import_module(f"metrics.{m['name']}")
            v = reader.read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    dev = devices[0]
    out = {"correct": correct, "attempted": len(records), "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(devices),
                      "memory_peak_bytes": int(max(mem))}}
    if trace:
        out["device"]["busy_s"] = summary.mean_busy_s
        out["device"]["window_s"] = summary.window_s
        out["breakdown"] = trace_reduce.breakdown(summary)
    if ctl is not None:
        out["control"] = ctl
    out["checks"] = checks
    _checks_line(checks)
    return out
