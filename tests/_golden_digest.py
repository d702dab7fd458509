"""Canonical digest of a simulator run — the tenants=1 byte-identity pin.

Multi-tenancy must be *zero-cost when off*: a run with every request on
the default tenant has to produce the identical records, log lines, and
summary the pre-tenancy simulator produced. This module computes a
stable sha256 over exactly those three surfaces; the committed
``tests/golden/sim_digest.json`` was generated from the pre-tenancy
tree, and ``tests/test_tenants.py`` recomputes the digests on every run.

Float formatting relies on Python's shortest-roundtrip ``repr`` (stable
since 3.1) and the simulator's metrics are all sim-clock quantities, so
the digests are machine-independent.
"""
from __future__ import annotations

import hashlib
import json

from repro.configs import get_config
from repro.control import AdmissionController, Autoscaler
from repro.core.cluster import SimBackend, cluster_nodes
from repro.core.profiling import ProfilingTable
from repro.core.resource_manager import GatewayNode
from repro.core.variants import VariantPool
from repro.sim import OnlineSimulator, build_scenario

ARCH = "phi4-mini-3.8b"
HORIZON_S = 6.0
SEED = 0
NUM_STANDBY = 2
DIGEST_CASES = tuple(
    (scenario, "proportional", control)
    for scenario in ("steady", "diurnal", "node-churn", "straggler-storm",
                     "overload", "flash-crowd")
    for control in ("none", "full"))


def run_report(scenario: str, policy: str, control: str):
    """One simulator run, constructed exactly like run_sim.run_one's
    unsharded branch (seed 0, horizon 6, two standby slices)."""
    pool = VariantPool(get_config(ARCH))
    table = ProfilingTable(pool, cluster_nodes(NUM_STANDBY), seq_len=512)
    sc = build_scenario(scenario, table, seed=SEED, horizon_s=HORIZON_S)
    gn = GatewayNode(table, SimBackend(table, noise_std=0.0, seed=SEED),
                     policy=policy)
    admission = None
    if control in ("admission", "full"):
        admission = AdmissionController(table, rate=None)
    autoscaler = None
    if control in ("autoscale", "full"):
        standby = [n.name for n in table.nodes if not n.available]
        autoscaler = Autoscaler(table, standby)
    sim = OnlineSimulator(gn, sc.arrivals, sc.faults, scenario=sc.name,
                          horizon_s=sc.horizon_s, admission=admission,
                          autoscaler=autoscaler)
    return sim.run()


SECTIONS = ("records", "log", "summary")


def _surfaces(report):
    """The three digested surfaces, in the exact shapes the original
    combined digest serialized (wall-clock and event-count fields
    excluded — they are host-speed trivia, not serving behaviour)."""
    records = [
        (int(r.request.rid), repr(r.arrival_s), repr(r.dispatch_s),
         repr(r.finish_s), bool(r.rejected), r.reject_reason,
         bool(r.degraded_admission), int(r.redistributed),
         repr(r.latency_s) if r.done else "",
         bool(r.meets_deadline) if r.done else None)
        for r in report.records]
    summary = sorted(
        (k, repr(v)) for k, v in report.summary().items()
        if k not in ("wall_s", "n_events"))
    return records, list(report.log), summary


def report_digest(report) -> str:
    """sha256 over the run's records + log + summary — byte-identical to
    the digest the pre-tenancy tree committed."""
    records, log, summary = _surfaces(report)
    blob = json.dumps({"records": records, "log": log,
                       "summary": summary}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


def section_lines(report) -> dict:
    """Each surface as a list of one-line strings — one record / log
    line / summary pair per line — so a digest mismatch can be localized
    to a single line instead of 'some byte somewhere changed'."""
    records, log, summary = _surfaces(report)
    return {"records": [json.dumps(r) for r in records],
            "log": log,
            "summary": [json.dumps(kv) for kv in summary]}


def _line_hash(line: str) -> str:
    return hashlib.sha256(line.encode()).hexdigest()[:12]


def digest_entry(report) -> dict:
    """The v2 golden entry: the original combined sha plus per-section
    shas and per-line short hashes for failure localization."""
    lines = section_lines(report)
    return {
        "combined": report_digest(report),
        "sections": {
            name: hashlib.sha256("\n".join(ls).encode()).hexdigest()
            for name, ls in lines.items()},
        "lines": {name: [_line_hash(ln) for ln in ls]
                  for name, ls in lines.items()},
    }


def describe_mismatch(report, committed) -> str:
    """Human-usable failure message: which section diverged and the
    first differing line of the *current* run (the golden stores line
    hashes, so the committed content itself is not recoverable)."""
    got = digest_entry(report)
    if isinstance(committed, str):  # v1 golden: bare combined sha
        return (f"combined digest diverged: {got['combined']} != "
                f"{committed} (v1 golden entry carries no section "
                f"detail; regenerate with python tests/_golden_digest.py)")
    out = [f"combined digest diverged: {got['combined']} != "
           f"{committed['combined']}"]
    lines = section_lines(report)
    for name in SECTIONS:
        if got["sections"][name] == committed["sections"][name]:
            continue
        want_hashes = committed["lines"][name]
        got_hashes = got["lines"][name]
        n_want, n_got = len(want_hashes), len(got_hashes)
        idx = next((i for i, (a, b)
                    in enumerate(zip(got_hashes, want_hashes)) if a != b),
                   min(n_got, n_want))
        out.append(f"  section '{name}' diverged "
                   f"({n_got} lines now vs {n_want} golden), "
                   f"first difference at line {idx}:")
        if idx < n_got:
            out.append(f"    now: {lines[name][idx]}")
        else:
            out.append(f"    now: <section ended; golden has "
                       f"{n_want - n_got} more line(s)>")
    return "\n".join(out)


def compute_digests() -> dict:
    return {f"{s}/{p}/{c}": digest_entry(run_report(s, p, c))
            for s, p, c in DIGEST_CASES}


if __name__ == "__main__":
    import pathlib
    out = pathlib.Path(__file__).parent / "golden" / "sim_digest.json"
    entries = compute_digests()
    if out.exists():  # the combined shas are a pin — never drift silently
        old = json.loads(out.read_text())
        for key, entry in entries.items():
            prev = old.get(key)
            prev = prev["combined"] if isinstance(prev, dict) else prev
            if prev is not None and prev != entry["combined"]:
                raise SystemExit(
                    f"refusing to overwrite {key}: combined digest "
                    f"changed {prev} -> {entry['combined']} "
                    f"(delete the golden first if this is intentional)")
    out.write_text(json.dumps(entries, indent=2, sort_keys=True) + "\n")
    print(f"wrote {out} ({len(entries)} cases)")
