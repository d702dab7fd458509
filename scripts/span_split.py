#!/usr/bin/env python3
"""Split each served request's time by the program's spans.

  python3 scripts/span_split.py --dump out/f7.json -- \\
      --workload phi4-mini.fig7-mix --seed 1234 --seconds 40 --trace 1
  python3 scripts/span_split.py out/f7.json [more.json ...]

The first form runs ``benchmarks/chip/run_cell.py`` with the given
arguments, unchanged, in this process (from the root of a checkout, on the
cell's chips); it writes every request the cell served, with its shares'
spans and compile counts, to the dump, then prints the split. The second
form prints the split of dumps already written. A program without spans
gives a dump with none, and a split of ``run_s`` alone.

The split is per request, mean over the window's requests (and apart, the
traced ones): seconds in each span, ``runner_untimed_s`` (``runner.run``
minus build, prefill and decode, as its reader has it) and the named spans
inside it, and the window's three slowest requests with the spans that grew
most against the median request of the same plan.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time
from collections import defaultdict

T_START = time.perf_counter()

TIMED = ("runner.build", "engine.prefill", "engine.decode")
# the spans that lie in runner_untimed_s, outermost first
UNTIMED = ("engine.compile_prefill", "runner.fetch", "engine.aot_prefill",
           "engine.aot_decode", "runner.prompts", "runner.release")
SHOWN = ("runner.share",) + TIMED + ("engine.compile",) + UNTIMED


def _row(rec, traced: bool) -> dict:
    return {"rid": rec.spec.rid, "traced": traced, "plan_s": rec.plan_s,
            "run_s": rec.run_s,
            "shares": [{"node": s.node, "level": s.level, "device": s.device,
                        "compiles": getattr(s, "compiles", None),
                        "spans": [[sp.name, sp.start_ns, sp.end_ns, sp.parent]
                                  for sp in getattr(s, "spans", ())]}
                       for s in rec.shares]}


def run(dump: str, argv) -> int:
    """Run one cell as ``run_cell.py`` does, keeping every served request."""
    here = os.path.join(os.getcwd(), "benchmarks", "chip")
    sys.path.insert(0, here)
    import harness
    import run_cell
    run_cell.T_START = T_START           # setup_s counts from our start
    rows, serve = [], harness.serve

    def keep(gn, runner, spec, annotate, gc_clock=None):
        rec = serve(gn, runner, spec, annotate, gc_clock)
        # the window passes a clock; the traced requests run without one
        rows.append(_row(rec, traced=gc_clock is None))
        return rec
    harness.serve = keep
    try:
        rc = run_cell.main(argv)
    finally:
        harness.serve = serve
        os.makedirs(os.path.dirname(os.path.abspath(dump)), exist_ok=True)
        with open(dump, "w") as f:
            json.dump(rows, f)
    return rc


def per_request(row: dict) -> dict:
    """Seconds of each span summed over the request's shares, with
    ``run_s``, ``runner_untimed_s`` and ``between spans`` (what of it no
    named span holds)."""
    sec = defaultdict(float)
    for s in row["shares"]:
        for name, start, end, _ in s["spans"]:
            sec[name] += (end - start) * 1e-9
    out = {"run_s": row["run_s"]}
    out.update(sec)
    untimed = row["run_s"] - sum(sec[n] for n in TIMED)
    out["runner_untimed_s"] = untimed
    out["between spans"] = untimed - sum(sec[n] for n in UNTIMED)
    out["compiles"] = sum(s["compiles"] or 0 for s in row["shares"])
    return out


def _plan(row: dict) -> tuple:
    return tuple((s["node"], s["level"]) for s in row["shares"])


def split(rows) -> list:
    """Lines of the split of one dump's requests."""
    lines = []
    keys = ("run_s",) + SHOWN + ("runner_untimed_s", "between spans",
                                 "compiles")
    for traced in (False, True):
        got = [per_request(r) for r in rows if r["traced"] == traced]
        if not got:
            continue
        lines.append(f"{'traced' if traced else 'window'}: {len(got)} "
                     "requests, per request, mean")
        lines += [f"  {k:24s} {sum(g.get(k, 0.0) for g in got) / len(got):.4f}"
                  for k in keys]
    window = [r for r in rows if not r["traced"]]
    by_plan = defaultdict(list)
    for r in window:
        by_plan[_plan(r)].append(per_request(r))
    for r in sorted(window, key=lambda r: -r["run_s"])[:3]:
        mine, peers = per_request(r), by_plan[_plan(r)]
        grew = sorted(((mine.get(k, 0.0) - statistics.median(
            p.get(k, 0.0) for p in peers), k) for k in SHOWN[1:] + (
                "between spans",)), reverse=True)[:2]
        lines.append(f"slow: rid {r['rid']} run_s {r['run_s']:.4f} against "
                     f"{statistics.median(p['run_s'] for p in peers):.4f} "
                     f"({len(peers)} of its plan); grew: " + ", ".join(
                         f"{k} {d:+.4f}" for d, k in grew))
    return lines


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--" in argv:
        i = argv.index("--")
        ap = argparse.ArgumentParser()
        ap.add_argument("--dump", required=True)
        dump = ap.parse_args(argv[:i]).dump
        rc = run(dump, argv[i + 1:])
        paths = [dump]
    else:
        rc, paths = 0, argv
    for path in paths:
        with open(path) as f:
            rows = json.load(f)
        print(f"### {path}", file=sys.stderr)
        print("\n".join(split(rows)), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
