#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

  python3 benchmarks/chip/run_cell.py --workload phi4-mini.fig7-mix \\
      --seed 1234 --seconds 45 --trace 0

Run from the root of a checkout on a machine with the cell's chips. It
fails (nonzero exit, no result) unless JAX's devices are TPUs and there are
at least as many as the cell asks for. The last line of stdout is one JSON
object: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics with ``--trace 0``, its per-layer metrics with
``--trace 1``), ``device``, with ``--trace 1`` ``breakdown``, and last
``checks``, each compared number beside its limit (also the last lines of
stderr).

``--control 1`` also reads the control on the run's own sample (the
reference in float8 in the program's place, judged by the same limits: it
has to come out not correct) and counts the served weights that differ
from the reference's; the result line then carries ``control`` before
``checks``. The benchmark's own runs leave it off.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the compile cache lives at a fixed path inside the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, HERE)
    import harness
    import entry

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cell = harness.find_cell(json.load(f), args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's devices are {devices[0].platform}",
              file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX finds "
              f"{len(devices)}", file=sys.stderr)
        return 2
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    entry.enable_compile_cache()

    doc = harness.load_json(cell.config_file)
    cfg = entry.get_config(doc["arch"])
    out = harness.run(cell, doc, cfg, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      t_start=T_START, control=bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
