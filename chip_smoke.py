#!/usr/bin/env python3
"""Chip smoke test: full-width phi4-mini served through the gateway on TPU.

  python chip_smoke.py             # one chip: Pallas-vs-XLA check, then serve
  python chip_smoke.py --chips 4   # only the four-node phase, on four chips

The gateway plans demo requests with Algorithm 1 and every node's share is
served by a real engine (``repro.launch.serve.ShareRunner``) at the full
published width of ``phi4-mini-3.8b``, bf16 weights drawn from a seed.

Phases:
  check  level 0 prefill logits and one decode step with the Pallas kernels
         compiled (``use_kernels=True``) against the XLA path, to a bf16-sized
         tolerance.
  serve  a few demo requests, every node on device 0; per share it prints
         weight build, compile, prefill and decode-step seconds.
  four   (``--chips 4``) one request planned over four nodes, node i's engine
         on ``jax.devices()[i]``, all in one process; its shares are compared
         with the same shares run on device 0 alone: identical greedy tokens,
         logits within tolerance.

Each phase runs in a process of its own (a chip belongs to one process), and
this parent never imports JAX. A phase fails unless JAX's first device is a
TPU. Any failure exits nonzero without the result line; on success the last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
ARCH = "phi4-mini-3.8b"
PHASES = {1: ("check", "serve"), 4: ("four",)}
PHASE_TIMEOUT_S = 1100
SERVE_REQUESTS = 3          # demo requests the serve phase sends
CHECK_BATCH = 4             # prompts in the kernels-vs-XLA check
# Pallas vs XLA at bf16: max |a - b| over max |b|. The two paths round
# differently (the kernels keep probabilities in f32, the XLA path casts them
# to bf16 before the PV product), and 32 layers compound it.
REL_TOL = 5e-2
# the same program on two chips of one kind must agree to the bit; a small
# tolerance is kept for logits only
SAME_PROGRAM_TOL = 1e-6


# ----------------------------------------------------------------------
# parent: runs each phase in a child process and never touches JAX
def _run_phase(phase: str, chips: int) -> dict:
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(chips)]
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE, text=True, cwd=ROOT)
    timer = threading.Timer(PHASE_TIMEOUT_S, proc.kill)
    timer.start()
    last = ""
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.strip():
                last = line.strip()
        rc = proc.wait()
    finally:
        timer.cancel()
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    if rc != 0:
        raise SystemExit(f"phase {phase!r} failed with exit code {rc}")
    result = json.loads(last)
    if result.get("phase") != phase:
        raise SystemExit(f"phase {phase!r} ended without its result line")
    return result


def main_parent(chips: int) -> int:
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print(f"no repro package under {ROOT}/src", file=sys.stderr)
        return 1
    device = None
    for phase in PHASES[chips]:
        t0 = time.perf_counter()
        device = _run_phase(phase, chips)["device"]
        print(f"[{phase}] done in {time.perf_counter() - t0:.1f} s",
              flush=True)
    if device["platform"] != "tpu" or device["count"] < chips:
        print(f"expected {chips} TPU chip(s), saw {device}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


# ----------------------------------------------------------------------
# phases: each runs in its own process
def _close(name: str, got, want, tol: float) -> float:
    """Fail unless both are finite and max|got - want| <= tol * max|want|."""
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shapes {got.shape} != {want.shape}")
    if not (np.isfinite(got).all() and np.isfinite(want).all()):
        raise AssertionError(f"{name}: non-finite values")
    err = float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))
    print(f"{name}: max|diff|/max|ref| = {err:.3e} (tolerance {tol:.0e})",
          flush=True)
    if err > tol:
        raise AssertionError(f"{name}: relative error {err:.3e} > {tol:.0e}")
    return err


def _check_finite(name: str, x):
    import numpy as np
    if not np.isfinite(np.asarray(x, np.float32)).all():
        raise AssertionError(f"{name}: non-finite values")


def _check_tokens(name: str, tokens, vocab: int):
    import numpy as np
    tokens = np.asarray(tokens)
    if tokens.size == 0 or tokens.min() < 0 or tokens.max() >= vocab:
        raise AssertionError(f"{name}: tokens outside [0, {vocab})")


def _timed(fn):
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def phase_check(cfg, device) -> None:
    """Level 0 with the Pallas kernels compiled against the XLA path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch.serve import CACHE_LEN, PROMPT_LEN
    from repro.serving import spans
    from repro.serving.engine import Engine, EngineConfig, init_params_on

    params, build_s = _timed(
        lambda: init_params_on(cfg, jax.random.PRNGKey(0), device))
    print(f"build level 0 weights: {build_s:.3f} s", flush=True)
    engines = {
        "xla": Engine(cfg, params, EngineConfig(max_len=CACHE_LEN), device),
        "pallas": Engine(cfg, params, EngineConfig(max_len=CACHE_LEN,
                                                   use_kernels=True), device),
    }
    tokens = jax.device_put(np.random.default_rng(0).integers(
        0, cfg.vocab_size, (CHECK_BATCH, PROMPT_LEN), dtype=np.int32), device)
    out = {}
    for name, eng in engines.items():
        with spans.collect("check.compile") as got:
            eng.compile(tokens)
        for prog in ("prefill", "decode"):
            s = spans.seconds(got, f"engine.aot_{prog}")
            print(f"compile {name} {prog}: {s:.3f} s", flush=True)
        (logits, caches, lengths, _), s = _timed(lambda: eng.prefill(tokens))
        print(f"{name} prefill B={CHECK_BATCH} S={PROMPT_LEN}: {s:.4f} s",
              flush=True)
        out[name] = (logits, caches, lengths)
    _close("prefill logits pallas vs xla", out["pallas"][0], out["xla"][0],
           REL_TOL)

    # one decode step from the same (XLA-prefilled) cache on both paths;
    # decode donates its cache, so the kernel path gets a copy
    logits, caches, lengths = out.pop("xla")
    out.clear()
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    _check_tokens("prefill argmax", tok, cfg.vocab_size)
    copy = jax.tree_util.tree_map(jnp.copy, caches)
    (dp, _, _), s = _timed(lambda: engines["pallas"].decode(copy, lengths, tok))
    print(f"pallas decode step: {s:.4f} s", flush=True)
    (dx, _, _), s = _timed(lambda: engines["xla"].decode(caches, lengths, tok))
    print(f"xla decode step: {s:.4f} s", flush=True)
    _close("decode logits pallas vs xla", dp, dx, REL_TOL)
    _check_tokens("decode argmax", jnp.argmax(dx, axis=-1), cfg.vocab_size)


def _serve(runner, gn, request) -> list:
    from repro.core.resource_manager import Event
    from repro.launch.serve import DECODE_STEPS, format_share
    res = gn.handle(Event(kind="workload", request=request))
    print(f"request {request.rid}: {request.num_items} items, plan perf "
          f"{res.achieved_perf:.1f}/s acc {res.achieved_acc:.2f}", flush=True)
    shares = runner.run(gn.dispatches[-1])
    vocab = runner.pool.base.vocab_size
    for s in shares:
        print(format_share(s), flush=True)
        _check_finite(f"{s.node} logits", s.logits)
        _check_tokens(f"{s.node} tokens", s.tokens, vocab)
        if s.tokens.shape != (s.served, DECODE_STEPS):
            raise AssertionError(f"{s.node}: tokens {s.tokens.shape}")
    return shares


def _runner(cfg, gn, devices):
    from repro.launch.serve import SHARE_ITEMS, ShareRunner, place_nodes
    print(f"at most {SHARE_ITEMS} items per share", flush=True)
    return ShareRunner(cfg, place_nodes([n.name for n in gn.table.nodes],
                                        devices))


def phase_serve(cfg, device) -> None:
    """A few demo requests through the gateway, every node on ``device``."""
    from repro.launch.serve import build_gateway, demo_requests
    gn = build_gateway(cfg)
    runner = _runner(cfg, gn, [device])
    for r in demo_requests(gn, SERVE_REQUESTS):
        _serve(runner, gn, r)
    runner.close()


def phase_four(cfg, devices) -> None:
    """One request over four nodes on four devices vs device 0 alone."""
    import numpy as np
    from repro.launch.serve import build_gateway, demo_requests
    if len(devices) != 4:
        raise AssertionError(f"the four-node phase needs 4 devices, "
                             f"found {len(devices)}")
    gn = build_gateway(cfg)
    request = demo_requests(gn, 1)[0]
    runner = _runner(cfg, gn, devices)
    spread = _serve(runner, gn, request)
    runner.close()
    if len({s.device for s in spread}) != 4:
        raise AssertionError("the shares did not use four devices")

    single = _runner(cfg, gn, devices[:1])
    alone = {s.node: s for s in single.run(gn.dispatches[-1])}
    single.close()
    for s in spread:
        ref = alone[s.node]
        print(f"{s.node}: level {s.level} on {s.device} vs {ref.device}",
              flush=True)
        if not np.array_equal(s.tokens, ref.tokens):
            raise AssertionError(f"{s.node}: greedy tokens differ")
        _close(f"{s.node} logits", s.logits, ref.logits, SAME_PROGRAM_TOL)


def main_phase(phase: str, chips: int) -> int:
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    devices = jax.devices()
    dev = devices[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}
    print(f"[{phase}] devices: {info}", flush=True)
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips:
        print(f"asked for {chips} chips, found {len(devices)}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.roofline.analysis import chip_peaks
    print(f"[{phase}] compile cache: {enable_compile_cache()}", flush=True)
    peaks = chip_peaks(dev.device_kind)     # unknown kinds fail here
    print(f"[{phase}] peaks: {peaks}", flush=True)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if phase == "check":
        phase_check(cfg, dev)
    elif phase == "serve":
        phase_serve(cfg, dev)
    elif phase == "four":
        phase_four(cfg, devices[:4])
    else:
        raise ValueError(f"unknown phase {phase!r}")
    for d in devices[:chips]:
        stats = d.memory_stats() or {}
        print(f"[{phase}] {d}: peak_bytes_in_use="
              f"{stats.get('peak_bytes_in_use')} "
              f"bytes_limit={stats.get('bytes_limit')}", flush=True)
    print(f"[{phase}] ran in {time.perf_counter() - t0:.1f} s", flush=True)
    print(json.dumps({"phase": phase, "device": info}))
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=sorted(PHASES), default=1,
                    help="1: check and serve on one chip; 4: only the "
                         "four-node phase")
    ap.add_argument("--phase", choices=("check", "serve", "four"),
                    help=argparse.SUPPRESS)   # set by the parent
    args = ap.parse_args(argv)
    if args.phase:
        return main_phase(args.phase, args.chips)
    return main_parent(args.chips)


if __name__ == "__main__":
    sys.exit(main())
