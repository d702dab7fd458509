"""The reader of the program's compile counter (``window_compiles``) on
hand-made records and on a smoke-config run, the program's spans on that
run, and idle gaps named by the program's spans."""
import contextlib
import importlib
import types

import entry
import harness
import jax
import pytest
import trace_reduce as tr
from conftest import smoke_doc
from trace_reduce import Event

from repro.configs import get_smoke_config
from repro.serving.spans import Span

MS = 1_000_000
READERS = ("window_compiles",)


def _read(name, records):
    ctx = types.SimpleNamespace(records=records)
    return importlib.import_module(f"metrics.{name}").read(ctx)


def _share(compiles=0):
    return types.SimpleNamespace(spans=(Span("runner.share", 0, MS, None),),
                                 compiles=compiles)


def _record(*shares):
    return types.SimpleNamespace(shares=list(shares))


def test_readers_on_hand_made_records():
    recs = [_record(_share(2), _share()),
            _record(_share(), _share(), _share(), _share(1))]
    assert _read("window_compiles", recs) == pytest.approx(1.5)


def test_readers_find_nothing_without_spans():
    """The program before spans: its shares carry neither spans nor a
    counter, and every reader returns None rather than raising."""
    bare = types.SimpleNamespace(served=8, build_s=0.0, prefill_s=0.2)
    for name in READERS:
        assert _read(name, [_record(bare)]) is None
        assert _read(name, []) is None


def test_readers_on_a_smoke_run():
    """Warm up a dispatch, then serve it again as the window: nothing
    compiles, and no share runs an extra prefill to compile its decode
    step (no ``engine.compile_prefill`` span)."""
    arch = "phi4-mini-3.8b"
    cfg, doc = get_smoke_config(arch), smoke_doc(arch)
    gn = entry.build_gateway(cfg, policy=doc["policy"])
    nodes = [n.name for n in gn.table.nodes]
    runner = entry.ShareRunner(cfg, entry.place_nodes(nodes, jax.devices()))
    req = entry.InferenceRequest(rid=3, num_items=390, perf_req=1.0,
                                 acc_req=0.0)
    gn.handle(entry.Event(kind="workload", request=req))
    d = gn.dispatches[-1]
    runner.run(d)                                   # warm-up

    def serve():
        spec = types.SimpleNamespace(rid=3, num_items=390, perf_req=1.0,
                                     acc_req=0.0)
        return harness.serve(gn, runner, spec,
                             lambda name: contextlib.nullcontext())
    window = [serve(), serve()]
    runner.close()
    names = {sp.name for r in window for s in r.shares for sp in s.spans}
    assert {"runner.share", "engine.prefill", "engine.decode"} <= names
    assert "engine.compile_prefill" not in names
    assert _read("window_compiles", window) == 0
    # the runner's timed parts fit inside runner.run
    for r in window:
        timed = sum(s.build_s + s.prefill_s + entry.DECODE_STEPS *
                    s.decode_step_s for s in r.shares)
        assert timed <= r.run_s


def test_idle_gap_is_named_by_the_program_span():
    """A gap inside ``engine.prefill`` (itself inside ``bench.run``) is put
    down to ``engine.prefill``, the innermost host span around it."""
    host = [Event("bench.window", 0, 100 * MS),
            Event("bench.run", 0, 100 * MS),
            Event("runner.run", 1 * MS, 99 * MS),
            Event("runner.share", 2 * MS, 98 * MS),
            Event("engine.prefill", 40 * MS, 70 * MS)]
    dev = [Event("fusion.1", 0, 40 * MS),            # busy up to the prefill
           Event("fusion.2", 60 * MS, 100 * MS)]     # idle 40-60 ms
    s = tr.reduce(host, [dev])
    assert dict(s.idle_gaps) == pytest.approx({"engine.prefill": 0.02})


def test_recorded_spans_share_the_benchmark_thread(tmp_path):
    """Spans the program records land on the host line that holds the
    benchmark's window, inside ``bench.run``."""
    from repro.serving import spans
    f = jax.jit(lambda x: x * 2.0)
    x = jax.numpy.ones((16,))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        with jax.profiler.TraceAnnotation("bench.run"):
            with spans.collect("runner.share"):
                with spans.record("engine.prefill"):
                    f(x).block_until_ready()
    jax.profiler.stop_trace()
    host, _ = tr.load(str(tmp_path))
    by = {e.name: e for e in host}
    assert {"bench.run", "runner.share", "engine.prefill"} <= set(by)
    outer, mid, inner = by["bench.run"], by["runner.share"], by["engine.prefill"]
    assert outer.start <= mid.start <= inner.start
    assert inner.end <= mid.end <= outer.end
