"""trace_reduce.py on a hand-made trace and on a small recorded one."""
import time

import pytest
import trace_reduce as tr
from trace_reduce import Event


def test_busy_idle_and_attribution():
    ms = 1e6
    host = [Event("bench.window", 0, 100 * ms),
            Event("bench.plan", 0, 10 * ms),
            Event("bench.run", 10 * ms, 100 * ms),
            Event("numpy copy", 60 * ms, 70 * ms)]
    dev0 = [Event("fusion.1", 10 * ms, 40 * ms),
            Event("fusion.2", 30 * ms, 60 * ms),     # overlaps fusion.1
            Event("fusion.1", 70 * ms, 120 * ms)]    # clipped at 100
    dev1 = [Event("fusion.3", 20 * ms, 30 * ms)]
    s = tr.reduce(host, [dev0, dev1])
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx([0.08, 0.01])
    assert s.mean_busy_s == pytest.approx(0.045)
    ops = dict(s.device_ops)
    assert ops["fusion.1"] == pytest.approx((0.03 + 0.03) / 2)
    gaps = dict(s.idle_gaps)
    # chip 0 idles 0-10 (middle in the plan) and 60-70 (the copy); chip 1
    # idles 0-20 (middle at 10: the run) and 30-100 (middle at 65: the copy)
    assert gaps["bench.plan"] == pytest.approx(0.01 / 2)
    assert gaps["bench.run"] == pytest.approx(0.02 / 2)
    assert gaps["numpy copy"] == pytest.approx((0.01 + 0.07) / 2)
    assert sum(gaps.values()) == pytest.approx((0.2 - 0.09) / 2)


def test_no_window_or_no_device_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tr.reduce([Event("x", 0, 1)], [[Event("op", 0, 1)]])
    with pytest.raises(ValueError, match="no device"):
        tr.reduce([Event("bench.window", 0, 1)], [])


def test_recorded_trace_has_the_window_span(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((128, 128))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.run"):
                f(x).block_until_ready()
            time.sleep(0.005)
    jax.profiler.stop_trace()
    host, devices = tr.load(str(tmp_path))
    names = [e.name for e in host]
    assert names.count(tr.WINDOW_SPAN) == 1 and names.count("bench.run") == 3
    window = next(e for e in host if e.name == tr.WINDOW_SPAN)
    runs = [e for e in host if e.name == "bench.run"]
    assert all(window.start <= r.start and r.end <= window.end for r in runs)
    assert devices == []            # the CPU has no device plane


def test_containers_are_not_counted_and_ops_carry_their_program():
    body = [Event("%fusion.1 = f32[8] fusion(x)", 10, 20),
            Event("%fusion.2 = f32[8] fusion(y)", 25, 40)]
    loop = Event("%while.3 = (s32[]) while(t)", 5, 45)
    assert tr.leaves([loop] + body) == body
    named = tr._in_modules([loop] + body, [Event("jit_prefill(7)", 0, 50)])
    assert {e.name for e in named} == {"fusion.1", "fusion.2", "while.3"}
    assert {e.module for e in named} == {"jit_prefill(7)"}
    s = tr.reduce([Event("bench.window", 0, 50)], [named])
    assert dict(s.device_ops) == pytest.approx(
        {"jit_prefill(7)/fusion.2": 15e-9, "jit_prefill(7)/fusion.1": 10e-9})
    assert s.busy_s == pytest.approx([40e-9])
