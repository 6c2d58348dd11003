"""The reduction from a profiler trace to busy time, program time and
idle gaps, on spans built by hand and on a trace recorded on the CPU."""

from __future__ import annotations

import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Span, TraceData

MS = 1e6  # nanoseconds


def _hand_trace():
    dev = "/device:TPU:0"
    ops = [Span("fusion.1", 10 * MS, 20 * MS), Span("fusion.2", 15 * MS, 30 * MS),
           Span("dot.3", 60 * MS, 70 * MS), Span("fusion.1", 95 * MS, 130 * MS)]
    modules = [Span("jit_bench_prefill_1024(12)", 10 * MS, 30 * MS),
               Span("jit_bench_decode(3)", 60 * MS, 70 * MS),
               Span("jit_bench_decode(3)", 95 * MS, 130 * MS)]
    host = [Span(tr.WINDOW_SPAN, 0, 100 * MS), Span("bench.step", 5 * MS, 35 * MS),
            Span("bench.step", 40 * MS, 90 * MS), Span("bench.generate", 40 * MS, 58 * MS)]
    return TraceData(ops={dev: ops}, modules={dev: modules}, host=host)


def test_busy_program_time_and_gaps_from_spans():
    s = tr.summarize(_hand_trace())
    assert s.window_s == pytest.approx(0.1)
    # union of [10,30], [60,70], [95,100] inside the window [0,100]
    assert s.busy_s == pytest.approx(0.035)
    assert s.program_s["bench_prefill_1024"] == pytest.approx(0.020)
    assert s.program_seconds("bench_decode") == pytest.approx(0.015)
    assert s.program_runs["bench_decode"] == 2
    # 30..60 ms under bench.generate, 70..95 and 0..10 ms under bench.step
    assert s.idle_gaps == [("bench.generate", pytest.approx(0.030)),
                           ("bench.step", pytest.approx(0.025)),
                           ("bench.step", pytest.approx(0.010))]
    assert dict(s.top_ops)["bench_prefill_1024:fusion.2"] == pytest.approx(0.015)


def test_device_seconds_within_host_spans():
    td = _hand_trace()
    steps = tr.host_spans(td, "bench.step", tr.window_of(td))
    assert len(steps) == 2
    assert tr.device_seconds_within(td, "bench_decode", steps[1:]) == pytest.approx(0.010)
    assert tr.device_seconds_within(td, "bench_prefill", steps) == pytest.approx(0.020)


def test_a_window_span_is_required():
    td = _hand_trace()
    td.host = [h for h in td.host if h.name != tr.WINDOW_SPAN]
    with pytest.raises(ValueError):
        tr.summarize(td)


def test_a_trace_recorded_on_the_cpu_is_read(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    td = tr.load(tr.find_xplane(tmp_path))
    lo, hi = tr.window_of(td)
    assert hi > lo
    assert len(tr.host_spans(td, "bench.step", (lo, hi))) == 3
    s = tr.summarize(td)
    assert s.window_s > 0 and s.busy_s <= s.window_s
