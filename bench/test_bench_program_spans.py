"""The program's spans on the device trace's clock (``program_spans.py``)
and the readers of the metrics that read them, on traces built by hand and
on a session traced under a CPU profile."""

from __future__ import annotations

import copy
import json
import random
import statistics
import types

import numpy as np
import pytest

from bench import program_spans as ps_mod
from bench import registry, testing
from bench import trace_reduce as tr
from bench.trace_reduce import Span, TraceData

MS = 1e6            # nanoseconds
T0 = 100.0          # perf_counter of the profile's t=0 in the hand-built runs
CFG = json.loads((testing.REPO / "bench/configs/qwen3-14b-l8.json").read_text())
DEV = "/device:TPU:0"


def _clock():
    from repro.obs import ClockMap

    return ClockMap(T0, 0.0, 1e9, 0.0)


def _s(ms: float) -> float:
    return T0 + ms * 1e-3


def _step_records(off: float, tid0: int):
    """One engine step at ``off`` ms: phases on the caller's ring, a decode
    and a sample on worker 0 and a decode on worker 1."""
    def phase(b, e, label):
        return [(-1, _s(off + b), "phase_begin", label, 1, -1),
                (-1, _s(off + e), "phase_end", label, 1, -1)]

    def body(w, b, e, name, tid):
        return [(w, _s(off + b), "task_start", f"compute|{name}", tid, 0),
                (w, _s(off + e), "task_end", "", tid, -1)]

    return (phase(0, 30, "engine.step") + phase(0, 4, "engine.admit")
            + phase(1, 3, "engine.prefill") + phase(5, 20, "engine.run_graph")
            + phase(5, 20, "session.run") + phase(5, 6, "session.plan")
            + phase(6.5, 19.5, "session.execute") + phase(20, 30, "engine.collect")
            + body(0, 7, 9, "decode0", tid0) + body(0, 10, 11, "sample0", tid0 + 1)
            + body(1, 8, 12, "decode1", tid0 + 2))


def _window(dropped: int = 0):
    from repro.obs import Window

    events = sorted(_step_records(0, 0) + _step_records(40, 0), key=lambda e: e[1])
    return Window(events, dropped, 2)


def _program(dropped: int = 0):
    return ps_mod.ProgramSpans.from_window(_window(dropped), _clock())


def _hand_trace():
    """Device ops of two steps: a prefill in the first step's admission,
    a decode in each step's graph run."""
    ops = [Span("fusion.1", 1.5 * MS, 3.5 * MS), Span("fusion.2", 9 * MS, 19 * MS),
           Span("fusion.2", 49 * MS, 59 * MS)]
    modules = [Span("jit_bench_prefill_512(1)", 1.5 * MS, 3.5 * MS),
               Span("jit_bench_decode(2)", 9 * MS, 19 * MS),
               Span("jit_bench_decode(2)", 49 * MS, 59 * MS)]
    host = [Span(tr.WINDOW_SPAN, 0, 80 * MS), Span("bench.step", 0, 30 * MS),
            Span("bench.step", 40 * MS, 70 * MS)]
    return TraceData(ops={DEV: ops}, modules={DEV: modules}, host=host)


def test_spans_of_a_window_pair_up_and_map():
    ps = _program()
    labels = [p.label for p in ps.phases]
    assert labels.count("engine.step") == 2 and labels.count("session.execute") == 2
    assert len(ps.bodies) == 6 and ps.dropped == 0
    names = {s.name for s in ps.spans()}
    assert {"repro.engine.step", "repro.engine.collect", "repro.session.plan",
            "repro.task.compute"} <= names
    steps = ps.phase_spans("engine.step")
    assert [(s.start_ns, s.end_ns) for s in steps] == [
        pytest.approx((0, 30 * MS)), pytest.approx((40 * MS, 70 * MS))]
    # one gap per run on worker 0 (decode0 -> sample0), none across runs
    assert ps.sched_gaps_s() == pytest.approx([1e-3, 1e-3])
    # session.run at 5 ms, its first body at 7 ms
    assert ps.run_setups_s() == pytest.approx([2e-3, 2e-3])


def test_a_window_that_dropped_events_reads_nothing():
    ps = _program(dropped=3)
    assert ps.sched_gaps_s() == [] and ps.run_setups_s() == []
    run = types.SimpleNamespace(program=ps, summary=tr.summarize(_hand_trace()),
                                window=types.SimpleNamespace(trace=_hand_trace()))
    assert _reader("step_idle_ms")(run) is None
    assert _reader("run_setup_ms")(run) is None


def _reader(name):
    return registry.metric_reader(testing.REPO, name)


def test_new_readers_on_a_hand_built_trace():
    td = _hand_trace()
    run = types.SimpleNamespace(program=_program(), summary=tr.summarize(td),
                                window=types.SimpleNamespace(trace=td))
    # step 1: 30 ms less 2 ms of prefill and 10 ms of decode; step 2: 30 - 10
    assert _reader("step_idle_ms")(run) == pytest.approx(statistics.median([18, 20]))
    assert _reader("run_setup_ms")(run) == pytest.approx(2.0)
    # sched_gap_us.batch is read by the stem reader, over the step runs' bodies
    lm = types.SimpleNamespace(sched_gaps_s=run.program.sched_gaps_s())
    assert _reader("sched_gap_us.batch")(lm) == pytest.approx(1000.0)


@pytest.mark.parametrize("name", ["step_idle_ms", "run_setup_ms"])
def test_new_readers_read_nothing_from_a_program_without_spans(name):
    run = types.SimpleNamespace(summary=None, window=None)
    assert _reader(name)(run) is None


def test_the_sweep_labels_gaps_as_trace_reduce_does():
    rng = random.Random(5)
    host = [Span(tr.WINDOW_SPAN, 0, 1000)]
    for _ in range(60):
        a = rng.uniform(0, 1000)
        host.append(Span(rng.choice(["bench.x", "repro.y", "repro.z"]), a,
                         a + rng.choice([5.0, 20.0, 80.0, 300.0])))
    gaps = [(a, a + rng.uniform(0.1, 30)) for a in
            sorted(rng.uniform(0, 1000) for _ in range(300))]
    assert ps_mod.label_gaps(host, gaps) == [tr._label_gap(host, a, b) for a, b in gaps]


def test_launch_margins_put_each_step_s_first_program_after_its_first_body():
    td = _hand_trace()
    window = tr.window_of(td)
    # decode0 starts at 7 ms of each step, its program at 9 ms
    assert ps_mod.launch_margins_ns(_program(), td, window) == pytest.approx([2 * MS, 2 * MS])
    late = ps_mod.ProgramSpans.from_window(_window(), _late_clock(3e-3))
    # a clock 3 ms late puts the programs before the bodies that launch them
    assert all(m < 0 for m in ps_mod.launch_margins_ns(late, td, window))


def _late_clock(seconds):
    from repro.obs import ClockMap

    return ClockMap(T0 - seconds, 0.0, 1e9, 0.0)


def test_idle_by_label_adds_up_to_the_idle_time():
    td = _hand_trace()
    spans = _program().spans()
    out = ps_mod.idle_by_label(td, tr.window_of(td), spans)
    s = tr.summarize(td)
    assert sum(out.values()) == pytest.approx(s.window_s - s.busy_s)
    # every gap inside a step falls under one of its parts, none under the
    # bare engine.step; the window's last gap, 59-80 ms, has its midpoint
    # in the second step's collect, the one between the steps in no span
    assert "repro.engine.step" not in out
    assert out["repro.engine.collect"] == pytest.approx(21e-3)
    assert out[ps_mod.OUTSIDE] == pytest.approx(30e-3)


def _lm_run(td):
    from bench.drivers.lm_serve import LMRun, Rec, Step, Window
    from bench.work import LMShapes

    recs = [Rec(0, _s(0.5), np.zeros((1, 512), np.int32), 4, admitted_s=_s(1)),
            Rec(1, _s(40.5), np.zeros((1, 512), np.int32), 4, admitted_s=_s(42))]
    steps = [Step(_s(0), _s(30), contexts=[513, 600], prefills=[512], traced=True),
             Step(_s(40), _s(70), contexts=[514, 601], traced=True)]
    w = Window(_s(0), _s(80), recs, steps, trace=td)
    return LMRun(window=w, shapes=LMShapes.from_config(CFG), device_kind="TPU v5 lite",
                 summary=tr.summarize(td))


def _chol_run(td):
    from bench.drivers.cholesky import CholRun, Window

    w = Window(_s(0), _s(80), 2, [0.03, 0.03], [], [1e-4, 2e-4, 3e-4], trace=td)
    return CholRun(w, tr.summarize(td))


#: every reader there was before the program's spans, on the kind of cell
#: that reports it
READERS = [("serve_mfu", _lm_run), ("decode_roofline", _lm_run),
           ("device_idle_frac", _lm_run), ("prefill_device_frac", _lm_run),
           ("prefill_roofline", _lm_run), ("queue_wait_p90_ms", _lm_run),
           ("sched_gap_us", _chol_run), ("device_idle_frac", _chol_run)]


def _with_program_spans():
    plain = _hand_trace()
    traced = copy.deepcopy(plain)
    assert ps_mod.attach(traced, _program().spans(), tr.window_of(traced)) > 0
    return plain, traced


def test_program_spans_change_only_the_labels_of_idle_gaps():
    plain, traced = _with_program_spans()
    a, b = tr.summarize(plain), tr.summarize(traced)
    assert (a.busy_s, a.window_s, a.program_s, a.program_runs, a.top_ops) == \
        (b.busy_s, b.window_s, b.program_s, b.program_runs, b.top_ops)
    assert [g[1] for g in a.idle_gaps] == [g[1] for g in b.idle_gaps]
    assert [g[0] for g in a.idle_gaps] != [g[0] for g in b.idle_gaps]
    assert any(g[0].startswith("repro.") for g in b.idle_gaps)


@pytest.mark.parametrize("name,make", READERS,
                         ids=[f"{n}-{m.__name__[1:]}" for n, m in READERS])
def test_existing_readers_ignore_appended_program_spans(name, make):
    plain, traced = _with_program_spans()
    read = _reader(name)
    before = read(make(plain))
    assert before is not None
    assert read(make(traced)) == before


def test_attach_keeps_the_labels_of_the_longest_gaps_exact():
    td = _hand_trace()
    spans = _program().spans()
    window = tr.window_of(td)
    exact = ps_mod.label_gaps(td.host + spans, ps_mod.idle_gaps(td, window))
    by_gap = dict(zip(ps_mod.idle_gaps(td, window), exact))
    ps_mod.attach(td, spans, window, top=3)
    s = tr.summarize(td, top=3)
    longest = sorted(by_gap.items(), key=lambda kv: kv[0][1] - kv[0][0], reverse=True)[:3]
    assert [g[0] for g in s.idle_gaps] == [label for _, label in longest]


def test_a_session_traced_under_a_cpu_profile_maps_inside_its_annotations(tmp_path):
    """The recorder's spans land where the profiler saw them: a phase
    emitted inside an annotation maps inside it, within 50 us."""
    import jax

    import repro

    with repro.Session(1, trace=True) as session:
        g = repro.Graph("probe")
        g.add(lambda: 1, name="one")
        session.run(g)                                  # warm
        jax.profiler.start_trace(str(tmp_path))
        rec = ps_mod.Recorder(session)
        with jax.profiler.TraceAnnotation(tr.WINDOW_SPAN):
            rec.open()
            for _ in range(3):
                with jax.profiler.TraceAnnotation("bench.step"):
                    g = repro.Graph("probe")
                    g.add(lambda: 1, name="one")
                    session.run(g)
            rec.close()
        jax.profiler.stop_trace()
    xplane = tr.find_xplane(tmp_path)
    td = tr.load(xplane)
    ps = rec.read(xplane)
    assert ps.dropped == 0 and ps.clock.error_ns < 50e3
    runs = ps.phase_spans("session.run")
    steps = tr.host_spans(td, "bench.step", tr.window_of(td))
    assert len(runs) == len(steps) == 3
    for run, step in zip(runs, steps):
        assert run.start_ns >= step.start_ns - 50e3
        assert run.end_ns <= step.end_ns + 50e3
    assert len(ps.run_setups_s()) == 3
