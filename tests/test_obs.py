"""Flight-recorder / observability tests (repro.obs).

Covers the always-on-tracing contract: the off path allocates nothing, the
on path assembles a :class:`~repro.obs.trace.RuntimeTrace` whose counters
reconcile exactly with ``RunReport.stats``, Perfetto export round-trips to
an equal trace, and the session/pool plumbing surfaces traces + serving
stats end to end.  The suite-level leak check (no ring buffer outliving
its session) lives in ``conftest.py``.
"""

import gc
import json
import sys

import pytest

import repro
from repro.core.policies import POLICIES, VictimPolicy, register_policy
from repro.core.tracing import (
    EV_PHASE_BEGIN,
    EV_PHASE_END,
    EV_TASK_END,
    EV_TASK_START,
    KIND_BARRIER,
    KIND_COMPUTE,
    KIND_STEAL,
    KIND_SWITCH,
    SPAN_KINDS,
)
from repro.obs import (
    NULL_RECORDER,
    ClockMap,
    FlightRecorder,
    RuntimeTrace,
    anchor_spans,
    assemble,
    load_trace,
    phase_spans,
    profile_anchor,
    validate_trace_json,
    write_trace,
)
from repro.obs.export import main as export_main
from repro.obs.recorder import _Ring


# ---------------------------------------------------------------------------
# graph builders
# ---------------------------------------------------------------------------

def _mixed_graph(fanout=6):
    """Fan-out of plain tasks plus a channel-coupled producer/consumer frame
    pair: exercises task, steal, frame-suspend/resume and block events."""
    g = repro.Graph("obs-mixed")
    ch = repro.Channel("obs.ch", capacity=1)

    def producer(ctx):
        for i in range(3):
            yield ctx.send(ch, i)
        return "done"

    def consumer(ctx):
        total = 0
        for _ in range(3):
            v = yield ctx.recv(ch)
            total += v
        return total

    root = g.add(lambda: 1, name="root")
    mids = [g.add(lambda x: x + 1, root, name=f"m{i}") for i in range(fanout)]
    p = g.add(producer, deps=[root], name="producer")
    c = g.add(consumer, deps=[root], name="consumer")
    join = g.add(lambda *xs: sum(x for x in xs if isinstance(x, int)),
                 *mids, c, deps=[p], name="join")
    return g, c, join


# ---------------------------------------------------------------------------
# the off path is free
# ---------------------------------------------------------------------------

class _FakeTask:
    kind = "compute"
    name = "t"
    tid = 7


class _FakeFrame:
    task = _FakeTask()
    resumes = 2


class _FakeRequest:
    @staticmethod
    def source_uid():
        return 3

    @staticmethod
    def describe():
        return "recv(ch)"


def test_null_recorder_emits_allocate_nothing():
    """The tracing-off hot path — ``NULL_RECORDER.emit*`` with raw objects —
    must not allocate: no f-strings, no ``*args`` tuple packing."""
    task, frame, req = _FakeTask(), _FakeFrame(), _FakeRequest()
    r = NULL_RECORDER

    def burst(n=2000):
        for _ in range(n):
            r.emit(0, EV_TASK_START, "x", 1, 2)
            r.emit(0, EV_TASK_START)
            r.emit_task_start(0, task)
            r.emit_frame_resume(1, frame)
            r.emit_frame_suspend(1, frame, req)
            r.phase_begin("engine.step")
            r.phase_end("engine.step")
            r.begin_run()

    burst(100)                      # warm free lists / specializations
    gc.disable()
    try:
        deltas = []
        for _ in range(5):
            before = sys.getallocatedblocks()
            burst()
            deltas.append(sys.getallocatedblocks() - before)
    finally:
        gc.enable()
    # interpreter background noise can add a block or two once; a per-call
    # cost would show in EVERY sample across 16k calls
    assert min(deltas) == 0, f"no-op emit path allocates: deltas={deltas}"


def test_untraced_runtime_uses_null_recorder_singleton():
    from repro.core.runtime import Runtime

    rt = Runtime(2)
    assert rt._dispatch.recorder is NULL_RECORDER
    assert rt.last_trace is None
    rt.shutdown()


# ---------------------------------------------------------------------------
# ring mechanics
# ---------------------------------------------------------------------------

def test_ring_wraps_and_counts_dropped():
    ring = _Ring(4)
    for i in range(7):
        ring.append((float(i), "k", "", i, 0))
    events, lost = ring.window(0, 7)
    assert [e[3] for e in events] == [3, 4, 5, 6] and lost == 3
    # a window of emission indices counts what was overwritten in it
    events, lost = ring.window(1, 5)
    assert [e[3] for e in events] == [3, 4] and lost == 2
    assert ring.window(5, 7) == (ring.window(0, 7)[0][2:], 0)
    assert ring.window(7, 7) == ([], 0)


def test_recorder_routes_external_threads_to_extra_ring():
    rec = FlightRecorder(2, capacity=8)
    rec.emit(0, "a", "", 1)
    rec.emit(-1, "b", "", 2)       # non-worker thread (e.g. outside waker)
    snap = rec.snapshot()
    assert [(w, k) for (w, _, k, _, _, _) in snap] == [(0, "a"), (-1, "b")]


def test_windows_between_marks_count_what_the_rings_overwrote():
    rec = FlightRecorder(1, capacity=4)
    for i in range(3):
        rec.emit(0, "a", "", i)
    m = rec.mark()
    for i in range(3, 10):
        rec.emit(0, "a", "", i)
    # 10 events on a ring of 4: indices 0..5 are gone, 3..5 of them after m
    w = rec.window(since=m)
    assert [e[4] for e in w.events] == [6, 7, 8, 9] and w.dropped == 3
    assert rec.window(until=m) == ([], 3, 1)
    whole = rec.window()
    assert whole.dropped == 6 and whole.events == w.events


def test_run_trace_is_the_run_alone_on_a_recorder_that_keeps_earlier_runs():
    """``begin_run`` marks instead of resetting: a run's trace is what a
    recorder reset at the run's start gave on the same events, whatever
    came before it and whatever host phases the caller interleaves."""
    def run_events(t0, tid0):
        return [(0, t0, EV_TASK_START, "compute|a", tid0, 0),
                (0, t0 + 1.0, EV_TASK_END, "", tid0, -1),
                (1, t0 + 0.5, EV_TASK_START, "comm|b", tid0 + 1, 0),
                (1, t0 + 2.0, EV_TASK_END, "", tid0 + 1, -1)]

    shared, fresh = FlightRecorder(2), FlightRecorder(2)
    for (w, t, kind, label, a, b) in run_events(10.0, 0):
        shared.rings[w].append((t, kind, label, a, b))
    shared.begin_run()
    second = run_events(20.0, 0)
    phases = [(-1, 19.5, EV_PHASE_BEGIN, "session.execute", 1, -1),
              (-1, 23.0, EV_PHASE_END, "session.execute", 1, -1)]
    for (w, t, kind, label, a, b) in sorted(second + phases, key=lambda e: e[1]):
        shared.rings[w].append((t, kind, label, a, b))
    for (w, t, kind, label, a, b) in second:
        fresh.rings[w].append((t, kind, label, a, b))
    got = RuntimeTrace.from_recorder(shared)
    assert got == RuntimeTrace.from_recorder(fresh) == assemble(second, 2)
    assert got.t_base == 20.0 and got.counters["tasks"] == 2
    # the whole window keeps both runs, and the caller's phase pairs up
    assert shared.window().assemble().counters["tasks"] == 4
    (span,) = phase_spans(shared.window().events)
    assert (span.label, span.t0, span.t1) == ("session.execute", 19.5, 23.0)


def test_phases_pair_per_thread_and_drop_unmatched_ends():
    events = [(-1, 1.0, EV_PHASE_BEGIN, "p", 1, -1),
              (-1, 2.0, EV_PHASE_BEGIN, "p", 2, -1),   # another thread
              (-1, 3.0, EV_PHASE_END, "p", 1, -1),
              (-1, 3.5, EV_PHASE_END, "q", 1, -1),     # its begin fell outside
              (-1, 4.0, EV_PHASE_BEGIN, "p", 1, -1),   # never ends
              (-1, 5.0, EV_PHASE_END, "p", 2, -1)]
    assert [(p.t0, p.t1, p.thread) for p in phase_spans(events)] == [
        (1.0, 3.0, 1), (2.0, 5.0, 2)]


# ---------------------------------------------------------------------------
# session plumbing + reconciliation
# ---------------------------------------------------------------------------

def test_untraced_session_report_has_no_trace():
    g, _, join = _mixed_graph()
    with repro.Session(2) as s:
        report = s.run(g)
    assert report.trace is None
    assert join in report


@pytest.mark.parametrize("scheduler", ["dynamic", "replay", "pool"])
def test_session_window_keeps_every_run(scheduler):
    """One recorder per session: every executor it builds writes to it,
    each run's report traces that run alone, and the window holds all of
    them with the session's own phases."""
    n = 4
    with repro.Session(2, scheduler=scheduler, trace=True) as s:
        start = s.trace_mark()
        reports = []
        for _ in range(n):
            g, _, _ = _mixed_graph(fanout=3)
            reports.append(s.run(g))
        window = s.trace_window(since=start)
        assert s.recorder.enabled and window.dropped == 0
    assert s.recorder is NULL_RECORDER and s.trace_window() is None
    n_tasks = len(g.tasks)
    for report in reports:
        assert report.trace.counters["tasks"] == n_tasks
        assert report.trace.reconcile(report.stats) == {}
    assert window.assemble().counters["tasks"] == n * n_tasks
    labels = [p.label for p in phase_spans(window.events)]
    for phase in ("session.run", "session.plan", "session.execute"):
        assert labels.count(phase) == n
    runs = [p for p in phase_spans(window.events) if p.label == "session.run"]
    for run, report in zip(runs, reports):
        assert run.t0 <= report.trace.t_base <= run.t1


def test_traced_dynamic_run_reconciles_with_stats(tmp_path):
    g, c, join = _mixed_graph()
    with repro.Session(2, trace=True) as s:
        report = s.run(g)
    trace = report.trace
    assert isinstance(trace, RuntimeTrace)
    assert report[c] == 0 + 1 + 2
    # every counted scheduler event has a matching recorded event
    assert trace.reconcile(report.stats) == {}
    assert trace.counters["frame_suspends"] >= 1
    assert trace.counters["tasks"] == len(g.tasks)
    assert set(e.kind for e in trace.events) <= SPAN_KINDS
    assert trace.metrics()["dropped_events"] == 0


def test_traced_one_worker_replay_reconciles_exactly():
    """On one worker the replay is deterministic: suspend/resume/fallback
    counters in ``RunReport.stats`` must equal the trace's event counts."""
    g1, _, _ = _mixed_graph(fanout=3)
    with repro.Session(1, scheduler="replay", trace=True) as s:
        first = s.run(g1)                       # records
        assert first.plan.mode == "record"
        g2, _, _ = _mixed_graph(fanout=3)
        second = s.run(g2)                      # replays
    assert second.plan.mode == "replay"
    trace = second.trace
    assert isinstance(trace, RuntimeTrace)
    assert trace.reconcile(second.stats) == {}
    assert trace.counters["frame_suspends"] == second.stats["frame_suspends"]
    assert trace.counters["fallback_steals"] == second.stats["fallback_steals"]


def test_trace_breakdown_shares_simulator_vocabulary():
    from repro.core import microbatch_overlap_graph, simulate

    sim_trace = simulate(microbatch_overlap_graph(8), 2, seed=0)
    g, _, _ = _mixed_graph()
    with repro.Session(2, trace=True) as s:
        run_trace = s.run(g).trace
    # same Event schema + kind vocabulary: the same analysis code runs on
    # both the offline simulator trace and the live flight recorder
    for tr in (sim_trace, run_trace):
        b = tr.breakdown()
        assert set(b) <= SPAN_KINDS
        assert 0.0 <= tr.utilization() <= 1.0
    assert run_trace.breakdown().get(KIND_COMPUTE, 0.0) > 0.0


def test_recorder_time_maps_inside_the_profile_annotation_around_it(tmp_path):
    """Anchors put recorder time on the JAX profile's clock: a phase
    emitted inside a ``TraceAnnotation`` maps inside it, within 50 us."""
    import time

    import jax

    rec = FlightRecorder(1)
    jax.profiler.start_trace(str(tmp_path))
    readings = [profile_anchor()]
    for _ in range(4):
        with jax.profiler.TraceAnnotation("obs.probe"):
            rec.phase_begin("probe")
            time.sleep(2e-3)
            rec.phase_end("probe")
        time.sleep(1e-3)
    readings.append(profile_anchor())
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData

    (path,) = tmp_path.rglob("*.xplane.pb")
    profile = ProfileData.from_file(str(path))
    clock = ClockMap.from_anchors(readings, anchor_spans(profile))
    assert clock.error_ns < 50e3
    assert abs(clock.ns_per_s / 1e9 - 1.0) < 1e-3
    probes = sorted((e.start_ns, e.start_ns + e.duration_ns)
                    for plane in profile.planes for line in plane.lines
                    for e in line.events if e.name == "obs.probe")
    spans = phase_spans(rec.window().events)
    assert len(spans) == len(probes) == 4
    for span, (a, b) in zip(spans, probes):
        assert clock.ns(span.t0) >= a - 50e3
        assert clock.ns(span.t1) <= b + 50e3


# ---------------------------------------------------------------------------
# Perfetto export
# ---------------------------------------------------------------------------

def _traced_run(workers=2):
    g, _, _ = _mixed_graph()
    with repro.Session(workers, trace=True) as s:
        return s.run(g).trace


def test_perfetto_roundtrip_is_exact(tmp_path):
    trace = _traced_run()
    path = tmp_path / "trace.json"
    write_trace(trace, path)
    loaded = load_trace(path)
    assert loaded == trace
    assert loaded.metrics() == trace.metrics()
    assert loaded.t_base == trace.t_base is not None


def test_perfetto_json_shape_and_validation(tmp_path):
    trace = _traced_run()
    path = tmp_path / "trace.json"
    write_trace(trace, path)
    info = validate_trace_json(path)
    assert info["schema"] == "repro.obs/1"
    assert info["rows"] == trace.n_workers + 1      # + external row
    data = json.loads(path.read_text())
    events = data["traceEvents"]
    # one named row per worker (+ external), slices, and steal/frame flows
    assert sum(1 for e in events if e["ph"] == "M"
               and e["name"] == "thread_name") == trace.n_workers + 1
    assert any(e["ph"] == "X" for e in events)
    if trace.steal_flows or trace.frame_flows:
        assert any(e["ph"] == "s" for e in events)
        assert any(e["ph"] == "f" for e in events)
    assert data["otherData"]["counters"] == trace.counters


def test_validate_rejects_malformed_trace(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"traceEvents": [
        {"ph": "X", "name": "x", "ts": 0, "dur": -5, "pid": 1, "tid": 0,
         "cat": "nope"}]}))
    with pytest.raises(ValueError, match="schema"):
        validate_trace_json(bad)


def test_export_cli_demo_and_validate(tmp_path, capsys):
    out = tmp_path / "demo.json"
    assert export_main(["--out", str(out), "--workers", "2",
                        "--steps", "2"]) == 0
    assert export_main(["--validate", str(out),
                        "--summarize", str(out)]) == 0
    text = capsys.readouterr().out
    assert "breakdown" in text and "steal success" in text


# ---------------------------------------------------------------------------
# pool serving stats + rolling trace metrics (ROADMAP item 4 plumbing)
# ---------------------------------------------------------------------------

def test_pool_surfaces_mode_replay_stats_and_trace_metrics():
    with repro.Session(2, scheduler="pool", trace=True,
                       pool_kwargs={"warmup_runs": 1}) as s:
        modes = []
        for _ in range(3):
            g, _, _ = _mixed_graph(fanout=3)
            report = s.run(g)
            modes.append(report.stats["pool_mode"])
            assert isinstance(report.trace, RuntimeTrace)
        assert modes == ["warmup", "record", "replay"]
        # the replay serve carries the executor's raw deviation counters —
        # a speedup<1 row is explainable from the outcome alone
        rs = report.stats["replay_stats"]
        assert {"fallback_steals", "stalls", "skips",
                "run_ahead"} <= set(rs)
        (entry_stats,) = s.pool.describe().values()
        tm = entry_stats["trace_metrics"]
        assert {"steal_success_rate", "dispatch_overhead_fraction",
                "utilization", "resume_latency_mean_s"} <= set(tm)
        assert 0.0 <= tm["utilization"] <= 1.0


def test_untraced_pool_keeps_trace_metrics_empty():
    with repro.Session(2, scheduler="pool") as s:
        g, _, _ = _mixed_graph(fanout=3)
        report = s.run(g)
        assert report.trace is None
        (entry_stats,) = s.pool.describe().values()
        assert entry_stats["trace_metrics"] == {}


# ---------------------------------------------------------------------------
# victim-policy feedback
# ---------------------------------------------------------------------------

def test_traced_runs_feed_policy_observe():
    observed = []

    @register_policy("obs-spy")
    class SpyPolicy(VictimPolicy):
        name = "obs-spy"

        def select(self):
            return self._rand_victim()

        def record(self, victim, success):
            pass

        def observe(self, metrics):
            observed.append(metrics)

    try:
        g, _, _ = _mixed_graph()
        with repro.Session(2, policy="obs-spy", trace=True) as s:
            s.run(g)
        # one observe() per worker's policy, fed the assembled metrics
        assert len(observed) == 2
        assert "steal_by_victim" in observed[0]
        assert "resume_latency" in observed[0]
    finally:
        POLICIES.pop("obs-spy", None)


def test_untraced_runs_do_not_feed_policies():
    observed = []

    @register_policy("obs-spy2")
    class SpyPolicy(VictimPolicy):
        name = "obs-spy2"

        def select(self):
            return self._rand_victim()

        def record(self, victim, success):
            pass

        def observe(self, metrics):
            observed.append(metrics)

    try:
        g, _, _ = _mixed_graph()
        with repro.Session(2, policy="obs-spy2") as s:
            s.run(g)
        assert observed == []
    finally:
        POLICIES.pop("obs-spy2", None)


# ---------------------------------------------------------------------------
# assembled-span sanity
# ---------------------------------------------------------------------------

def test_assembled_spans_are_well_formed():
    trace = _traced_run()
    assert trace.events, "traced run produced no spans"
    for e in trace.events:
        assert e.t1 >= e.t0 >= 0.0
        assert -1 <= e.worker < trace.n_workers
    # zero-length markers are reserved for steal/switch instants
    for e in trace.events:
        if e.kind not in (KIND_STEAL, KIND_SWITCH, KIND_BARRIER):
            assert e.dt >= 0.0
