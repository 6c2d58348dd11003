"""The program's own spans, from its flight recorder, on the clock of the
device trace.

A traced session (``repro.Session(trace=True)``) records its host phases
(``engine.step`` and its parts, ``session.run`` and its parts) and every
task body in one flight recorder.  Around the benchmark's traced window,
:class:`Recorder` marks the recorder and takes two clock anchors
(``repro.obs.profile_anchor``), one at each end; after the profile is
written, :meth:`Recorder.read` maps the window's events onto the
profile's clock and returns them as :class:`ProgramSpans`:

* host phases as spans named ``repro.<phase>`` (``repro.engine.collect``);
* task bodies as ``repro.task.<kind>`` (``repro.task.compute``).

:func:`attach` appends to ``TraceData.host`` the program spans that cover
the window's longest device-idle gaps, so ``trace_reduce.summarize``
names those gaps by program phase (it labels a gap with the shortest host
span over its midpoint; it scans every host span for every gap, so the
whole of a window's task bodies would make it quadratic).
:func:`idle_by_label` attributes every idle gap the same way, in one
sweep.

Readers of per-layer metrics find these on the run object they are given,
as ``run.program``; a run without it (tracing off, or a program that does
not record phases) reads None.
"""

from __future__ import annotations

import dataclasses
import heapq
import statistics
from typing import Dict, List, Optional, Sequence, Tuple

from bench import trace_reduce
from bench.trace_reduce import Span, TraceData

PREFIX = "repro."
#: the label ``trace_reduce`` gives a gap that no host span covers
OUTSIDE = "outside bench spans"
_NOT_TASKS = ("idle", "steal", "switch", "barrier")


@dataclasses.dataclass(frozen=True)
class Body:
    """One task body (or frame segment) on a worker; times on
    ``perf_counter``."""

    worker: int
    t0: float
    t1: float
    kind: str
    name: str


@dataclasses.dataclass
class ProgramSpans:
    """The program's phases and task bodies of one traced window."""

    phases: list                 # repro.obs.PhaseSpan, on perf_counter
    bodies: List[Body]
    dropped: int                 # events the recorder's rings overwrote
    clock: object = None         # repro.obs.ClockMap, once the profile is read

    @classmethod
    def from_window(cls, window, clock=None) -> "ProgramSpans":
        """From a session's ``trace_window`` (a ``repro.obs.Window``)."""
        from repro.obs import phase_spans

        trace = window.assemble()
        base = trace.t_base or 0.0
        bodies = [Body(e.worker, base + e.t0, base + e.t1, e.kind, e.label)
                  for e in trace.events if e.kind not in _NOT_TASKS]
        bodies.sort(key=lambda b: (b.t0, b.worker))
        return cls(phase_spans(window.events), bodies, window.dropped, clock)

    def named(self, label: str) -> list:
        return [p for p in self.phases if p.label == label]

    # -- on the profile's clock --------------------------------------------
    def spans(self) -> List[Span]:
        """Every phase as ``repro.<label>`` and every body as
        ``repro.task.<kind>``, on the profile's clock."""
        ns = self.clock.ns
        out = [Span(PREFIX + p.label, ns(p.t0), ns(p.t1)) for p in self.phases]
        out += [Span(f"{PREFIX}task.{b.kind}", ns(b.t0), ns(b.t1)) for b in self.bodies]
        return out

    def phase_spans(self, label: str) -> List[Span]:
        ns = self.clock.ns
        return [Span(PREFIX + p.label, ns(p.t0), ns(p.t1)) for p in self.named(label)]

    # -- host-clock quantities ---------------------------------------------
    def runs(self) -> List[List[Body]]:
        """The bodies of each run (those that start inside a
        ``session.execute`` phase), one list per run."""
        return [[b for b in self.bodies if run.t0 <= b.t0 <= run.t1]
                for run in self.named("session.execute")]

    def sched_gaps_s(self) -> List[float]:
        """Per run and worker, the host time from one body's end to the
        next one's start (none across runs); empty if events were lost."""
        if self.dropped:
            return []
        gaps: List[float] = []
        for bodies in self.runs():
            by_worker: Dict[int, List[Body]] = {}
            for b in bodies:
                by_worker.setdefault(b.worker, []).append(b)
            for evs in by_worker.values():
                gaps.extend(y.t0 - x.t1 for x, y in zip(evs, evs[1:]) if y.t0 >= x.t1)
        return gaps

    def run_setups_s(self) -> List[float]:
        """Per ``Session.run``: from its start to the first task body's start
        on any worker; empty if events were lost."""
        if self.dropped:
            return []
        out = []
        for run in self.named("session.run"):
            first = next((b.t0 for b in self.bodies if run.t0 <= b.t0 <= run.t1), None)
            if first is not None:
                out.append(first - run.t0)
        return out


class Recorder:
    """Take a session's program spans over a traced window: call
    :meth:`open` right after the window's annotation opens, :meth:`close`
    right before it closes (both inside the profile), and :meth:`read`
    with the written profile."""

    def __init__(self, session):
        self.session = session
        self.mark = None
        self.readings: List[float] = []
        self.window = None

    def open(self) -> None:
        from repro.obs import profile_anchor

        self.mark = self.session.trace_mark()
        self.readings = [profile_anchor()]

    def close(self) -> None:
        from repro.obs import profile_anchor

        self.readings.append(profile_anchor())
        self.window = self.session.trace_window(since=self.mark)

    def read(self, xplane) -> Optional[ProgramSpans]:
        """The window's spans on the profile's clock; None when the session
        did not trace."""
        from repro.obs import ClockMap, anchor_spans

        if self.window is None:
            return None
        clock = ClockMap.from_anchors(self.readings, anchor_spans(xplane))
        return ProgramSpans.from_window(self.window, clock)


# -- idle gaps ------------------------------------------------------------
def first_device_busy(td: TraceData, window: Tuple[float, float]
                      ) -> List[Tuple[float, float]]:
    """The merged op intervals, inside ``window``, of the first device
    that ran anything there (the device ``summarize`` takes gaps from)."""
    lo, hi = window
    for dev in sorted(td.ops):
        merged = trace_reduce.union(trace_reduce._clip(td.ops[dev], lo, hi))
        if merged:
            return merged
    return []


def idle_gaps(td: TraceData, window: Tuple[float, float]) -> List[Tuple[float, float]]:
    """The first device's idle intervals in ``window``, edges included."""
    merged = first_device_busy(td, window)
    if not merged:
        return []
    edges = [window[0]] + [x for iv in merged for x in iv] + [window[1]]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def label_gaps(host: Sequence[Span], gaps: Sequence[Tuple[float, float]]) -> List[str]:
    """For each gap, the shortest host span over its midpoint, as
    ``trace_reduce`` labels a gap, in one sweep."""
    spans = [(s.start_ns, s.end_ns, i, s.name) for i, s in enumerate(host)
             if s.name != trace_reduce.WINDOW_SPAN]
    spans.sort()
    order = sorted(range(len(gaps)), key=lambda i: gaps[i][0] + gaps[i][1])
    out = [OUTSIDE] * len(gaps)
    heap: List[Tuple[float, int, float, str]] = []
    j = 0
    for i in order:
        mid = 0.5 * (gaps[i][0] + gaps[i][1])
        while j < len(spans) and spans[j][0] <= mid:
            a, b, k, name = spans[j]
            heapq.heappush(heap, (b - a, k, b, name))
            j += 1
        while heap and heap[0][2] < mid:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][3]
    return out


def idle_by_label(td: TraceData, window: Tuple[float, float],
                  extra: Sequence[Span] = ()) -> Dict[str, float]:
    """Seconds of the first device's idle time in ``window`` under each
    label, the host spans of ``td`` and ``extra`` taken together."""
    gaps = idle_gaps(td, window)
    out: Dict[str, float] = {}
    for (a, b), label in zip(gaps, label_gaps(list(td.host) + list(extra), gaps)):
        out[label] = out.get(label, 0.0) + (b - a) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def attach(td: TraceData, spans: Sequence[Span], window: Tuple[float, float],
           top: int = 10) -> int:
    """Append to ``td.host`` the spans that cover the midpoint of one of
    the ``top`` longest idle gaps (ties included), so that ``summarize``
    labels those gaps as :func:`label_gaps` would over every span.
    Returns how many were appended."""
    gaps = sorted(idle_gaps(td, window), key=lambda g: g[1] - g[0], reverse=True)
    if not gaps:
        return 0
    floor = gaps[min(top, len(gaps)) - 1]
    longest = [g for g in gaps if g[1] - g[0] >= floor[1] - floor[0]]
    mids = [0.5 * (a + b) for a, b in longest]
    keep = [s for s in spans if any(s.start_ns <= m <= s.end_ns for m in mids)]
    td.host.extend(keep)
    return len(keep)


def step_idle_s(td: TraceData, window: Tuple[float, float],
                steps: Sequence[Span]) -> List[float]:
    """Per step span wholly inside ``window``: its length less the first
    device's busy time inside it."""
    merged = first_device_busy(td, window)
    out = []
    for s in steps:
        if s.start_ns < window[0] or s.end_ns > window[1]:
            continue
        busy = sum(min(b, s.end_ns) - max(a, s.start_ns) for a, b in merged
                   if b > s.start_ns and a < s.end_ns)
        out.append((s.end_ns - s.start_ns - busy) * 1e-9)
    return out


def launch_margins_ns(ps: ProgramSpans, td: TraceData, window: Tuple[float, float],
                      task_prefix: str = "decode", program: str = "bench_decode"
                      ) -> List[float]:
    """Clock check: for each ``engine.step`` span inside ``window``, how
    long after its first ``task_prefix...`` body starts on the host its
    first ``program`` run starts on the device, in ns.  A program cannot
    start before the body that launches it, so every margin should be
    positive; steps without both are left out."""
    ns = ps.clock.ns
    starts = sorted(s.start_ns for dev in td.modules.values() for s in dev
                    if trace_reduce.program_name(s.name) == program)
    out = []
    for step in ps.named("engine.step"):
        a, b = ns(step.t0), ns(step.t1)
        if a < window[0] or b > window[1]:
            continue
        body = next((ns(x.t0) for x in ps.bodies
                     if step.t0 <= x.t0 <= step.t1 and x.name.startswith(task_prefix)), None)
        launch = next((t for t in starts if a <= t <= b), None)
        if body is not None and launch is not None:
            out.append(launch - body)
    return out


def median_ms(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) * 1e3 if values else None
