"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy and idle
time, device time per named program, and idle gaps attributed to what the
host was doing.

A trace is read into plain :class:`Span` lists first (:func:`load`), so the
reduction itself (:func:`summarize`) runs on spans that a test can build by
hand.  Conventions, from the planes the profiler writes:

* a device is a plane named ``/device:<platform>:<n>``; its line
  ``XLA Ops`` holds one event per operation it ran, and its line
  ``XLA Modules`` one event per program run, named after the jitted
  function (``jit_bench_decode(...)``);
* host spans are the benchmark's own ``jax.profiler.TraceAnnotation``
  events on ``/host:CPU``, named ``bench.<what>``;
* the traced window is the host span ``bench.traced``.

Busy time is the union of the operation intervals on a device, clipped to
the window, averaged over the devices that ran anything.
"""

from __future__ import annotations

import dataclasses
import pathlib
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.traced"
_DEVICE_PLANE = re.compile(r"^/device:[A-Za-z]+:\d+$")
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start_ns: float
    end_ns: float

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


@dataclasses.dataclass
class TraceData:
    """What the reduction reads: per device, its operations and program
    runs; and the benchmark's host spans."""

    ops: Dict[str, List[Span]]
    modules: Dict[str, List[Span]]
    host: List[Span]


def program_name(event_name: str) -> str:
    """``jit_bench_decode(123)`` -> ``bench_decode``."""
    name = _SUFFIX.sub("", event_name)
    return name[4:] if name.startswith("jit_") else name


def find_xplane(directory: pathlib.Path) -> pathlib.Path:
    found = sorted(pathlib.Path(directory).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def load(path: pathlib.Path) -> TraceData:
    """Read an ``.xplane.pb`` with ``jax.profiler.ProfileData``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    ops: Dict[str, List[Span]] = {}
    modules: Dict[str, List[Span]] = {}
    host: List[Span] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                spans = [Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events]
                (ops if line.name == "XLA Ops" else modules)[plane.name] = spans
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                host.extend(Span(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events if e.name.startswith(HOST_PREFIX))
    return TraceData(ops=ops, modules=modules, host=host)


def _clip(spans: Iterable[Span], lo: float, hi: float) -> List[Tuple[float, float]]:
    out = []
    for s in spans:
        a, b = max(s.start_ns, lo), min(s.end_ns, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1] = (merged[-1][0], b)
        else:
            merged.append((a, b))
    return merged


@dataclasses.dataclass
class Summary:
    """The traced window reduced to numbers; times in seconds."""

    window_s: float
    busy_s: float                        # mean over devices that ran anything
    n_devices: int
    program_s: Dict[str, float]          # program name -> device seconds
    program_runs: Dict[str, int]
    top_ops: List[Tuple[str, float]]     # "program:op" -> device seconds
    idle_gaps: List[Tuple[str, float]]   # host span over the gap -> seconds
    window_ns: Tuple[float, float]

    def program_seconds(self, prefix: str) -> float:
        return sum(v for k, v in self.program_s.items() if k.startswith(prefix))


def window_of(td: TraceData) -> Tuple[float, float]:
    spans = [s for s in td.host if s.name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    return spans[0].start_ns, spans[0].end_ns


def _label_gap(host: List[Span], a: float, b: float) -> str:
    """The innermost benchmark span that covers the gap's midpoint."""
    mid = 0.5 * (a + b)
    covering = [s for s in host
                if s.start_ns <= mid <= s.end_ns and s.name != WINDOW_SPAN]
    if not covering:
        return "outside bench spans"
    return min(covering, key=lambda s: s.end_ns - s.start_ns).name


def summarize(td: TraceData, window: Optional[Tuple[float, float]] = None,
              top: int = 10) -> Summary:
    lo, hi = window if window is not None else window_of(td)
    busy: List[float] = []
    gaps: List[Tuple[str, float]] = []
    first_device = True
    for dev in sorted(td.ops):
        merged = union(_clip(td.ops[dev], lo, hi))
        if not merged:
            continue
        busy.append(sum(b - a for a, b in merged) * 1e-9)
        if first_device:
            # gaps of the first device that ran anything, window edges included
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps.append((_label_gap(td.host, a, b), (b - a) * 1e-9))
            first_device = False
    prog_s: Dict[str, float] = defaultdict(float)
    prog_n: Dict[str, int] = defaultdict(int)
    mod_of: Dict[str, List[Tuple[float, float, str]]] = {}
    for dev, spans in td.modules.items():
        mod_of[dev] = []
        for s in spans:
            a, b = max(s.start_ns, lo), min(s.end_ns, hi)
            if b <= a:
                continue
            name = program_name(s.name)
            prog_s[name] += (b - a) * 1e-9
            prog_n[name] += 1
            mod_of[dev].append((s.start_ns, s.end_ns, name))
    op_s: Dict[str, float] = defaultdict(float)
    for dev, spans in td.ops.items():
        mods = sorted(mod_of.get(dev, []))
        j = 0
        for s in sorted(spans, key=lambda s: s.start_ns):
            a, b = max(s.start_ns, lo), min(s.end_ns, hi)
            if b <= a:
                continue
            while j < len(mods) and mods[j][1] < s.start_ns:
                j += 1
            owner = mods[j][2] if j < len(mods) and mods[j][0] <= s.start_ns else "?"
            op_s[f"{owner}:{s.name.split(' = ')[0]}"] += (b - a) * 1e-9
    gaps.sort(key=lambda g: g[1], reverse=True)
    return Summary(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy) / len(busy) if busy else 0.0,
        n_devices=len(busy),
        program_s=dict(prog_s), program_runs=dict(prog_n),
        top_ops=sorted(op_s.items(), key=lambda kv: kv[1], reverse=True)[:top],
        idle_gaps=gaps[:top],
        window_ns=(lo, hi))


def host_spans(td: TraceData, name: str, window: Tuple[float, float]) -> List[Span]:
    """Host spans called ``name`` that lie wholly inside ``window``."""
    lo, hi = window
    return sorted((s for s in td.host
                   if s.name == name and s.start_ns >= lo and s.end_ns <= hi),
                  key=lambda s: s.start_ns)


def device_seconds_within(td: TraceData, prefix: str, spans: Sequence[Span]) -> float:
    """Device seconds of programs named ``prefix...`` that start inside one
    of ``spans`` (host spans, on the trace's clock), summed over devices."""
    bounds = sorted((s.start_ns, s.end_ns) for s in spans)
    total = 0.0
    for dev_spans in td.modules.values():
        for s in dev_spans:
            if not program_name(s.name).startswith(prefix):
                continue
            if any(a <= s.start_ns <= b for a, b in bounds):
                total += s.seconds
    return total
