"""The flight recorder: per-worker lock-free ring buffers of point events.

Design constraints (Taskgraph's low-contention argument — instrumentation
must be cheap enough to leave on):

* **one writer per ring** — worker ``w`` appends only to ``rings[w]``, so
  no lock is needed on the hot path: a ring append is one ``perf_counter``
  call, one tuple pack, one CPython-atomic list store and an int add.
  Events emitted from *non-worker* threads (a channel send from outside
  the pool, a background re-record, the caller's host phases) go to one
  extra "external" ring, guarded by a small lock (those paths are rare
  and never hot).
* **bounded memory** — each ring holds ``capacity`` events; older events
  are overwritten and counted as dropped (surfaced on the assembled
  :class:`~repro.obs.trace.RuntimeTrace` and on every :class:`Window`).
* **one recorder per session** — a traced :class:`~repro.api.Session`
  owns one recorder that every executor it builds writes to.  A run does
  not reset the rings: ``begin_run`` marks where the run starts, a run's
  trace is assembled from the events after that mark, and
  :meth:`FlightRecorder.window` hands out any stretch between two marks.
* **near-zero cost when off** — executors hold :data:`NULL_RECORDER`, a
  module-level singleton whose ``emit`` does nothing.  The hot loops do
  ``self.recorder.emit(...)`` unconditionally: no branch, one attribute
  call.  The signature is positional and fixed (no ``*args``) so a no-op
  emit allocates nothing — tested in ``tests/test_obs.py``.  Phase labels
  are constant strings for the same reason.

Recorders register in a ``WeakSet`` so the test suite can assert no trace
buffer outlives its session (``live_recorders``).
"""

from __future__ import annotations

import threading
import weakref
from time import perf_counter
from typing import List, NamedTuple, Optional, Tuple

from ..core.tracing import (EV_BATCH, EV_FRAME_RESUME, EV_FRAME_SUSPEND,
                            EV_PHASE_BEGIN, EV_PHASE_END, EV_TASK_END,
                            EV_TASK_START)
from .trace import RuntimeTrace, assemble

__all__ = ["FlightRecorder", "NullRecorder", "NULL_RECORDER", "Window",
           "live_recorders", "recorder_for"]

#: raw record: (t, event kind, label, a, b) — worker id is the ring index
RawEvent = Tuple[float, str, str, int, int]
#: a snapshot record: (worker, t, event kind, label, a, b); worker -1 is the
#: external ring
Record = Tuple[int, float, str, str, int, int]
#: a position in every ring (``FlightRecorder.mark``): events emitted so far
Mark = Tuple[int, ...]

_live: "weakref.WeakSet[FlightRecorder]" = weakref.WeakSet()


def live_recorders() -> List["FlightRecorder"]:
    """Every :class:`FlightRecorder` still referenced somewhere — the
    suite-level leak check asserts this drains when sessions close."""
    return list(_live)


class _Ring:
    """Fixed-capacity single-writer ring of raw events."""

    __slots__ = ("buf", "cap", "n")

    def __init__(self, capacity: int):
        self.cap = capacity
        self.buf: List[RawEvent] = [None] * capacity  # type: ignore[list-item]
        self.n = 0

    def append(self, item: RawEvent) -> None:
        self.buf[self.n % self.cap] = item
        self.n += 1

    def window(self, lo: int, hi: int) -> Tuple[List[RawEvent], int]:
        """The surviving events of emission indices ``[lo, hi)``, in
        emission order, and how many of that range were overwritten."""
        cap, buf = self.cap, self.buf
        first = max(lo, self.n - cap)
        lost = max(0, min(first, hi) - lo)
        out = [buf[i % cap] for i in range(first, hi)]
        return [e for e in out if e is not None], lost


class Window(NamedTuple):
    """The raw events of a stretch of a recorder's life, between two marks
    (:meth:`FlightRecorder.mark`): ``events`` as ``(worker, t, kind, label,
    a, b)`` records sorted by time (``t`` on ``perf_counter``), ``dropped``
    the events of the stretch that ring overflow overwrote."""

    events: List[Record]
    dropped: int
    n_workers: int

    def assemble(self) -> RuntimeTrace:
        """The stretch as a :class:`~repro.obs.trace.RuntimeTrace`."""
        return assemble(self.events, self.n_workers, dropped=self.dropped)


class NullRecorder:
    """The off-switch: every method is a no-op.  ``emit`` keeps the exact
    positional signature of :meth:`FlightRecorder.emit` — fixed arity, no
    ``*args`` (packing a ``*args`` tuple would allocate per call).  The
    ``emit_*`` helpers exist so hot call sites pass raw objects instead of
    building label strings: with tracing off, a call allocates nothing."""

    __slots__ = ()
    enabled = False

    def emit(self, worker, kind, label="", a=-1, b=-1):
        return None

    def emit_task_start(self, worker, task):
        return None

    def emit_frame_resume(self, worker, frame):
        return None

    def emit_frame_suspend(self, worker, frame, request):
        return None

    def emit_resource(self, worker, kind, task, n_res=0):
        return None

    def emit_batch_start(self, worker, tasks):
        return None

    def emit_batch_end(self, worker, tasks):
        return None

    def phase_begin(self, label):
        return None

    def phase_end(self, label):
        return None

    def begin_run(self):
        return None


#: module-level singleton installed on every executor while tracing is off
NULL_RECORDER = NullRecorder()


class FlightRecorder:
    """Per-worker event rings for one session (or one executor).

    ``emit(worker, kind, label, a, b)`` timestamps with ``perf_counter``
    and appends to ``worker``'s ring; ``worker=-1`` routes to the shared
    external ring (non-worker threads).  ``begin_run`` marks where a run
    starts: :meth:`run_window` covers the current run, :meth:`window` any
    stretch between two marks (:meth:`mark`).
    """

    __slots__ = ("n_workers", "rings", "_ext_lock", "_run_mark",
                 "__weakref__")

    enabled = True

    def __init__(self, n_workers: int, capacity: int = 1 << 15):
        self.n_workers = n_workers
        # ring [-1] is the external ring: Python's negative indexing makes
        # `rings[worker]` correct for worker ids in [-1, n_workers)
        self.rings = [_Ring(capacity) for _ in range(n_workers + 1)]
        self._ext_lock = threading.Lock()
        self._run_mark: Mark = (0,) * (n_workers + 1)
        _live.add(self)

    def emit(self, worker, kind, label="", a=-1, b=-1):
        if worker >= 0:
            self.rings[worker].append((perf_counter(), kind, label, a, b))
        else:
            with self._ext_lock:
                self.rings[-1].append((perf_counter(), kind, label, a, b))

    # -- hot-path helpers: label building lives HERE, not at call sites,
    # so a NullRecorder call allocates nothing ---------------------------
    def emit_task_start(self, worker, task):
        self.emit(worker, EV_TASK_START, task.kind + "|" + task.name,
                  task.tid, 0)

    def emit_frame_resume(self, worker, frame):
        task = frame.task
        self.emit(worker, EV_FRAME_RESUME, task.kind + "|" + task.name,
                  task.tid, frame.resumes)

    def emit_frame_suspend(self, worker, frame, request):
        uid = request.source_uid()
        label = request.describe()
        if uid >= 0:
            label = f"{label}@c{uid}"     # channel/event identity
        self.emit(worker, EV_FRAME_SUSPEND, label, frame.task.tid,
                  frame.resumes + 1)

    def emit_resource(self, worker, kind, task, n_res=0):
        """Resource acquire/wait/release for ``task`` (kind is one of the
        EV_RESOURCE_* constants; label building stays off the null path)."""
        self.emit(worker, kind, task.name, task.tid, n_res)

    # -- a batched launch, on a worker's ring: every task's start (end)
    # carries one timestamp, so the assembled trace shows the launch as one
    # span and the gaps between spans stay the gaps between launches -------
    def emit_batch_start(self, worker, tasks):
        ring, t = self.rings[worker], perf_counter()
        ring.append((t, EV_BATCH, "", len(tasks), -1))
        for task in tasks:
            ring.append((t, EV_TASK_START, task.kind + "|" + task.name, task.tid, 0))

    def emit_batch_end(self, worker, tasks):
        ring, t = self.rings[worker], perf_counter()
        for task in tasks:
            ring.append((t, EV_TASK_END, "", task.tid, -1))

    # -- host phases of the caller (``engine.step``, ``session.run``, ...):
    # begin/end point events on the external ring, tagged with the calling
    # thread so phases of concurrent callers pair up apart -----------------
    def phase_begin(self, label):
        self.emit(-1, EV_PHASE_BEGIN, label, threading.get_ident())

    def phase_end(self, label):
        self.emit(-1, EV_PHASE_END, label, threading.get_ident())

    # -- marks and windows ------------------------------------------------
    def mark(self) -> Mark:
        """The current position in every ring (events emitted so far)."""
        return tuple(r.n for r in self.rings)

    def begin_run(self):
        self._run_mark = self.mark()

    def window(self, since: Optional[Mark] = None,
               until: Optional[Mark] = None) -> Window:
        """Every event emitted between the marks ``since`` (default: the
        recorder's start) and ``until`` (default: now) that survives in
        the rings, with the count of those ring overflow overwrote."""
        rings = self.rings
        since = since if since is not None else (0,) * len(rings)
        until = until if until is not None else self.mark()
        out: List[Record] = []
        dropped = 0
        for i, ring in enumerate(rings):
            w = i if i < self.n_workers else -1
            events, lost = ring.window(since[i], until[i])
            dropped += lost
            out.extend((w, t, kind, label, a, b)
                       for (t, kind, label, a, b) in events)
        out.sort(key=lambda e: e[1])
        return Window(out, dropped, self.n_workers)

    def run_window(self) -> Window:
        """The events of the current run (since the last ``begin_run``)."""
        return self.window(self._run_mark)

    def snapshot(self) -> List[Record]:
        """All events of the current run as ``(worker, t, kind, label, a,
        b)`` tuples, globally sorted by timestamp.  External-ring events
        come back with ``worker = -1``."""
        return self.run_window().events


def recorder_for(trace, n_workers: int):
    """The recorder an executor of ``n_workers`` writes to: ``trace`` may
    be False/None (the no-op singleton), True (a private recorder) or a
    :class:`FlightRecorder` to share (a session's)."""
    if isinstance(trace, FlightRecorder):
        if trace.n_workers != n_workers:
            raise ValueError(
                f"a recorder of {trace.n_workers} workers cannot trace an "
                f"executor of {n_workers}")
        return trace
    if trace is None or isinstance(trace, NullRecorder):
        return NULL_RECORDER
    return FlightRecorder(n_workers) if trace else NULL_RECORDER
