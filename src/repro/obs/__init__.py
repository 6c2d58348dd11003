"""Observability for the live executor stack (the runtime flight recorder).

* :mod:`repro.obs.recorder` — per-worker lock-free ring buffers of
  timestamped point events, one recorder per traced session, with a
  module-level no-op emitter so tracing costs one attribute call when off;
* :mod:`repro.obs.trace` — assembles recorded events into a
  :class:`RuntimeTrace` sharing the simulator's ``Event``/kind schema
  (``breakdown()`` / ``utilization()`` work on both), plus multi-run
  metrics (steal success, resume latency, idle fractions, fallback rate),
  and pairs the caller's host phases into spans (:class:`PhaseSpan`);
* :mod:`repro.obs.clock` — anchors that map recorder time onto a JAX
  profile's clock, so program spans line up with device operations;
* :mod:`repro.obs.perfetto` — Chrome/Perfetto ``trace_event`` JSON export
  (one row per worker, flow arrows for steals and channel sends→recvs,
  frame segments as slices) and the matching loader/validator;
* ``python -m repro.obs.export`` — CLI: demo traces, re-export, validation.
"""

from .recorder import (NULL_RECORDER, FlightRecorder, NullRecorder, Window,
                       live_recorders)
from .trace import PhaseSpan, RuntimeTrace, assemble, phase_spans
from .clock import ClockMap, anchor_spans, profile_anchor
from .perfetto import (load_trace, to_perfetto, validate_trace_json,
                       write_trace)

__all__ = [
    "FlightRecorder", "NullRecorder", "NULL_RECORDER", "Window",
    "live_recorders",
    "PhaseSpan", "RuntimeTrace", "assemble", "phase_spans",
    "ClockMap", "anchor_spans", "profile_anchor",
    "to_perfetto", "write_trace", "load_trace", "validate_trace_json",
]
