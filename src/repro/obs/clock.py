"""Flight-recorder time on a JAX profile's clock.

The recorder stamps ``perf_counter``; the JAX profiler stamps its events
on its own clock, as offsets from the profile's start.  To place the
program's spans against the device's operations, take an *anchor* while a
profile is active (:func:`profile_anchor`): a ``jax.profiler``
annotation named ``repro.anchor`` with a ``perf_counter`` reading taken
inside it.  After the profile is written, each reading pairs with its
annotation's interval (:func:`anchor_spans`) and :class:`ClockMap` draws
the line through the pairs.  The reading lies somewhere inside its
annotation, so a mapped time is off by at most half the widest
annotation (``ClockMap.error_ns``, a few microseconds); two anchors, at
the start and end of the profile, also take out the drift between the
two clocks.
"""

from __future__ import annotations

import dataclasses
from time import perf_counter
from typing import Iterable, List, Sequence, Tuple

__all__ = ["ANCHOR", "ClockMap", "anchor_spans", "profile_anchor"]

#: name of the anchor annotation in the profile
ANCHOR = "repro.anchor"


def profile_anchor() -> float:
    """Inside an active JAX profile: open the ``repro.anchor`` annotation,
    read ``perf_counter`` inside it, and return the reading."""
    import jax

    with jax.profiler.TraceAnnotation(ANCHOR):
        return perf_counter()


def anchor_spans(profile) -> List[Tuple[float, float]]:
    """``(start_ns, end_ns)`` of every anchor annotation in a profile (a
    ``jax.profiler.ProfileData``, or the path of an ``.xplane.pb``), in
    time order."""
    if not hasattr(profile, "planes"):
        from jax.profiler import ProfileData

        profile = ProfileData.from_file(str(profile))
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out.extend((e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events if e.name == ANCHOR)
    return sorted(out)


@dataclasses.dataclass(frozen=True)
class ClockMap:
    """``perf_counter`` seconds -> nanoseconds on a profile's clock: the
    line through the anchors, each anchor at its annotation's midpoint."""

    t0: float            # perf_counter of the first anchor
    ns0: float           # its place on the profile's clock
    ns_per_s: float      # 1e9 with one anchor; the fitted slope with more
    error_ns: float      # half the widest anchor annotation

    @classmethod
    def from_anchors(cls, readings: Sequence[float],
                     spans: Sequence[Tuple[float, float]]) -> "ClockMap":
        """Pair the i-th ``perf_counter`` reading with the i-th anchor span
        (both in time order) and fit the line through the first and the
        last pair."""
        if not readings or len(readings) != len(spans):
            raise ValueError(
                f"{len(readings)} anchor readings for {len(spans)} anchor "
                "annotations in the profile")
        mids = [0.5 * (a + b) for a, b in spans]
        t0, ns0 = readings[0], mids[0]
        rate = 1e9
        if len(readings) > 1 and readings[-1] > t0:
            rate = (mids[-1] - ns0) / (readings[-1] - t0)
        return cls(t0, ns0, rate, max(0.5 * (b - a) for a, b in spans))

    def ns(self, t: float) -> float:
        """``perf_counter`` seconds -> profile nanoseconds."""
        return self.ns0 + (t - self.t0) * self.ns_per_s

    def map_events(self, events: Iterable[Tuple[int, float, str, str, int, int]]
                   ) -> List[Tuple[int, float, str, str, int, int]]:
        """A recorder snapshot or window's records with ``t`` replaced by
        nanoseconds on the profile's clock."""
        return [(w, self.ns(t), kind, label, a, b)
                for (w, t, kind, label, a, b) in events]
