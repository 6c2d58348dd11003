"""Arithmetic shared by the per-layer readers.  Each
reader in ``bench/metrics/`` names the quantity; the sums are here."""

from __future__ import annotations

from typing import Optional

from bench import trace_reduce


def model_flops_in_window(run) -> float:
    """The least FLOPs of every prefill and decoded token of the steps that
    ended in the window."""
    w, s = run.window, run.shapes
    total = 0.0
    for step in w.steps:
        if w.in_window(step.t1):
            total += sum(s.prefill_flops(n) for n in step.prefills)
            total += s.decode_flops(step.contexts)
    return total


def mfu_percent(run) -> Optional[float]:
    """Model FLOPs in the window over the window times the bf16 peak."""
    w = run.window
    if w.seconds <= 0:
        return None
    return 100.0 * model_flops_in_window(run) / (w.seconds * run.peak["bf16_flops_per_s"])


def roofline_percent(run, program: str) -> Optional[float]:
    """Least time over device time of the harness's ``program`` runs
    (``bench_decode`` or ``bench_prefill``) in the traced steps that ran
    it; None when the trace holds none."""
    pairs = run.traced_steps()
    if not pairs:
        return None
    s = run.shapes
    least, spans = 0.0, []
    for step, span in pairs:
        if program == "bench_decode" and step.contexts:
            least += run.least_seconds(s.decode_flops(step.contexts),
                                       s.decode_bytes(step.contexts))
            spans.append(span)
        elif program == "bench_prefill" and step.prefills:
            least += sum(run.least_seconds(s.prefill_flops(n), s.prefill_bytes(n))
                         for n in step.prefills)
            spans.append(span)
    device = trace_reduce.device_seconds_within(run.window.trace, program, spans)
    if device <= 0.0:
        return None
    return 100.0 * least / device


def idle_fraction(run) -> Optional[float]:
    summary = run.summary
    if summary is None or summary.window_s <= 0 or summary.n_devices == 0:
        return None
    return 1.0 - summary.busy_s / summary.window_s
