"""Admission (``serving/engine.py``): the 90th percentile, over requests
due in the window, of the time from a request's due time to the engine's
taking it from its queue into a prefill.  Moves ``ttft_p90_ms``."""

from bench import stats


def read(run):
    w = run.window
    waits = [r.admitted_s - r.due_s for r in w.requests
             if w.t_open <= r.due_s < w.t_close and r.admitted_s is not None]
    return stats.percentile(waits, 90) * 1e3 if waits else None
