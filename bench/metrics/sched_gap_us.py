"""Session and scheduler (``api/session.py``, ``exec/dynamic.py``): the
median host gap, on one worker, from one task body's end to the next one's
start, read from the program's flight recorder (``Session(trace=True)``)
over the traced factorizations.  Moves ``chol_gflops``."""

import statistics


def read(run):
    gaps = run.sched_gaps_s
    return statistics.median(gaps) * 1e6 if gaps else None
