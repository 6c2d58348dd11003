"""Session and scheduler (``api/session.py``, ``exec/``): per
``Session.run`` of a traced factorization, the host time from the
program's ``session.run`` span's start to the first task body's start on
any worker (planning, graph key, executor set-up), the median over runs,
in ms.  Read from the program's flight recorder (``run.program``,
``bench/program_spans.py``); without it, or when the recorder dropped
events of the window, nothing is read.  Moves ``chol_gflops``."""

from bench import program_spans


def read(run):
    ps = getattr(run, "program", None)
    if ps is None:
        return None
    return program_spans.median_ms(ps.run_setups_s())
