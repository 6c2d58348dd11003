"""Model step (``models/lm.py``): the share of the device's busy time in
the traced window spent in the harness's ``bench_prefill_<L>`` programs.
Moves ``itl_p95_ms``: a prefill runs between two decode steps."""


def read(run):
    s = run.summary
    if s is None or s.busy_s <= 0:
        return None
    return s.program_seconds("bench_prefill") / s.busy_s
