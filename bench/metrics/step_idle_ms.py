"""Engine (``serving/engine.py`` step loop): the device's idle time (one
minus the union of the first device's operations) inside each program
``engine.step`` span that lies wholly in the traced window, the median
over steps, in ms.  The spans come from the program's flight recorder
(``run.program``, ``bench/program_spans.py``), on the device trace's
clock; without them, or when the recorder dropped events of the window,
nothing is read.  Moves ``tok_s``."""

from bench import program_spans


def read(run):
    ps = getattr(run, "program", None)
    summary = getattr(run, "summary", None)
    if ps is None or ps.dropped or ps.clock is None or summary is None:
        return None
    steps = ps.phase_spans("engine.step")
    return program_spans.median_ms(
        program_spans.step_idle_s(run.window.trace, summary.window_ns, steps))
