"""Device: one minus the union of the device's operation intervals over
the traced window.  Split by the end-to-end metric it moves:
``device_idle_frac.batch`` (``tok_s``), ``device_idle_frac.chol``
(``chol_gflops``)."""

from bench.readers import idle_fraction


def read(run):
    return idle_fraction(run)
