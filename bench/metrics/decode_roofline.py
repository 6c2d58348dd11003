"""Kernels (the compiled decode programs): the least time of the traced
decode steps (every weight read once per step over its lanes, each lane's
valid K/V read, or the FLOPs at peak, whichever is longer) over the device
time of the harness's ``bench_decode`` programs in those steps, in
percent.  Moves ``tok_s``."""

from bench.readers import roofline_percent


def read(run):
    return roofline_percent(run, "bench_decode")
