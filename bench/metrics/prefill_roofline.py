"""Kernels (the compiled prefill programs): the least time of each traced
prefill (its FLOPs at peak, or its weights and K/V at full bandwidth,
whichever is longer) over the device time of the harness's
``bench_prefill_<L>`` programs, summed, in percent.  Moves
``ttft_p90_ms``."""

from bench.readers import roofline_percent


def read(run):
    return roofline_percent(run, "bench_prefill")
