"""Kernels (the Gated DeltaNet mixer, ``models/gdn.py``): the least time of
the work under the program's ``gdn`` scope in the traced steps (each
prefill's projections and chunked delta rule, each lane's decode call's
weights and state, at peak FLOP/s or bandwidth, whichever is longer) over
the device time of the operations under that scope, in percent
(``bench/op_scopes.py``).  Moves ``tok_s``."""

from bench.op_scopes import scope_roofline


def read(run):
    return scope_roofline(run, "gdn")
