"""Kernels (the dropless expert layer, ``models/layers.py``
``moe_dropless``): the least time of the work under the program's ``moe``
scope in the traced steps (router, shared expert and the routed pairs the
routing counters recorded; the held experts touched, read once) over the
device time of the operations under that scope, in percent
(``bench/op_scopes.py``).  Moves ``tok_s``."""

from bench.op_scopes import scope_roofline


def read(run):
    return scope_roofline(run, "moe")
