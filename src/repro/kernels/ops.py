"""jit'd public wrappers for the Pallas kernels.

Every caller names the implementation with ``mode``; nothing is chosen
from the device, so a kernel never silently becomes the reference:

* ``pallas``    — the kernel, compiled for the TPU
* ``interpret`` — kernel body interpreted in Python (CPU validation)
* ``ref``       — pure-jnp oracle
"""

from __future__ import annotations

from . import ref as _ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .ssd_scan import ssd_scan as _ssd_pallas, ssd_scan_ref as _ssd_ref
from .tile_matmul import tile_matmul as _mm_pallas


_MODES = ("pallas", "interpret", "ref")


def _resolve(mode: str) -> str:
    if mode not in _MODES:
        raise ValueError(f"unknown kernel mode {mode!r}; valid modes: "
                         f"{', '.join(_MODES)}")
    return mode


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    mode: str, **kw):
    m = _resolve(mode)
    if m == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    return _flash_pallas(q, k, v, causal=causal, window=window,
                         interpret=(m == "interpret"), **kw)


def decode_attention(q, k, v, length, *, mode: str, **kw):
    m = _resolve(mode)
    if m == "ref":
        return _ref.decode_attention_ref(q, k, v, length)
    return _decode_pallas(q, k, v, length, interpret=(m == "interpret"), **kw)


def ssd_scan(xdt, cs, Bm, Cm, *, mode: str):
    m = _resolve(mode)
    if m == "ref":
        return _ssd_ref(xdt, cs, Bm, Cm)
    return _ssd_pallas(xdt, cs, Bm, Cm, interpret=(m == "interpret"))


def tile_matmul(a, b, *, mode: str, **kw):
    m = _resolve(mode)
    if m == "ref":
        return _ref.tile_matmul_ref(a, b)
    return _mm_pallas(a, b, interpret=(m == "interpret"), **kw)
