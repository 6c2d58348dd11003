"""The device a process computes on, and where its compiled programs live.

Entry points (``chip_smoke.py``, ``examples/serve_lm.py``, the benchmarks)
call :func:`describe_device` once and print what it returns, so every
result names the device it came from.  A command that measures the chip
passes ``require_tpu=True`` and stops when there is none: nothing here
falls back to the CPU.

:func:`enable_compile_cache` points JAX's persistent compilation cache at
one fixed directory.  Entry points call it before their first compile;
library modules and tests never do.
"""

from __future__ import annotations

import dataclasses
import os
import pathlib

__all__ = ["CHECKOUT_ROOT", "Device", "NoAcceleratorError", "describe_device",
           "enable_compile_cache", "refuse_child_processes"]

#: The checkout this module was imported from (``src/repro/device.py``).
CHECKOUT_ROOT = pathlib.Path(__file__).resolve().parents[2]


class NoAcceleratorError(RuntimeError):
    """A command that measures the chip found no TPU."""


@dataclasses.dataclass(frozen=True)
class Device:
    """What JAX reports for this process: ``jax.devices()[0].platform``,
    its ``device_kind`` and ``len(jax.devices())``."""

    platform: str
    kind: str
    count: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)

    def __str__(self) -> str:
        return f"{self.platform} ({self.kind}) x{self.count}"


def describe_device(*, require_tpu: bool = False) -> Device:
    """The devices JAX computes on.  With ``require_tpu`` a process that
    found no TPU raises :class:`NoAcceleratorError` instead of going on
    with the CPU."""
    import jax

    devices = jax.devices()
    dev = Device(devices[0].platform, devices[0].device_kind, len(devices))
    if require_tpu and dev.platform != "tpu":
        raise NoAcceleratorError(
            f"no TPU present: JAX computes on {dev}. This command runs on "
            "the chip and does not fall back to the CPU.")
    return dev


def refuse_child_processes(what: str) -> None:
    """Raise before ``what`` spawns worker processes from a process that
    computes on a TPU.  A chip belongs to one process: the parent holds it
    once it has touched JAX, and a child that needs it then fails or
    hangs at its first JAX call."""
    import jax

    if jax.default_backend() == "tpu":
        raise RuntimeError(
            f"{what} would start worker processes, but this process owns "
            "the TPU and a chip serves one process. Run in one process "
            "(procs=None).")


def enable_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in one fixed directory and
    return it.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    uses it and nothing else is set.  Otherwise the cache goes to
    ``.jax_cache/`` at the checkout root: the directory is part of what a
    later run looks up, so it never depends on a process id or the time."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT_ROOT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path
