"""What the harness hands a driver, and what a driver hands back."""

from __future__ import annotations

import dataclasses
import math
import sys
from typing import Any, Dict, List, Tuple

from bench import registry


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def compare(numbers: Dict[str, float], limits: Dict[str, Dict[str, Any]]
            ) -> List[Tuple[str, float, float]]:
    """(name, number, limit) for each number a cell's limits file names; a
    number the run could not read compares as infinite."""
    return [(name, float(numbers.get(name, math.inf)), float(lim["limit"]))
            for name, lim in limits.items()]


def verdict(checks: List[Tuple[str, float, float]]) -> bool:
    """``correct``: every number compared is finite and within its limit."""
    return all(math.isfinite(v) and v <= lim for _, v, lim in checks)


@dataclasses.dataclass
class Result:
    """What a driver's ``run(ctx)`` returns."""

    attempted: int
    failed: int
    setup_s: float
    end_to_end: Dict[str, float]
    run: Any                         # what the cell's per-layer readers read
    checks: List[Tuple[str, float, float]]   # (name, value, limit)
    memory_peak_bytes: int
    summary: Any = None              # trace_reduce.Summary of a traced run


@dataclasses.dataclass
class Context:
    """What a driver is given."""

    cell: registry.Cell
    seed: int
    seconds: float
    trace: bool
    t_process: float
    device_kind: str
    limits: Dict[str, Dict[str, Any]]

    def log(self, msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)

    def checks(self, numbers: Dict[str, float]) -> List[Tuple[str, float, float]]:
        """Each number the cell's limits file names, beside its limit."""
        return compare(numbers, self.limits)

    def memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip the cell uses."""
        import jax

        peak = 0
        for dev in jax.local_devices()[:self.cell.chips]:
            st = dev.memory_stats() or {}
            peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
        return peak
