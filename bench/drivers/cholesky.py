"""Driver of factorization cells: the paper's tiled Cholesky through the
program's task graph.

Set-up makes the seed's SPD matrix on the device (``bench/reference/
cholesky.py``), builds a ``repro.Session`` with the program's default
scheduler and policy, and factors one matrix untimed, so that every tile
kernel is compiled and every worker is warm.

The window runs factorizations back to back, each doing what a user's
call does: ``to_tiles``, ``build_cholesky_graph``, ``Session.run``,
``cholesky_extract``, ending in ``block_until_ready``.  Factorization ``j``
factors ``A0 + s_j I``.  The window ends with the first factorization to
end after ``--seconds``, so it holds whole factorizations only.

A sample of the window's factors, drawn from the seed (reservoir sampling,
so no factor outside it is kept), is compared after the window with the
float64 LAPACK factor of the same matrix.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import statistics
import tempfile
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import trace_reduce
from bench.reference import cholesky as ref
from bench.reference import seed_key
from bench.work import cholesky_flops

_NOT_TASKS = ("idle", "steal", "switch", "barrier")


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    count: int
    durations: List[float]
    kept: List[Tuple[int, float, Any]]        # (index, shift, factor)
    sched_gaps_s: List[float]
    trace: Optional[trace_reduce.TraceData] = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open


def task_gaps(trace) -> List[float]:
    """Per worker, the host time from one task body's end to the next
    one's start, from the program's flight-recorder trace."""
    by_worker: Dict[int, List[Any]] = {}
    for ev in trace.events:
        if ev.kind not in _NOT_TASKS:
            by_worker.setdefault(ev.worker, []).append(ev)
    gaps = []
    for evs in by_worker.values():
        evs.sort(key=lambda e: e.t0)
        gaps.extend(b.t0 - a.t1 for a, b in zip(evs, evs[1:]) if b.t0 >= a.t1)
    return gaps


class CholeskyCell:
    def __init__(self, cell):
        self.cell = cell
        self.n = int(cell.config["n"])
        self.b = int(cell.traffic["tile"])
        if self.n % self.b:
            raise ValueError(f"tile {self.b} does not divide n = {self.n}")
        self.nb = self.n // self.b
        self.workers = int(cell.traffic["workers"])
        self.shift_lo, self.shift_hi = (float(x) for x in cell.traffic["shift_range"])
        self.a0 = None

    def load(self, seed: int) -> None:
        self.a0 = ref.make_spd(seed_key(seed), self.n)
        self.a0.block_until_ready()

    def shifts(self, seed: int):
        rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 2]))
        while True:
            yield float(rng.uniform(self.shift_lo, self.shift_hi))

    def factor(self, session, shift: float):
        """One factorization as a user calls it; returns the factor."""
        import jax
        import jax.numpy as jnp

        from repro.linalg import build_cholesky_graph, cholesky_extract, to_tiles

        ann = jax.profiler.TraceAnnotation
        with ann("bench.generate"):
            a = ref.shifted(self.a0, jnp.float32(shift))
        with ann("bench.to_tiles"):
            store = to_tiles(a, self.b)
        with ann("bench.graph_build"):
            graph = build_cholesky_graph(self.nb, self.b, store=store)
        with ann("bench.session_run"):
            report = session.run(graph, timeout=600.0)
        with ann("bench.extract"):
            l = cholesky_extract(store)
            l.block_until_ready()
        return l, report

    def serve(self, seed: int, seconds: float, *, trace: bool = False,
              keep: int = 2) -> Window:
        import jax

        import repro

        shifts = self.shifts(seed)
        rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 3]))
        trace_s = float(self.cell.traffic.get("trace_seconds", 3.0))
        profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
        session = repro.Session(self.workers, trace=trace)
        kept: List[Tuple[int, float, Any]] = []
        durations: List[float] = []
        gaps: List[float] = []
        td = None
        traced = None
        try:
            self.factor(session, next(shifts))
            t_open = t_close = time.perf_counter()
            j = 0
            while True:
                t0 = time.perf_counter()
                if t0 - t_open >= seconds:
                    break
                lead = max(trace_s, 1.5 * durations[0]) if durations else trace_s
                if trace and traced is None and t0 >= t_open + seconds - lead:
                    jax.profiler.start_trace(profile_dir)
                    traced = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
                    traced.__enter__()
                shift = next(shifts)
                with jax.profiler.TraceAnnotation("bench.factorization"):
                    l, report = self.factor(session, shift)
                t_close = time.perf_counter()
                durations.append(t_close - t0)
                if traced is not None and report.trace is not None:
                    gaps.extend(task_gaps(report.trace))
                if j < keep:
                    kept.append((j, shift, l))
                else:
                    r = int(rng.integers(0, j + 1))
                    if r < keep:
                        kept[r] = (j, shift, l)
                del l, report
                j += 1
            if traced is not None:
                traced.__exit__(None, None, None)
                jax.profiler.stop_trace()
                td = trace_reduce.load(trace_reduce.find_xplane(profile_dir))
        finally:
            session.close()
            if profile_dir is not None:
                shutil.rmtree(profile_dir, ignore_errors=True)
        return Window(t_open, t_close, j, durations, kept, gaps, td)

    def check(self, kept) -> float:
        """The largest relative error of a kept factor against float64
        LAPACK on the same matrix."""
        a0 = np.asarray(self.a0, np.float64)
        errs = [ref.relative_error(np.asarray(l),
                                   ref.reference_factor(a0 + shift * np.eye(self.n)))
                for _, shift, l in kept]
        if not errs or not all(np.isfinite(errs)):
            return float("inf")
        return max(errs)

    def free(self) -> None:
        if self.a0 is not None:
            self.a0.delete()
        self.a0 = None
        gc.collect()


@dataclasses.dataclass
class CholRun:
    """What the per-layer readers of a factorization cell read."""

    window: Window
    summary: Optional[trace_reduce.Summary]

    @property
    def sched_gaps_s(self) -> List[float]:
        return self.window.sched_gaps_s


def run(ctx):
    """One run of a factorization cell (see ``bench/run.py``)."""
    from bench.harness import Result

    cc = CholeskyCell(ctx.cell)
    cc.load(ctx.seed)
    keep = int(ctx.cell.traffic.get("check_factorizations", 2))
    w = cc.serve(ctx.seed, ctx.seconds, trace=ctx.trace, keep=keep)
    setup_s = w.t_open - ctx.t_process
    ctx.log(f"{w.count} factorizations of n={cc.n} in {cc.nb}x{cc.nb} tiles of {cc.b} "
            f"in {w.seconds:.3f} s; each {statistics.median(w.durations):.4f} s at the "
            f"median")
    memory_peak = ctx.memory_peak()
    gflops = w.count * cholesky_flops(cc.n) / w.seconds / 1e9
    summary = trace_reduce.summarize(w.trace) if w.trace else None
    err = cc.check(w.kept)
    ctx.log(f"check: {len(w.kept)} factors (indices {[k[0] for k in w.kept]}) compared "
            "with float64 LAPACK")
    w.kept = []
    cc.free()
    return Result(attempted=w.count, failed=0, setup_s=setup_s,
                  end_to_end={"chol_gflops": gflops}, run=CholRun(w, summary),
                  checks=ctx.checks({"factor_rel_error": err}),
                  memory_peak_bytes=memory_peak, summary=summary)
