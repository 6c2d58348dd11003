"""Wall-clock microbenchmarks of the REAL threaded runtime (JAX CPU ops
release the GIL):

* ``wallclock_overlap`` — hybrid vs history victim selection on an
  overlap-structured graph (comm sleeps hidden behind GEMM floods);
* ``warm_reuse`` — dynamic scheduling on one persistent ``Session`` (warm
  leased workers, the unified-executor-core path) vs a fresh private-core
  ``Session`` per run (thread spawn + queue allocation per request, the
  pre-refactor ``run_graph`` cost model).  The refactor's contract: warm
  dynamic scheduling is no slower than per-run-thread scheduling at every
  worker count (``no_slower`` per row, asserted by the CI smoke job and
  gated against the committed noise floor by ``benchmarks.perf_gate``);
* ``suspend_frames`` — fan-in communication (producers feeding consumers
  over a :class:`~repro.core.Channel`) with *blocking* plain-body consumers
  (each pins a worker work-conservingly) vs *suspendable* generator-frame
  consumers (each parks worker-free).  Contract: suspendable bodies are no
  slower at equal workers (``no_slower`` per row, asserted in CI);
* ``trace_off`` — the flight recorder's off-switch cost: the same warm
  session serving the same graphs with ``trace=False`` vs ``trace=True``.
  Contract: tracing OFF is no slower than tracing ON (the no-op emitter
  adds no measurable per-event cost; ``no_slower`` per row, gated like
  ``warm_reuse``);
* ``victim_frames`` — stats-driven frame-aware victim selection
  (``frame_hybrid``, fed per-run trace metrics through
  ``VictimPolicy.observe``) vs the paper hybrid on a skewed fan-in of
  suspendable frames.  Contract: frame_hybrid is no slower;
* ``compiled_linalg`` — a Cholesky sweep served by the ``compiled``
  scheduler (recordings lowered to fused jitted serial programs) vs
  ``replay`` and ``dynamic`` on the same warm substrate, with the
  driver-measured ``dispatch_overhead_fraction`` against replay's traced
  equivalent.  Contract: compiled is no slower than replay;
* ``async_overlap`` — ``Session.submit`` (async: the client builds request
  ``i+1`` — data prep + graph construction — while request ``i`` executes)
  vs the blocking build-then-run loop, same graphs, same warm session.
  Contract: pipelined submission is no slower than the serial loop;
* ``resource_contention`` — a shared-accumulator workload (skewed computes
  each feeding an update of ONE accumulator) serialized two ways: a chain
  of dependency *edges* between the updates (pins an order nobody needs)
  vs one exclusive :class:`~repro.resources.Resource` shared by the
  updates with **no cross-edges** (the arbiter serializes them in finish
  order).  Contract: the resource variant is no slower than the edge
  variant — conflicts-without-dependencies never lose to fake edges.

Every row carries ``noise`` — the observed relative spread ``(max-min)/min``
across its repeats — which the CI workflow surfaces per run: the first step
toward turning the bench-smoke job into a perf-regression gate (thresholds
need a characterized noise floor first).

Emits CSV rows (benchmarks.common schema) and ``BENCH_runtime.json``.
Env knobs: ``BENCH_SMOKE=1`` shrinks sizes for CI; ``BENCH_RUNTIME_JSON``
overrides the output path.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List

import numpy as np

import repro
from repro.core import Channel, TaskGraph
from repro.resources import Resource

SMOKE = os.environ.get("BENCH_SMOKE", "") not in ("", "0")
WORKERS = (1, 2) if SMOKE else (1, 2, 4)
FRAME_WORKERS = (2,) if SMOKE else (2, 4)
JSON_PATH = os.environ.get("BENCH_RUNTIME_JSON", "BENCH_runtime.json")


def _spread(samples: List[float]) -> float:
    """Relative spread across repeats: (max - min) / min."""
    return round((max(samples) - min(samples)) / max(min(samples), 1e-12), 4)


def overlap_graph(n_steps: int = 6, n_children: int = 8, gemm: int = 384,
                  comm_s: float = 0.03) -> TaskGraph:
    """Cholesky-shaped: per step, a comm task (sleep) on the critical path
    and a flood of GEMM children that can hide it."""
    g = TaskGraph("wall-overlap")
    rng = np.random.default_rng(0)
    mats = [np.asarray(rng.standard_normal((gemm, gemm)), np.float32)
            for _ in range(2)]

    def gemm_task(ctx):
        return float(np.linalg.norm(mats[0] @ mats[1]))

    def comm_task(ctx):
        time.sleep(comm_s)

    prev_comm = None
    prev_join = None
    for k in range(n_steps):
        pdeps = [t for t in (prev_comm,) if t is not None]
        panel = g.add(gemm_task, name=f"panel[{k}]", kind="panel", deps=pdeps)
        comm = g.add(comm_task, name=f"bcast[{k}]", kind="comm", deps=[panel])
        parent_deps = [comm] + ([prev_join] if prev_join is not None else [])
        parent = g.add(lambda ctx: None, name=f"trail*[{k}]", deps=parent_deps)
        children = [g.add(gemm_task, name=f"tr[{k}.{j}]", deps=[parent])
                    for j in range(n_children)]
        prev_join = g.add(lambda ctx: None, name=f"join[{k}]", deps=children)
        prev_comm = comm
    return g


def bench(workers: int = 4, repeats: int = 3) -> List[dict]:
    steps, children, gemm = (3, 4, 128) if SMOKE else (6, 8, 384)
    comm_s = 0.01 if SMOKE else 0.03
    rows = []
    for policy in ("history", "hybrid"):
        times = []
        for r in range(repeats):
            g = overlap_graph(steps, children, gemm, comm_s)
            with repro.Session(workers, policy=policy, seed=r) as session:
                t0 = time.perf_counter()
                session.run(g, timeout=120.0)
                times.append(time.perf_counter() - t0)
        best = min(times)
        rows.append({
            "bench": "wallclock_overlap", "policy": policy,
            "workers": workers,
            "best_s": round(best, 3),
            "us_per_call": round(best * 1e6, 1),
            "noise": _spread(times),
        })
    return rows


def reuse_graph(n_tasks: int = 48) -> TaskGraph:
    """Small mixed-fanout graph of trivial bodies: per-run scheduling and
    construction overhead dominate, which is exactly what warm reuse
    eliminates."""
    g = TaskGraph("reuse")
    root = g.add(lambda ctx: 0, name="root")
    mids = [g.add(lambda ctx, i=i: i, deps=[root], name=f"m{i}")
            for i in range(n_tasks)]
    g.add(lambda ctx: sum(ctx.dep_results()), deps=mids, name="join")
    return g


def bench_reuse(workers: int, iters: int = 10, repeats: int = 5) -> Dict:
    """Best-of-``repeats`` mean per-run wall clock: a fresh private-core
    Session per run (per-run thread spawn — what every pre-refactor
    ``run_graph`` call paid) vs one persistent Session serving every run
    on warm leased workers."""
    graphs = [reuse_graph() for _ in range(iters)]
    with repro.Session(workers) as s:
        s.run(graphs[0])                              # warm imports/JIT paths
    fresh_times: List[float] = []
    warm_times: List[float] = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for g in graphs:
            with repro.Session(workers, shared_cores=False) as session:
                session.run(g)
        fresh_times.append((time.perf_counter() - t0) / iters)
    with repro.Session(workers) as session:
        session.run(graphs[0])                        # spawn outside the clock
        for _ in range(repeats):
            t0 = time.perf_counter()
            for g in graphs:
                session.run(g)
            warm_times.append((time.perf_counter() - t0) / iters)
    fresh_best, warm_best = min(fresh_times), min(warm_times)
    return {
        "bench": "warm_reuse", "workers": workers,
        "fresh_ms": round(fresh_best * 1e3, 4),
        "warm_ms": round(warm_best * 1e3, 4),
        "speedup": round(fresh_best / warm_best, 3),
        # generous noise headroom: the claim is "no slower", not "faster"
        "no_slower": bool(warm_best <= fresh_best * 1.25),
        "noise": _spread(warm_times),
    }


def bench_trace_off(workers: int, iters: int = 10, repeats: int = 5) -> Dict:
    """Tracing-off vs tracing-on on warm sessions serving the same graphs.
    The observability contract is that the OFF path costs nothing: hot
    loops call the module-level no-op emitter (one attribute call, zero
    allocation), so ``off_ms <= on_ms * headroom`` must hold."""
    graphs = [reuse_graph() for _ in range(iters)]
    times: Dict[bool, List[float]] = {False: [], True: []}
    for traced in (False, True):
        with repro.Session(workers, trace=traced) as session:
            session.run(graphs[0])                    # spawn outside the clock
            for _ in range(repeats):
                t0 = time.perf_counter()
                for g in graphs:
                    session.run(g)
                times[traced].append((time.perf_counter() - t0) / iters)
    off_best, on_best = min(times[False]), min(times[True])
    return {
        "bench": "trace_off", "workers": workers,
        "off_ms": round(off_best * 1e3, 4),
        "on_ms": round(on_best * 1e3, 4),
        "overhead": round(on_best / off_best, 3),
        # the gated claim: the off-switch is free (off is no slower than
        # on, with the same noise headroom the other rows use)
        "no_slower": bool(off_best <= on_best * 1.25),
        "noise": _spread(times[False]),
    }


def frames_graph(n_pairs: int, use_frames: bool, work_s: float) -> TaskGraph:
    """Fan-in communication: ``n_pairs`` consumers each receive one token
    from a channel fed by ``n_pairs`` independent producers (each doing
    ``work_s`` of off-GIL 'compute').  Blocking consumers pin their worker
    at ``ctx.recv`` (work-conservingly); suspendable consumers park."""
    g = TaskGraph("suspend" if use_frames else "blocking")
    ch = Channel("bench.tokens")
    for i in range(n_pairs):
        if use_frames:
            def body(ctx, i=i):
                v = yield ctx.recv(ch)
                return v
        else:
            def body(ctx, i=i):
                return ctx.recv(ch)
        g.add(body, name=f"cons{i}")
    for i in range(n_pairs):
        def prod(ctx, i=i):
            time.sleep(work_s)
            ch.send(i)
        g.add(prod, name=f"prod{i}")
    return g


def bench_frames(workers: int, repeats: int = 3) -> Dict:
    """Blocking-body vs suspendable-body throughput on the same fan-in
    graph.  Contract: suspendable is no slower at equal workers."""
    n_pairs = 6 if SMOKE else 12
    work_s = 0.001 if SMOKE else 0.002
    samples: Dict[str, List[float]] = {"blocking": [], "suspend": []}
    with repro.Session(workers) as warm:
        warm.run(frames_graph(n_pairs, True, work_s))         # warm paths
    for _ in range(repeats):
        for mode in ("blocking", "suspend"):
            g = frames_graph(n_pairs, mode == "suspend", work_s)
            # per-request session, spawn included in the timed window —
            # the serving-loop cost model this row has always measured
            t0 = time.perf_counter()
            with repro.Session(workers, shared_cores=False) as session:
                session.run(g, timeout=120.0)
            samples[mode].append(time.perf_counter() - t0)
    blocking_best = min(samples["blocking"])
    suspend_best = min(samples["suspend"])
    return {
        "bench": "suspend_frames", "workers": workers, "pairs": n_pairs,
        "blocking_ms": round(blocking_best * 1e3, 3),
        "suspend_ms": round(suspend_best * 1e3, 3),
        "speedup": round(blocking_best / suspend_best, 3),
        "no_slower": bool(suspend_best <= blocking_best * 1.25),
        "noise": _spread(samples["suspend"]),
    }


def skewed_frames_graph(n_pairs: int, work_s: float) -> TaskGraph:
    """Skewed fan-in: a single root fans every producer out onto ONE
    worker's deque, the consumers are suspendable frames waiting on the
    channel — the shape where victim selection decides whether the fan-in
    drains in parallel or serializes behind the root's worker."""
    g = TaskGraph("victim-frames")
    ch = Channel("bench.skew")
    for i in range(n_pairs):
        def body(ctx, i=i):
            v = yield ctx.recv(ch)
            return v
        g.add(body, name=f"cons{i}")
    root = g.add(lambda ctx: None, name="root")
    for i in range(n_pairs):
        def prod(ctx, i=i):
            time.sleep(work_s)
            ch.send(i)
        g.add(prod, deps=[root], name=f"prod{i}")
    return g


def bench_victim_frames(workers: int, iters: int = 5, repeats: int = 3) -> Dict:
    """Frame-aware (``frame_hybrid``) vs paper-hybrid victim selection on
    the skewed fan-in graph.  One persistent *traced* session per policy:
    every run's trace metrics are fed back through ``VictimPolicy.observe``,
    so the stats-driven policy steers later runs from earlier feedback
    (``frame_resumes_by_worker`` + per-victim steal hit rates).  Contract:
    frame_hybrid is no slower than hybrid."""
    n_pairs = 8 if SMOKE else 16
    work_s = 0.001 if SMOKE else 0.002
    best: Dict[str, float] = {}
    noise = 0.0
    for policy in ("hybrid", "frame_hybrid"):
        times: List[float] = []
        with repro.Session(workers, policy=policy, trace=True) as session:
            session.run(skewed_frames_graph(n_pairs, work_s),
                        timeout=120.0)                # warm + first feedback
            for _ in range(repeats):
                t0 = time.perf_counter()
                for _ in range(iters):
                    session.run(skewed_frames_graph(n_pairs, work_s),
                                timeout=120.0)
                times.append((time.perf_counter() - t0) / iters)
        best[policy] = min(times)
        if policy == "frame_hybrid":
            noise = _spread(times)
    return {
        "bench": "victim_frames", "workers": workers, "pairs": n_pairs,
        "hybrid_ms": round(best["hybrid"] * 1e3, 3),
        "frame_ms": round(best["frame_hybrid"] * 1e3, 3),
        "speedup": round(best["hybrid"] / best["frame_hybrid"], 3),
        "no_slower": bool(best["frame_hybrid"] <= best["hybrid"] * 1.25),
        "noise": noise,
    }


def bench_compiled_linalg(workers: int, repeats: int = 4) -> Dict:
    """One Cholesky shape swept dynamic vs replay vs compiled on warm
    sessions (fresh tiles per run, identical SPD input).  The compiled
    scheduler records on the first run and serves every later run from the
    fused serial program; its driver reports
    ``dispatch_overhead_fraction`` directly (time outside kernel bodies),
    compared against the replay executor's traced equivalent.  Contract:
    compiled is no slower than replay."""
    import jax.numpy as jnp

    from repro.linalg import build_cholesky_graph, random_spd, to_tiles

    nb, b = (4, 16) if SMOKE else (6, 32)
    a = random_spd(nb * b, seed=0, dtype=jnp.float32)

    def sweep(scheduler: str):
        times: List[float] = []
        last = None
        with repro.Session(workers, scheduler=scheduler) as session:
            for _ in range(2):       # warm jit + the recording iteration
                store = to_tiles(a, b)
                session.run(build_cholesky_graph(nb, b, store=store),
                            timeout=120.0)
            for _ in range(repeats):
                store = to_tiles(a, b)
                g = build_cholesky_graph(nb, b, store=store)
                t0 = time.perf_counter()
                last = session.run(g, timeout=120.0)
                times.append(time.perf_counter() - t0)
        return times, last

    dyn_times, _ = sweep("dynamic")
    rep_times, _ = sweep("replay")
    cmp_times, cmp_report = sweep("compiled")
    # replay's overhead fraction needs the flight recorder (untimed pass);
    # the compiled driver measures its own (1 - body_s / wall_s)
    with repro.Session(workers, scheduler="replay", trace=True) as session:
        rep_traced = None
        for _ in range(3):
            store = to_tiles(a, b)
            rep_traced = session.run(build_cholesky_graph(nb, b, store=store),
                                     timeout=120.0)
    replay_overhead = (rep_traced.trace.metrics()["dispatch_overhead_fraction"]
                       if rep_traced.trace is not None else None)
    dyn_best, rep_best, cmp_best = min(dyn_times), min(rep_times), min(cmp_times)
    return {
        "bench": "compiled_linalg", "workers": workers, "nb": nb, "b": b,
        "dynamic_ms": round(dyn_best * 1e3, 3),
        "replay_ms": round(rep_best * 1e3, 3),
        "compiled_ms": round(cmp_best * 1e3, 3),
        "speedup_vs_dynamic": round(dyn_best / cmp_best, 3),
        "speedup_vs_replay": round(rep_best / cmp_best, 3),
        "compiled_overhead_fraction": round(
            float(cmp_report.stats.get("dispatch_overhead_fraction", 0.0)), 4),
        "replay_overhead_fraction": (round(float(replay_overhead), 4)
                                     if replay_overhead is not None else None),
        "segments": int(cmp_report.stats.get("segments", 0)),
        "fused_tasks": int(cmp_report.stats.get("fused_tasks", 0)),
        "no_slower": bool(cmp_best <= rep_best * 1.25),
        "noise": _spread(cmp_times),
    }


def bench_async_overlap(workers: int, iters: int = 8,
                        repeats: int = 3) -> Dict:
    """Blocking build-then-run loop vs ``Session.submit`` pipelining.

    Each request is a realistic client turn: *build* (seeded data prep +
    graph construction, GIL-bound on the caller) then *run* (sleep-bodied
    tasks — off-GIL waiting, like device execution).  The serial loop pays
    ``iters * (build + run)``; the submit loop builds request ``i+1``
    while request ``i`` executes, so builds vanish into execution time.
    Contract: pipelining is no slower (and on any box, strictly hides the
    build cost up to noise)."""
    gemm = 256 if SMOKE else 384
    sleep_s = 0.004
    n_sleep = max(2, workers)

    def build(seed: int) -> TaskGraph:
        rng = np.random.default_rng(seed)
        mats = [np.asarray(rng.standard_normal((gemm, gemm)), np.float32)
                for _ in range(4)]                    # client-side prep
        g = TaskGraph("async-overlap")
        for i in range(n_sleep):
            def body(ctx, i=i):
                time.sleep(sleep_s)
                return i
            g.add(body, name=f"io{i}")
        g.add(lambda ctx: float(np.linalg.norm(mats[0] + mats[-1])),
              name="checksum")
        return g

    serial_times: List[float] = []
    overlap_times: List[float] = []
    with repro.Session(workers) as session:
        session.run(build(0))                         # warm paths
        for _ in range(repeats):
            t0 = time.perf_counter()
            for i in range(iters):
                session.run(build(i))
            serial_times.append((time.perf_counter() - t0) / iters)
    with repro.Session(workers) as session:
        session.run(build(0))
        for _ in range(repeats):
            t0 = time.perf_counter()
            fut = session.submit(build(0))
            for i in range(1, iters):
                nxt = build(i)                        # overlaps fut's run
                fut.result(timeout=120.0)
                fut = session.submit(nxt)
            fut.result(timeout=120.0)
            overlap_times.append((time.perf_counter() - t0) / iters)
    serial_best, overlap_best = min(serial_times), min(overlap_times)
    return {
        "bench": "async_overlap", "workers": workers, "iters": iters,
        "serial_ms": round(serial_best * 1e3, 4),
        "overlap_ms": round(overlap_best * 1e3, 4),
        "speedup": round(serial_best / overlap_best, 3),
        "no_slower": bool(overlap_best <= serial_best * 1.25),
        "noise": _spread(overlap_times),
    }


def contention_graph(use_resource: bool, n_tasks: int, acc: List[int],
                     compute_s: float, write_s: float) -> TaskGraph:
    """Skewed computes each feeding an update of ONE shared accumulator.
    ``use_resource=False`` serializes the updates with a chain of edges
    (update ``i`` must wait for update ``i-1`` — and task 0's compute is
    the LONGEST, so the whole chain stalls behind it).  ``use_resource=True``
    drops the chain: the updates share one exclusive resource and the
    arbiter grants it in whatever order the computes finish — short
    computes' updates overlap the long computes still running."""
    g = TaskGraph("contend-res" if use_resource else "contend-edges")
    accumulator = Resource("accumulator") if use_resource else None
    prev = None
    for i in range(n_tasks):
        def compute(ctx, i=i):
            time.sleep(compute_s * (n_tasks - i) / n_tasks)   # 0 = longest
            return i

        def update(ctx, i=i):
            time.sleep(write_s)                # the guarded critical section
            acc.append(i)

        c = g.add(compute, name=f"compute{i}", kind="compute", cost=1.0)
        deps = [c] if use_resource else [c] + ([prev] if prev is not None
                                               else [])
        prev = g.add(update, name=f"update{i}", kind="comm", cost=0.2,
                     deps=deps,
                     uses=[accumulator] if use_resource else ())
    return g


def bench_resources(workers: int, repeats: int = 3) -> Dict:
    """Edge-serialized vs resource-serialized shared-accumulator updates on
    the same warm session.  Contract: resources are no slower than edges
    (same mutual exclusion, no fake ordering)."""
    n_tasks = 4 if SMOKE else 8
    compute_s = 0.004 if SMOKE else 0.01
    write_s = 0.001 if SMOKE else 0.002
    samples: Dict[str, List[float]] = {"edges": [], "resources": []}
    sums: Dict[str, int] = {}
    waits = acquires = 0
    with repro.Session(workers) as session:
        session.run(contention_graph(True, n_tasks, [], compute_s, write_s))
        for _ in range(repeats):
            for mode in ("edges", "resources"):
                acc: List[int] = []
                g = contention_graph(mode == "resources", n_tasks, acc,
                                     compute_s, write_s)
                t0 = time.perf_counter()
                rep = session.run(g, timeout=120.0)
                samples[mode].append(time.perf_counter() - t0)
                assert len(acc) == n_tasks
                sums[mode] = sum(acc)
                if mode == "resources":
                    waits = int(rep.stats.get("resource_waits", 0))
                    acquires = int(rep.stats.get("resource_acquires", 0))
    edges_best = min(samples["edges"])
    res_best = min(samples["resources"])
    return {
        "bench": "resource_contention", "workers": workers, "tasks": n_tasks,
        "edges_ms": round(edges_best * 1e3, 3),
        "resources_ms": round(res_best * 1e3, 3),
        "speedup": round(edges_best / res_best, 3),
        "resource_acquires": acquires,
        "resource_waits": waits,
        # same accumulator contents either way (order differs by design)
        "identical": bool(sums["edges"] == sums["resources"]),
        "no_slower": bool(res_best <= edges_best * 1.25),
        "noise": _spread(samples["resources"]),
    }


def write_json(rows: List[Dict], device: Dict, path: str = JSON_PATH) -> None:
    out = {
        "device": device,
        "bench": "runtime",
        "meta": {"workers": list(WORKERS), "frame_workers": list(FRAME_WORKERS),
                 "smoke": SMOKE},
        "rows": rows,
    }
    with open(path, "w") as fh:
        json.dump(out, fh, indent=1)


def main():
    from .common import emit, start
    device = start("bench_runtime")
    overlap_rows = bench(workers=2 if SMOKE else 4)
    emit(overlap_rows)
    print()
    reuse_rows = [bench_reuse(w) for w in WORKERS]
    emit(reuse_rows)
    print()
    trace_rows = [bench_trace_off(w) for w in WORKERS]
    emit(trace_rows)
    print()
    frame_rows = [bench_frames(w) for w in FRAME_WORKERS]
    emit(frame_rows)
    print()
    victim_rows = [bench_victim_frames(w) for w in FRAME_WORKERS]
    emit(victim_rows)
    print()
    compiled_rows = [bench_compiled_linalg(w) for w in FRAME_WORKERS]
    emit(compiled_rows)
    print()
    async_rows = [bench_async_overlap(w) for w in WORKERS]
    emit(async_rows)
    print()
    resource_rows = [bench_resources(w) for w in FRAME_WORKERS]
    emit(resource_rows)
    write_json(overlap_rows + reuse_rows + trace_rows + frame_rows
               + victim_rows + compiled_rows + async_rows + resource_rows,
               device)
    print(f"# wrote {JSON_PATH}")


if __name__ == "__main__":
    main()
