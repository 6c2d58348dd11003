"""Batched launch of ready sibling tasks in the dynamic executor.

A worker that takes a task carrying a ``jit_safe`` FuseSpec also takes the
ready siblings next to it in its deque that share its kernel, arity and
priority, and runs them as one jitted program.  These tests hold that to
the same results as launching every body alone (the same graph with its
specs stripped), count the launches it saves, check that graphs without
such specs never batch, that a recording made while batching replays
bit-identically, and that the programs it builds stay bounded.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro
from repro.compile.fuse import BATCH_MAX, batch_counts, batch_limit
from repro.linalg import (
    build_cholesky_graph,
    build_lu_graph,
    build_qr_graph,
    cholesky_extract,
    lu_extract,
    qr_extract_r,
    random_diagdom,
    random_spd,
    to_tiles,
)
from repro.replay import GraphCache
from repro.serving import ContinuousBatchingEngine
from repro.serving.workload import constant_prompt_requests

NB, B = 8, 8


def _cholesky(workers=2):
    mat = random_spd(NB * B, seed=11)
    return (mat, lambda st: build_cholesky_graph(NB, B, store=st),
            lambda st: (cholesky_extract(st),))


def _lu(workers=2):
    mat = random_diagdom(NB * B, seed=11)
    return (mat, lambda st: build_lu_graph(NB, B, store=st, panel_threads=workers),
            lu_extract)


def _qr(workers=2):
    mat = random_spd(NB * B, seed=11)
    return (mat, lambda st: build_qr_graph(NB, B, store=st, panel_threads=workers),
            lambda st: (qr_extract_r(st),))


FACTORIZATIONS = {"cholesky": _cholesky, "lu": _lu, "qr": _qr}


def _unbatched(session, graph):
    """``graph`` as a task graph whose tasks carry no FuseSpec, so that
    every body launches alone."""
    tg = session._as_taskgraph(graph)
    for task in tg.tasks:
        task.meta.pop("fuse", None)
    return tg


def _factor(workers, mat, builder, extract, *, strip=False, **session_kw):
    store = to_tiles(mat, B)
    with repro.Session(workers, **session_kw) as s:
        graph = builder(store)
        report = s.run(_unbatched(s, graph) if strip else graph)
    return tuple(np.asarray(x) for x in extract(store)), report


@pytest.mark.parametrize("workers", [1, 2])
@pytest.mark.parametrize("name", sorted(FACTORIZATIONS))
def test_factors_bit_identical_to_one_launch_per_task(name, workers):
    mat, builder, extract = FACTORIZATIONS[name](workers)
    batched, report = _factor(workers, mat, builder, extract)
    alone, alone_report = _factor(workers, mat, builder, extract, strip=True)
    for x, y in zip(batched, alone):
        assert (x == y).all()
    assert report.stats["batched_tasks"] > 0
    assert alone_report.stats["batched_tasks"] == 0


def test_cholesky_launches_fewer_programs_than_tasks():
    mat, builder, extract = _cholesky()
    _, report = _factor(2, mat, builder, extract, trace=True)
    n_tasks = len(report.results)
    stats = report.stats
    assert stats["launches"] < n_tasks
    assert 0 < stats["batched_tasks"] < n_tasks
    # the trace shows every batched launch and every task of it
    counters = report.trace.counters
    assert counters["batched_tasks"] == stats["batched_tasks"]
    assert 0 < counters["batch_launches"] <= stats["launches"]
    assert counters["tasks"] == n_tasks
    assert report.trace.reconcile(stats) == {}


def test_a_batched_launch_is_one_span_in_the_trace():
    mat, builder, extract = _cholesky()
    _, report = _factor(1, mat, builder, extract, trace=True)
    trace = report.trace
    spans = [e for e in trace.events
             if e.kind not in ("idle", "steal", "switch", "barrier")]
    n_tasks = len(report.results)
    # one span per launch (task bodies run alone, batched programs)
    assert len(spans) <= report.stats["launches"]
    assert len(spans) < n_tasks


def test_cost_model_graphs_never_batch():
    graph = build_cholesky_graph(NB, B)       # no store: body-less tasks
    with repro.Session(2) as s:
        report = s.run(graph)
    assert report.stats["batched_tasks"] == 0
    assert report.stats["launches"] == 0


def _toy_prefill(prompt):
    h = int(np.asarray(prompt).sum()) % 97
    return {"h": h}, [float(h == i) for i in range(5)]


def _toy_decode(cache, tok):
    h = (cache["h"] * 31 + int(tok) + 7) % 97
    return {"h": h}, [float(h % 5 == i) for i in range(5)]


def test_engine_lane_step_graphs_never_batch():
    reqs = constant_prompt_requests([0.0] * 4, [5, 3, 6, 4], np.asarray((1, 2, 3)))
    with repro.Session(2) as s:
        reports = []
        run = s.run

        def spy(*a, **kw):
            reports.append(run(*a, **kw))
            return reports[-1]

        s.run = spy
        ContinuousBatchingEngine(
            s, _toy_decode, _toy_prefill,
            sample_fn=lambda logits: int(np.argmax(logits)),
            max_batch=3, step_time=0.0).run(reqs)
    assert reports
    assert all(r.stats["batched_tasks"] == 0 for r in reports)
    assert all(r.stats["launches"] > 0 for r in reports)


def test_recording_made_while_batching_replays_bit_identically():
    mat, builder, extract = _cholesky()
    cache = GraphCache()
    stores = [to_tiles(mat, B) for _ in range(3)]
    with repro.Session(2, scheduler="replay", cache=cache) as s:
        first = s.run(builder(stores[0]))
        assert first.recording is not None
        assert first.stats["batched_tasks"] > 0
        replayed = [s.run(builder(st)) for st in stores[1:]]
    assert all(r.plan.mode == "replay" for r in replayed)
    want = np.asarray(cholesky_extract(stores[0]))
    for st in stores[1:]:
        assert (np.asarray(cholesky_extract(st)) == want).all()


def test_a_factorization_builds_at_most_seven_programs_per_kernel_shape():
    # a tile shape no other test uses, so every program here is new
    nb, b = 9, 5
    mat = random_spd(nb * b, seed=5, dtype=jnp.float32)
    compiled = []

    def listen(event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            compiled.append(kw.get("fun_name", ""))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        with repro.Session(2) as s:
            report = s.run(build_cholesky_graph(nb, b, store=to_tiles(mat, b)))
            first, compiled[:] = list(compiled), []
            s.run(build_cholesky_graph(nb, b, store=to_tiles(mat, b)))
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert report.stats["batched_tasks"] > 0
    per_kernel = {}
    for name in first:
        if "batched_" in name:
            kernel = name.split("batched_", 1)[1].rsplit("_", 1)[0]
            per_kernel[kernel] = per_kernel.get(kernel, 0) + 1
    assert per_kernel and all(n <= 7 for n in per_kernel.values())
    # every count was built the first time: the next factorization builds none
    assert not [name for name in compiled if "batched_" in name]


def test_runs_are_cut_into_powers_of_two():
    assert batch_counts(37) == [32, 4, 1]
    assert batch_counts(BATCH_MAX) == [BATCH_MAX]
    assert batch_counts(127) == [64, 32, 16, 8, 4, 2, 1]
    assert sum(batch_counts(1000)) == 1000


@pytest.mark.parametrize("tile,limit", [(8, BATCH_MAX), (192, BATCH_MAX), (384, 16),
                                        (1536, 1)])
def test_operand_bytes_bound_a_launch(tile, limit):
    x = jax.ShapeDtypeStruct((tile, tile), jnp.float32)
    ops = [np.zeros(x.shape, x.dtype)] * 3
    assert batch_limit(ops) == limit
