"""Tests for the continuous-batching serving layer (repro.serving).

Uses a pure-python toy model (integer hash caches, list logits) so the
engine, admission queue, and pool integration run fast and
deterministically with no jax in the loop; the full-LM path is exercised
by benchmarks/bench_serving.py and examples/serve_lm.py.
"""

import threading

import numpy as np
import pytest

import repro
from repro.serving import (
    AdmissionFull,
    ContinuousBatchingEngine,
    PoissonWorkload,
)
from repro.serving.workload import constant_prompt_requests

VOCAB = 13
PRIME = 10_007


def toy_prefill(prompt):
    h = (int(np.asarray(prompt).sum()) * 31 + 7) % PRIME
    return {"h": h}, _logits(h)


def toy_decode(cache, tok):
    h = (cache["h"] * 31 + int(tok) + 7) % PRIME
    return {"h": h}, _logits(h)


def _logits(h):
    row = [0.0] * VOCAB
    row[h % VOCAB] = 1.0
    return row


def toy_sample(logits):
    return int(np.argmax(np.asarray(logits)))


def _engine(session, **kw):
    kw.setdefault("max_batch", 3)
    kw.setdefault("step_time", 0.01)
    return ContinuousBatchingEngine(
        session, toy_decode, toy_prefill, sample_fn=toy_sample, **kw)


def _requests(budgets, arrivals=None, prompt=(1, 2, 3), eos=None):
    arrivals = [0.0] * len(budgets) if arrivals is None else arrivals
    return constant_prompt_requests(
        arrivals, budgets, np.asarray(prompt), eos_token=eos)


def _per_request_reference(requests):
    """Decode each request alone, serially, straight through the toy model
    (no engine, no runtime) — the ground-truth token streams."""
    out = {}
    for req in requests:
        cache, logits = toy_prefill(req.prompt)
        tok = toy_sample(logits)
        toks = [tok]
        while len(toks) < req.max_new_tokens and tok != req.eos_token:
            cache, logits = toy_decode(cache, tok)
            tok = toy_sample(logits)
            toks.append(tok)
        out[req.rid] = toks
    return out


# ---------------------------------------------------------------------------
# workload generator
def test_poisson_workload_deterministic_under_seed():
    a = PoissonWorkload(50.0, 20, seed=7, prompt_len=(4, 12),
                        max_new_tokens=(2, 9))
    b = PoissonWorkload(50.0, 20, seed=7, prompt_len=(4, 12),
                        max_new_tokens=(2, 9))
    assert np.array_equal(a.arrivals, b.arrivals)
    ra, rb = a.requests(), b.requests()
    assert [r.max_new_tokens for r in ra] == [r.max_new_tokens for r in rb]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(ra, rb))
    assert (np.diff(a.arrivals) >= 0).all()
    c = PoissonWorkload(50.0, 20, seed=8, prompt_len=(4, 12),
                        max_new_tokens=(2, 9))
    assert not np.array_equal(a.arrivals, c.arrivals)


def test_poisson_workload_validation():
    with pytest.raises(ValueError, match="rate"):
        PoissonWorkload(0.0, 4)
    with pytest.raises(ValueError, match="request"):
        PoissonWorkload(1.0, 0)
    with pytest.raises(ValueError, match="span"):
        PoissonWorkload(1.0, 4, max_new_tokens=(5, 2))


def test_workload_budget_and_eos_stamp():
    w = PoissonWorkload(10.0, 6, seed=0, max_new_tokens=(3, 3), eos_token=2)
    reqs = w.requests()
    assert w.total_budget() == 18
    assert all(r.max_new_tokens == 3 and r.eos_token == 2 for r in reqs)


# ---------------------------------------------------------------------------
# engine basics: composition, early exit, determinism
def test_streams_bit_identical_to_per_request_dynamic_baseline():
    """Continuous batching (pooled, batch 3) and the per-request dynamic
    baseline (batch 1, FCFS) produce bit-identical per-request streams."""
    reqs = _requests([6, 4, 8, 3, 5, 7])
    with repro.Session(2, scheduler="pool") as s:
        batched = _engine(s).run(_requests([6, 4, 8, 3, 5, 7]))
    with repro.Session(2) as s:
        baseline = _engine(s, max_batch=1).run(reqs)
    assert batched.tokens_by_rid() == baseline.tokens_by_rid()
    assert batched.tokens_by_rid() == _per_request_reference(reqs)
    assert baseline.warm_hit_rate == 0.0        # dynamic serves, no pool


def test_early_exit_releases_batch_slots():
    """A finished request's slot is handed to the next queued request on
    the very next step, and occupancy never exceeds max_batch."""
    reqs = _requests([2, 5, 4])
    with repro.Session(2, scheduler="pool") as s:
        eng = _engine(s, max_batch=2)
        report = eng.run(reqs)
    recs = report.records
    # budget 2 = prefill token + one decode step, then the slot frees
    assert len(recs[0].tokens) == 2
    assert recs[2].admitted_s >= recs[0].done_s
    # both slots stayed busy the whole time: every step ran 2 lanes
    assert report.shape_counts == {2: 4}
    assert report.occupancy == 1.0
    assert [len(recs[r].tokens) for r in (0, 1, 2)] == [2, 5, 4]


def test_eos_stops_a_request_early():
    """toy_decode is a deterministic hash walk; find a token the walk hits
    and declare it EOS — the request must stop there, under budget."""
    ref = _per_request_reference(_requests([10]))[0]
    # first token value not seen earlier in the walk — a sound EOS marker
    idx = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[idx]
    (req,) = _requests([10], eos=eos)
    with repro.Session(1, scheduler="pool") as s:
        report = _engine(s, max_batch=1).run([req])
    toks = report.records[0].tokens
    assert toks == ref[: idx + 1]
    assert toks[-1] == eos and len(toks) < 10


def test_virtual_clock_composition_is_deterministic():
    """Same seeded workload + virtual clock => identical step compositions
    and latency numbers, run to run."""
    w = PoissonWorkload(200.0, 10, seed=3, prompt_len=4,
                        max_new_tokens=(2, 6), vocab_size=50)
    outs = []
    for _ in range(2):
        with repro.Session(2, scheduler="pool") as s:
            outs.append(_engine(s).run(w.requests()))
    assert outs[0].shape_counts == outs[1].shape_counts
    assert outs[0].tokens_by_rid() == outs[1].tokens_by_rid()
    assert outs[0].summary() == outs[1].summary()


# ---------------------------------------------------------------------------
# admission backpressure
def test_admission_backpressure_under_full_queue():
    with repro.Session(1) as s:
        eng = _engine(s, max_batch=1, admission_capacity=2)
        reqs = _requests([3, 3, 3, 3, 3])
        eng.submit(reqs[0])
        eng.submit(reqs[1])
        with pytest.raises(AdmissionFull, match="admission queue full"):
            eng.submit(reqs[2])
        assert not eng.try_submit(reqs[2])
        assert eng.queue_depth() == 2
        # a decode step admits one into the freed lane -> a slot opens
        assert eng.step()
        eng.submit(reqs[2])
        with pytest.raises(AdmissionFull):
            eng.submit(reqs[3], block=True, timeout=0.01)
        # a blocked submitter gets through once steps drain the queue
        t = threading.Thread(target=eng.submit, args=(reqs[3],),
                             kwargs={"block": True, "timeout": 30.0})
        t.start()
        for _ in range(40):
            if not eng.step() and not eng.queue_depth():
                break
        t.join(timeout=30.0)
        assert not t.is_alive()
        while eng.in_flight() or eng.queue_depth():
            eng.step()
        report = eng.report()
    assert sorted(report.records) == [0, 1, 2, 3]
    assert all(len(r.tokens) == 3 for r in report.records.values())


def test_duplicate_rid_rejected():
    with repro.Session(1) as s:
        eng = _engine(s)
        (req,) = _requests([2])
        eng.submit(req)
        with pytest.raises(ValueError, match="duplicate"):
            eng.submit(req)
        while eng.in_flight() or eng.queue_depth():
            eng.step()


# ---------------------------------------------------------------------------
# warm replay under churn
def test_shape_churn_still_replays_warm():
    """Ragged budgets churn the lane count step to step; every distinct
    shape records once and the rest of the steps replay warm."""
    budgets = [7, 5, 9, 4, 6, 8, 3, 5]
    with repro.Session(2, scheduler="pool",
                       pool_kwargs={"warmup_runs": 0}) as s:
        eng = _engine(s, max_batch=3)
        report = eng.run(_requests(budgets))
        by_key = s.pool.describe()
    shapes = len(report.shape_counts)
    assert shapes >= 2                      # churn actually happened
    # each shape pays at most its one recording run (plus, rarely, a
    # drift-triggered re-record under a loaded box) — everything else
    # must be a warm replay
    assert report.steps > 2 * shapes
    assert report.warm_steps >= report.steps - 2 * shapes
    assert report.warm_hit_rate > 0.5
    assert sum(e["records"] for e in by_key.values()) == shapes
    assert report.tokens_by_rid() == _per_request_reference(
        _requests(budgets))


def test_remap_absorbs_worker_count_churn():
    """Recordings made by a 2-worker replica serve a 3-worker replica via
    remap_recording: no re-recording, streams bit-identical."""
    from repro.replay import GraphCache

    budgets = [6, 4, 7, 5]
    cache = GraphCache()
    with repro.Session(2, scheduler="pool", cache=cache,
                       pool_kwargs={"warmup_runs": 0}) as s:
        ref = _engine(s, max_batch=2).run(_requests(budgets))
    with repro.Session(3, scheduler="pool", cache=cache,
                       pool_kwargs={"warmup_runs": 0}) as s:
        eng = _engine(s, max_batch=2)
        out = eng.run(_requests(budgets))
        by_key = s.pool.describe()
    assert out.tokens_by_rid() == ref.tokens_by_rid()
    assert sum(e["records"] for e in by_key.values()) == 0
    assert sum(e["remaps"] for e in by_key.values()) == len(
        out.shape_counts)


def test_prime_builds_graphs_off_the_hot_path():
    with repro.Session(1, scheduler="pool") as s:
        eng = _engine(s, max_batch=3)
        eng.prime()
        assert sorted(eng._graphs) == [1, 2, 3]
        graphs_before = {k: g for k, (g, _) in eng._graphs.items()}
        eng.run(_requests([4, 3, 2]))
        # the loop reused the primed graphs, never rebuilt them
        assert all(eng._graphs[k][0] is g for k, g in graphs_before.items())


# ---------------------------------------------------------------------------
# session key pass-through
def test_session_key_passthrough_skips_hash_not_safety():
    from repro.replay import graph_key

    g = repro.Graph("keyed")
    a = g.add(lambda: 3, name="a")
    g.add(lambda x: x + 1, a, name="b")
    key = graph_key(g)
    with repro.Session(1, scheduler="pool",
                       pool_kwargs={"warmup_runs": 0}) as s:
        r1 = s.run(g, key=key)
        r2 = s.run(g, key=key)
        assert r1.results[1] == r2.results[1] == 4
        assert r2.stats.get("pool_mode") == "replay"
        wrong = repro.Graph("wrong")
        wrong.add(lambda: 0, name="only")
        with pytest.raises(Exception):
            s.run(wrong, key=key)
    with repro.Session(1) as s:
        plan = s.plan(g, key=key)
        assert plan.digest == key.digest and plan.key is key


def test_report_refuses_requests_still_in_flight():
    with repro.Session(1) as s:
        eng = _engine(s, max_batch=2)
        eng.submit(_requests([5])[0])
        eng.step()
        with pytest.raises(RuntimeError, match="in flight"):
            eng.report()
        while eng.in_flight() or eng.queue_depth():
            eng.step()
        assert eng.report().completed == 1


# ---------------------------------------------------------------------------
# the engine's host phases in the session's flight recorder
def test_traced_engine_records_its_phases_and_reports_the_window():
    reqs = _requests([3, 5, 2, 4])
    with repro.Session(2, scheduler="pool", trace=True) as s:
        report = _engine(s, max_batch=2).run(reqs)
        spans = repro.obs.phase_spans(s.trace_window().events)
    by = {}
    for p in spans:
        by.setdefault(p.label, []).append(p)
    assert len(by["engine.prefill"]) == len(reqs)
    assert len(by["engine.run_graph"]) == len(by["engine.collect"]) == report.steps
    assert len(by["session.run"]) == report.steps
    assert len(by["engine.step"]) >= report.steps
    # each graph run sits inside a step, after its admission, and encloses
    # the session's run; collection follows it
    for run, sess, collect in zip(by["engine.run_graph"], by["session.run"],
                                  by["engine.collect"]):
        step = next(p for p in by["engine.step"] if p.t0 <= run.t0 <= p.t1)
        assert step.t0 <= run.t0 and collect.t1 <= step.t1
        assert run.t0 <= sess.t0 and sess.t1 <= run.t1 <= collect.t0
    # the report's trace is the whole window: every task of every step
    lanes = sum(k * n for k, n in report.shape_counts.items())
    assert report.trace.counters["tasks"] == 2 * lanes + report.steps
    with repro.Session(2, scheduler="pool") as s:
        assert _engine(s, max_batch=2).run(_requests([3, 5])).trace is None
