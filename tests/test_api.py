"""API v2 tests: futures-based construction, sessions/plans/reports,
old-vs-new parity (bit-identical recordings through the shims), the policy
registry, `ctx.wait_any` multi-wait and bounded channels.
"""

import json
import threading

import numpy as np
import pytest

import repro
from repro.core import (
    Channel,
    ChannelFull,
    PolicyError,
    TaskEvent,
    TaskGraph,
    run_graph,
)
from repro.replay import GraphCache, replay_graph

WORKERS = 3


# ---------------------------------------------------------------------------
# futures-based construction
# ---------------------------------------------------------------------------

def test_handles_infer_deps_and_flow_values():
    g = repro.Graph("flow")
    a = g.add(lambda: 3, name="a")
    b = g.add(lambda: 4, name="b")
    # nested containers: handles found in tuples and dicts
    c = g.add(lambda pair, d: pair[0] * pair[1] + d["b"], (a, b), {"b": b},
              name="c")
    assert g.tasks[c.tid].deps == (a.tid, b.tid)
    with repro.Session(2) as s:
        report = s.run(g)
    assert report[c] == 16
    assert c.result(report) == 16


def test_explicit_deps_compose_with_inferred():
    g = repro.Graph("mixed")
    a = g.add(lambda: 1, name="a")
    side = g.add(lambda: None, name="side")
    b = g.add(lambda x: x + 1, a, deps=[side], name="b")
    # explicit first, inferred appended, deduplicated
    assert g.tasks[b.tid].deps == (side.tid, a.tid)
    c = g.add(lambda x: x, a, deps=[a], name="c")
    assert g.tasks[c.tid].deps == (a.tid,)


def test_ctx_convention_and_generator_bodies():
    g = repro.Graph("ctx")
    ch = Channel("api.ch")
    a = g.add(lambda: 5, name="a")

    def consumer(ctx, base):
        v = yield ctx.recv(ch)
        return base + v

    cons = g.add(consumer, a, name="cons")
    g.add(lambda ctx: ch.send(10), name="prod")
    with repro.Session(2) as s:
        report = s.run(g)
    assert report[cons] == 15


def test_handle_in_set_rejected_at_build_time():
    g = repro.Graph("sets")
    a = g.add(lambda: 1)
    with pytest.raises(TypeError, match="inside a set"):
        g.add(lambda xs: xs, {a})


def test_foreign_handle_rejected():
    g1, g2 = repro.Graph("g1"), repro.Graph("g2")
    h = g1.add(lambda: 1)
    with pytest.raises(ValueError, match="belongs to graph"):
        g2.add(lambda x: x, h)


def test_graph_is_a_taskgraph_everywhere():
    g = repro.Graph("compat")
    a = g.add(lambda: 1, name="a")
    g.add(lambda x: x, a, name="b")
    assert isinstance(g, TaskGraph)
    res = run_graph(g, 2)                    # v1 entry point accepts it
    assert res[a.tid] == 1


def test_dataflow_deps_match_explicit_declaration_hypothesis():
    """Property: a random DAG declared via handle arguments has exactly the
    same dependency structure (and digest) as the explicitly-wired twin."""
    hypothesis = pytest.importorskip("hypothesis")
    st = hypothesis.strategies

    @hypothesis.given(st.data())
    @hypothesis.settings(max_examples=40, deadline=None)
    def prop(data):
        n = data.draw(st.integers(min_value=1, max_value=12))
        dep_sets = []
        for tid in range(n):
            pool = list(range(tid))
            # the first task has no candidates: Hypothesis refuses a
            # bounded list drawn from an empty strategy
            deps = (data.draw(st.lists(st.sampled_from(pool), unique=True,
                                       max_size=4)) if pool else [])
            dep_sets.append(deps)
        implicit, explicit = repro.Graph("dag"), repro.Graph("dag")
        ih, eh = [], []

        def fn(*xs):
            return sum(xs) + 1

        for tid, deps in enumerate(dep_sets):
            ih.append(implicit.add(fn, *[ih[d] for d in deps],
                                   name=f"t{tid}"))
            eh.append(explicit.add(
                lambda ctx, _d=tuple(deps): sum(
                    ctx.result(t.tid) for t in [eh[d] for d in _d]) + 1,
                deps=[eh[d] for d in deps], name=f"t{tid}"))
        for tid in range(n):
            assert implicit.tasks[tid].deps == explicit.tasks[tid].deps
        from repro.replay import graph_key
        assert graph_key(implicit) == graph_key(explicit)

    prop()


# ---------------------------------------------------------------------------
# sessions, plans, reports
# ---------------------------------------------------------------------------

def _arith_graph(n=6):
    g = repro.Graph("arith")
    root = g.add(lambda: 1, name="root")
    mids = [g.add(lambda x, i=i: x + i, root, name=f"m{i}") for i in range(n)]
    total = g.add(lambda xs: sum(xs), mids, name="total")
    return g, total


def test_session_plan_modes_and_report():
    g, total = _arith_graph()
    cache = GraphCache()
    with repro.Session(2, cache=cache) as s:
        p1 = s.plan(g)
        assert p1.mode == "record" and "miss" in p1.reason
        r1 = s.run(g, plan=p1)
        assert r1.recording is not None and r1[total] == 21
        assert r1.wall_s > 0 and "steals" in r1.stats
        p2 = s.plan(g)
        assert p2.mode == "replay" and p2.recording is not None
        r2 = s.run(g, plan=p2)
        assert r2[total] == 21 and r2.stats.get("skips") == 0
    # no cache: warm dynamic, record only on request
    with repro.Session(2) as s:
        assert s.plan(g).mode == "warm"
        assert s.plan(g, record=True).mode == "record"
        rep = s.run(g)
        assert rep.recording is None and rep[total] == 21


def test_session_replay_scheduler_remaps_across_worker_counts():
    g, total = _arith_graph()
    cache = GraphCache()
    with repro.Session(2, cache=cache) as s:
        s.run(g, record=True)
    with repro.Session(3, scheduler="replay", cache=cache) as s:
        plan = s.plan(g)
        assert plan.mode == "replay" and plan.remapped_from == 2
        report = s.run(g, plan=plan)
        assert report[total] == 21
        # the remapped recording was adopted: next plan is a pure hit
        assert s.plan(g).remapped_from is None


def test_session_plan_reuse_across_same_shaped_graphs():
    cache = GraphCache()
    with repro.Session(2, scheduler="replay", cache=cache) as s:
        g0, t0 = _arith_graph()
        s.run(g0)                                    # records
        plan = s.plan(_arith_graph()[0])
        assert plan.mode == "replay"
        for _ in range(3):
            g, total = _arith_graph()
            assert s.run(g, plan=plan)[total] == 21

    g_other = repro.Graph("other")
    g_other.add(lambda: 0)
    with repro.Session(2, scheduler="replay", cache=cache) as s:
        g, _t = _arith_graph()
        plan = s.plan(g)
        with pytest.raises(repro.PlanError, match="hashes differently"):
            s.run(g_other, plan=plan)


def test_session_pool_scheduler_reports_pool_modes():
    with repro.Session(2, scheduler="pool",
                       pool_kwargs={"warmup_runs": 1}) as s:
        g, total = _arith_graph()
        modes = []
        for _ in range(4):
            g, total = _arith_graph()
            rep = s.run(g)
            assert rep[total] == 21
            modes.append(rep.stats["pool_mode"])
        assert modes == ["warmup", "record", "replay", "replay"]
        assert rep.recording is not None


def test_session_closed_is_terminal_and_releases_lease():
    s = repro.Session(2)
    g, total = _arith_graph()
    assert s.run(g)[total] == 21
    s.close()
    with pytest.raises(repro.PlanError, match="closed"):
        s.run(g)
    from repro.exec import REGISTRY
    assert REGISTRY.refcounts().get(2, 0) == 0


# ---------------------------------------------------------------------------
# policy registry
# ---------------------------------------------------------------------------

def test_policy_typo_fails_at_the_api_boundary_with_names():
    g, _total = _arith_graph()
    with pytest.raises(PolicyError, match="history, hybrid, random"):
        repro.Session(2, policy="hybird")
    with pytest.raises(PolicyError, match="valid policies"):
        run_graph(g, 2, policy="historyy")
    from repro.replay import ReplayPool
    with ReplayPool() as pool:
        with pytest.raises(PolicyError, match="valid policies"):
            pool.serve(g, 2, policy="nope")


def test_register_policy_extends_every_entry_point():
    from repro.core.policies import POLICIES, RandomPolicy, register_policy

    @register_policy("test-rr")
    class RoundRobin(RandomPolicy):
        name = "test-rr"

        def select(self):
            return (self.worker_id + 1) % self.n_workers

    try:
        assert "test-rr" in repro.available_policies()
        g, total = _arith_graph()
        with repro.Session(2, policy="test-rr") as s:
            assert s.run(g)[total] == 21
    finally:
        POLICIES.pop("test-rr", None)


# ---------------------------------------------------------------------------
# old-vs-new parity (the shim contract)
# ---------------------------------------------------------------------------

def _cholesky_setup(nb=4, b=16, seed=0):
    from repro.linalg import (build_cholesky_graph, cholesky_extract,
                              random_spd, to_tiles)

    a = random_spd(nb * b, seed=seed)
    st = to_tiles(a, b)
    return build_cholesky_graph(nb, b, store=st), st, cholesky_extract


def test_parity_cholesky_recording_bit_identical_at_one_worker():
    """At 1 worker a dynamic schedule is deterministic: the recording made
    through the v1 shim and the one on the v2 RunReport must be
    byte-identical JSON, and both factorizations bit-identical."""
    g_old, st_old, extract = _cholesky_setup()
    run_graph(g_old, 1, record=True)
    with pytest.warns(DeprecationWarning):
        rec_old = run_graph.last_recording
    g_new, st_new, _ = _cholesky_setup()
    with repro.Session(1) as s:
        report = s.run(g_new, record=True)
    assert json.dumps(rec_old.to_dict(), sort_keys=True) == \
        json.dumps(report.recording.to_dict(), sort_keys=True)
    assert (np.asarray(extract(st_old)) == np.asarray(extract(st_new))).all()


@pytest.mark.parametrize("builder", ["cholesky", "lu", "qr"])
def test_parity_factorizations_old_vs_new_api(builder):
    """Dynamic old-API run vs new-API session run: bit-identical factors;
    one shim-made recording replayed through BOTH APIs: bit-identical
    factors and equal deviation stats."""
    from repro.linalg import to_tiles
    if builder == "cholesky":
        from repro.linalg import build_cholesky_graph as build
        from repro.linalg import cholesky_extract as extract
        from repro.linalg import random_spd as gen
        kw = {}
    elif builder == "lu":
        from repro.linalg import build_lu_graph as build
        from repro.linalg import lu_extract as extract
        from repro.linalg import random_diagdom as gen
        kw = {"panel_threads": 2}
    else:
        from repro.linalg import build_qr_graph as build
        from repro.linalg import qr_extract_r as extract
        from repro.linalg import random_diagdom as gen
        kw = {"panel_threads": 2}
    nb, b = 4, 16
    a = gen(nb * b, seed=1)

    def factor(run):
        st = to_tiles(a, b)
        run(build(nb, b, store=st, **kw))
        out = extract(st)
        return np.asarray(out if not isinstance(out, tuple) else out[0])

    l_old = factor(lambda g: run_graph(g, WORKERS, record=True))
    with pytest.warns(DeprecationWarning):
        rec = run_graph.last_recording
    with repro.Session(WORKERS) as s:
        l_new = factor(lambda g: s.run(g))
    assert (l_old == l_new).all()

    # the same recording drives both replay paths bit-identically
    l_rep_old = factor(lambda g: run_graph(g, WORKERS, replay=rec))
    cache = GraphCache()
    cache.store(rec)
    with repro.Session(WORKERS, scheduler="replay", cache=cache) as s:
        l_rep_new = factor(lambda g: s.run(g))
    assert (l_rep_old == l_old).all() and (l_rep_new == l_old).all()


def test_parity_serving_decode_old_vs_new():
    """The pooled decode loop through the v1 shim vs through a
    Session(scheduler='pool'): identical token streams, and the live
    recording reported by the session encodes identically to the one the
    shim's pool produced for the same deterministic (1-worker) loop."""
    import jax.numpy as jnp

    from repro.models import DecodeShard, DecodeState, build_decode_graph
    from repro.replay import ReplayPool

    vocab = 7

    def toy_decode(params, cache, tok):
        h = cache["h"] * 31 + tok[:, 0] + 7
        logits = jnp.stack(
            [jnp.sin(h[:, None] * (i + 1)).astype(jnp.float32)
             for i in range(vocab)], axis=-1)
        return {"h": h}, logits

    def fresh_state(n_shards=3):
        shards = [
            DecodeShard(cache={"h": jnp.full((1,), s + 1, jnp.int32)},
                        tok=jnp.full((1, 1), s, jnp.int32))
            for s in range(n_shards)
        ]
        return DecodeState(params=None, shards=shards)

    def loop(run):
        state = fresh_state()
        for _ in range(5):
            run(build_decode_graph(state, toy_decode))
        return np.asarray(state.tokens())

    with ReplayPool(warmup_runs=1) as pool:
        tok_old = loop(lambda g: run_graph(g, 1, pool=pool))
    with pytest.warns(DeprecationWarning):
        rec_old = run_graph.last_recording
    reports = []
    with repro.Session(1, scheduler="pool",
                       pool_kwargs={"warmup_runs": 1}) as s:
        tok_new = loop(lambda g: reports.append(s.run(g)))
    assert (tok_old == tok_new).all()
    assert [r.stats["pool_mode"] for r in reports] == \
        ["warmup", "record", "replay", "replay", "replay"]
    # 1-worker decode recordings are deterministic: bit-identical encodings
    rec_new = reports[-1].recording
    assert json.dumps(rec_old.to_dict(), sort_keys=True) == \
        json.dumps(rec_new.to_dict(), sort_keys=True)


def test_run_graph_replay_kwarg_matches_replay_graph():
    g, total = _arith_graph()
    run_graph(g, 2, record=True)
    with pytest.warns(DeprecationWarning):
        rec = run_graph.last_recording
    g2, total2 = _arith_graph()
    res_shim = run_graph(g2, 2, replay=rec)
    g3, total3 = _arith_graph()
    res_lib = replay_graph(g3, rec)
    assert res_shim[total2.tid] == res_lib[total3.tid] == 21


def test_last_recording_alias_is_thread_local():
    """The v1 global leaked recordings across threads; the shim alias must
    not: each thread sees its own last recording."""
    seen = {}

    def worker(tag, n):
        g = repro.Graph(f"tl-{tag}")
        g.add(lambda: tag)
        for _ in range(n):
            run_graph(g, 1, record=True)
        with pytest.warns(DeprecationWarning):
            seen[tag] = run_graph.last_recording.graph_name

    threads = [threading.Thread(target=worker, args=(f"t{i}", 3))
               for i in range(3)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert seen == {f"t{i}": f"tl-t{i}" for i in range(3)}


# ---------------------------------------------------------------------------
# wait_any multi-wait
# ---------------------------------------------------------------------------

def test_wait_any_frame_first_ready_wins():
    g = repro.Graph("select")
    fast, slow = Channel("fast"), Channel("slow")

    def selector(ctx):
        idx, v = yield ctx.wait_any(slow, fast)
        return idx, v

    sel = g.add(selector, name="sel")
    g.add(lambda ctx: fast.send("f"), name="pf")
    with repro.Session(2) as s:
        idx, v = s.run(g)[sel]
    assert (idx, v) == (1, "f")
    assert len(slow) == 0                     # the loser was not consumed


def test_wait_any_loser_requeue_survives_full_bounded_channel():
    """A losing wait_any racer hands its consumed item back even when the
    bounded channel refilled meanwhile: the requeue bypasses the capacity
    check instead of raising ChannelFull in the sender's callback."""
    from repro.core.taskgraph import RecvRequest, WaitAnyRequest

    ch_a, ch_b = Channel("wa"), Channel("wb", capacity=1)
    req = WaitAnyRequest([RecvRequest(ch_a), RecvRequest(ch_b)])
    fired = []
    status, _ = req.park(fired.append)
    assert status == "parked"
    stale = ch_b._waiters[0]          # child 1's waiter, as a sender sees it
    ch_a.send("winner")               # child 0 claims; cancels child 1
    assert fired == [(0, "winner")]
    # simulate the race: a sender popped child 1 BEFORE the cancel landed,
    # and by now the bounded channel is full again
    ch_b.send("fill")
    stale("racing-item")              # must not raise, must not drop
    assert fired == [(0, "winner")]   # the loser never double-delivers
    assert [ch_b.recv_nowait(), ch_b.recv_nowait()] == \
        ["fill", "racing-item"]


def test_run_graph_pool_shim_refreshes_pool_last_recording():
    from repro.replay import ReplayPool

    with ReplayPool(warmup_runs=0) as pool:
        run_graph(_arith_graph()[0], 2, pool=pool)     # records
        run_graph(_arith_graph()[0], 2, pool=pool)     # replays
        assert pool.last_recording is not None
        assert pool.last_recording.n_workers == 2


def test_wait_any_event_and_plain_body():
    g = repro.Graph("select-plain")
    ch, ev = Channel("ch"), TaskEvent("ev")

    def plain(ctx):
        return ctx.wait_any(ch, ev)

    sel = g.add(plain, name="sel")
    g.add(lambda ctx: ev.set(), name="setter")
    with repro.Session(2) as s:
        idx, v = s.run(g)[sel]
    assert (idx, v) == (1, None)


def test_wait_any_replay_pins_recorded_choice():
    """Record a select whose winner is data-driven, then replay: the same
    branch must be taken (the recorded deterministic choice), even though
    at replay time both sources are ready."""
    ref = None
    for attempt in ("record", "replay"):
        g = repro.Graph("select-replay")
        a, b = Channel("a"), Channel("b")

        def selector(ctx):
            taken = []
            for _ in range(2):
                idx, v = yield ctx.wait_any(a, b)
                taken.append((idx, v))
            return taken

        sel = g.add(selector, name="sel")

        def producer(ctx):
            a.send("va")
            b.send("vb")

        g.add(producer, name="prod")
        if attempt == "record":
            res = run_graph(g, 2, record=True)
            with pytest.warns(DeprecationWarning):
                rec = run_graph.last_recording
            ref = res[sel.tid]
            assert sorted(ref) == [(0, "va"), (1, "vb")]
            assert rec.wait_choices          # the choices were instrumented
            # recordings round-trip the choices through JSON
            from repro.replay import Recording
            assert Recording.from_json(rec.to_json()).wait_choices == \
                rec.wait_choices
        else:
            res = replay_graph(g, rec)
            assert res[sel.tid] == ref


# ---------------------------------------------------------------------------
# bounded channels
# ---------------------------------------------------------------------------

def test_bounded_channel_raw_send_raises_when_full():
    ch = Channel("bounded", capacity=2)
    ch.send(1)
    ch.send(2)
    with pytest.raises(ChannelFull, match="capacity 2"):
        ch.send(3)
    assert ch.recv_nowait() == 1
    ch.send(3)                               # slot freed
    assert len(ch) == 2
    with pytest.raises(ValueError, match="capacity"):
        Channel("bad", capacity=0)


def test_bounded_channel_suspends_frame_senders():
    """A producer frame on a capacity-1 channel parks between sends; the
    consumer's receives free slots and resume it.  FIFO order holds."""
    g = repro.Graph("backpressure")
    ch = Channel("bp", capacity=1)
    n = 6

    def producer(ctx):
        for i in range(n):
            yield ctx.send(ch, i)
        return "done"

    def consumer(ctx):
        out = []
        for _ in range(n):
            v = yield ctx.recv(ch)
            out.append(v)
        return out

    prod = g.add(producer, name="prod")
    cons = g.add(consumer, name="cons")
    with repro.Session(2) as s:
        report = s.run(g)
    assert report[cons] == list(range(n)) and report[prod] == "done"
    assert report.stats["frame_suspends"] > 0


def test_bounded_channel_blocks_plain_senders_work_conservingly():
    """A plain-body producer on a full channel blocks work-conservingly
    while a plain consumer on another worker drains it.  At ONE worker the
    same pair is a genuine plain-body limitation (the consumer nests on
    the producer's stack and neither can finish) — the suspension-deadlock
    detector must raise instead of hanging; generator frames are the
    supported 1-worker shape."""
    from repro.core import DeadlockError

    def build(frame_consumer):
        g = repro.Graph("bp-plain")
        ch = Channel("bp2", capacity=1)
        n = 4

        def producer(ctx):
            for i in range(n):
                ctx.send(ch, i)
            return "done"

        def frame_cons(ctx):
            out = []
            for _ in range(n):
                out.append((yield ctx.recv(ch)))
            return out

        def plain_cons(ctx):
            return [ctx.recv(ch) for _ in range(n)]

        prod = g.add(producer, name="prod")
        cons = g.add(frame_cons if frame_consumer else plain_cons,
                     name="cons")
        return g, prod, cons, n

    for workers in (1, 2):
        g, prod, cons, n = build(frame_consumer=True)
        res = run_graph(g, workers, timeout=30.0)
        assert res[cons.tid] == list(range(n)) and res[prod.tid] == "done"
    # plain-plain at one worker: the consumer nests on the producer's
    # stack and neither can finish — detected, not hung
    g, prod, cons, n = build(frame_consumer=False)
    with pytest.raises(DeadlockError):
        run_graph(g, 1, timeout=30.0)


def test_bounded_channel_record_replay_parity():
    def build():
        g = repro.Graph("bp-rr")
        ch = Channel("bp3", capacity=2)

        def producer(ctx):
            for i in range(5):
                yield ctx.send(ch, i)

        def consumer(ctx):
            out = []
            for _ in range(5):
                out.append((yield ctx.recv(ch)))
            return out

        g.add(producer, name="prod")
        cons = g.add(consumer, name="cons")
        return g, cons

    g, cons = build()
    res = run_graph(g, 2, record=True)
    with pytest.warns(DeprecationWarning):
        rec = run_graph.last_recording
    g2, cons2 = build()
    assert replay_graph(g2, rec)[cons2.tid] == res[cons.tid] == list(range(5))


def test_bounded_channel_sender_deadlock_detected():
    """A frame sender filling a bounded channel nobody drains must raise a
    suspension deadlock, not hang."""
    from repro.core import DeadlockError

    g = repro.Graph("bp-dead")
    ch = Channel("bp4", capacity=1)

    def producer(ctx):
        yield ctx.send(ch, 1)
        yield ctx.send(ch, 2)

    g.add(producer, name="prod")
    with pytest.raises(DeadlockError, match="suspension deadlock"):
        run_graph(g, 2, timeout=30.0)
