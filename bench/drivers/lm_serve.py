"""Driver of serving cells: a dense decoder served by the program's
continuous-batching engine.

Set-up makes the weights from the seed in one jitted call (the
benchmark's own generator, ``bench/reference/dense_lm.py``), in the layout
the program's ``lm.init_params`` gives, and compiles one prefill program
per prompt bucket and one decode program, under the names
``bench_prefill_<L>`` and ``bench_decode``, so that the trace finds them.
It runs each once, then builds a ``repro.Session`` with the program's
default scheduler and a ``ContinuousBatchingEngine`` over them.

The window drives ``engine.step`` on the traffic of the cell.  The
benchmark sees the engine only through the callables it hands it: the
prefill and decode functions tag each request's cache and logits with its
id, and the sampler (the program's greedy sampler) records every emitted
token, so that tokens and their times are known per request without the
engine's own report.  A token's time is when its step returned (the
engine waits for every lane's token before it returns); the first token's
is when its prefill's token was ready.

After the window: the peak memory is read, the program's state is freed,
and the reference runs over a sample of the finished requests (see
:func:`check`).
"""

from __future__ import annotations

import dataclasses
import gc
import math
import shutil
import tempfile
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from bench import stats, trace_reduce
from bench.reference import dense_lm as ref
from bench.traffic_gen import LMTraffic
from bench.work import LMShapes, least_seconds, peaks

#: the event JAX records for each program its backend compiles (not for a
#: program loaded from the persistent cache)
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class Rec:
    """One request as the benchmark saw it; times on ``perf_counter``."""

    rid: int
    due_s: float
    prompt: np.ndarray
    budget: int
    submitted_s: Optional[float] = None
    admitted_s: Optional[float] = None
    first_s: Optional[float] = None
    done_s: Optional[float] = None
    decoded: int = 0
    tokens: List[Any] = dataclasses.field(default_factory=list)
    times: List[float] = dataclasses.field(default_factory=list)

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[1])


@dataclasses.dataclass
class Step:
    """One ``engine.step`` call: its host times, the decode contexts (keys
    each decoded token sees) and the prefill lengths it ran."""

    t0: float
    t1: float = 0.0
    contexts: List[int] = dataclasses.field(default_factory=list)
    prefills: List[int] = dataclasses.field(default_factory=list)
    traced: bool = False


@dataclasses.dataclass
class Window:
    t_open: float
    t_close: float
    requests: List[Rec]
    steps: List[Step]
    trace: Optional[trace_reduce.TraceData] = None
    compiles: int = 0

    @property
    def seconds(self) -> float:
        return self.t_close - self.t_open

    def in_window(self, t: Optional[float]) -> bool:
        return t is not None and self.t_open < t <= self.t_close


class _Tag:
    """A request's cache or logits, carried through the engine with the
    request's id."""

    __slots__ = ("value", "rid", "first")

    def __init__(self, value, rid: int, first: bool = False):
        self.value, self.rid, self.first = value, rid, first


def model_config(cfg: Dict[str, Any]):
    """The program's ``ModelConfig`` for a dense decoder configuration: the
    Hugging Face keys, with ``family`` and ``qk_norm`` from keys of the
    benchmark's configuration file."""
    from repro.models import ModelConfig

    if cfg["family"] != "dense" or cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: this driver serves dense decoders without "
                         "attention biases")
    return ModelConfig(
        name=cfg["name"], family=cfg["family"], n_layers=int(cfg["num_hidden_layers"]),
        d_model=int(cfg["hidden_size"]), n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=int(cfg["head_dim"]),
        d_ff=int(cfg["intermediate_size"]), vocab_size=int(cfg["vocab_size"]),
        qk_norm=bool(cfg["qk_norm"]), rope_theta=float(cfg["rope_theta"]),
        norm_eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"],
        tie_embeddings=bool(cfg["tie_word_embeddings"])).validate()


def _to_program_tree(w: Dict[str, Any], abstract: Dict[str, Any]) -> Dict[str, Any]:
    """The benchmark's leaves in the tree ``lm.init_params`` returns; the
    embedding and head are zero-padded to the program's padded vocabulary."""
    import jax.numpy as jnp

    def pad_rows(x, rows):
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))

    vpad = abstract["embed"]["table"].shape[0]
    tree = {
        "embed": {"table": pad_rows(w["embed"], vpad)},
        "final_norm": w["final_norm"],
        "blocks": {
            "ln1": w["ln1"], "ln2": w["ln2"],
            "attn": {k: w[k] for k in abstract["blocks"]["attn"]},
            "mlp": {k: w[k] for k in ("wg", "wu", "wd")},
        },
    }
    if "unembed" in abstract:
        tree["unembed"] = {"out": pad_rows(w["unembed"].T, vpad).T}
    return tree


class ServingCell:
    """Set-up, windows and checks of one serving cell; one process may run
    several seeds through one instance (the compiled programs are shared)."""

    def __init__(self, cell):
        import jax

        from repro.models import lm

        self.cell = cell
        self.cfg = cell.config
        self.spec = cell.traffic
        self.shapes = LMShapes.from_config(self.cfg)
        self.mcfg = model_config(self.cfg)
        self.buckets = sorted(int(b) for b in self.spec["prompt_buckets"])
        self.max_out = int(self.spec["output_tokens"]["max"])
        self.max_len = max(self.buckets) + self.max_out + 1
        self.max_batch = int(self.spec["max_batch"])
        self.workers = int(self.spec["workers"])
        self.params = None
        self._lm = lm
        self._jax = jax
        self.compile_s = 0.0

    # -- set-up --------------------------------------------------------
    def compile(self) -> None:
        """Compile (or load from the persistent cache) every program a run
        uses: the weights' generator, a prefill per bucket, the decode."""
        jax, lm, mcfg = self._jax, self._lm, self.mcfg
        import jax.numpy as jnp

        t0 = time.perf_counter()
        abstract = lm.abstract_params(mcfg)
        dtype = jnp.dtype(self.cfg["torch_dtype"])

        def make_weights(key):
            return _to_program_tree(ref.draw_all(key, self.cfg, dtype), abstract)

        key0 = ref.seed_key(0)
        self._make = jax.jit(make_weights).lower(key0).compile()
        made = jax.eval_shape(make_weights, key0)
        want = jax.tree.map(lambda a: (a.shape, a.dtype), abstract)
        got = jax.tree.map(lambda a: (a.shape, a.dtype), made)
        if want != got:
            raise ValueError(f"weights do not match lm.abstract_params: {got} != {want}")

        max_len = self.max_len
        self._prefill = {}
        for length in self.buckets:
            def fn(params, tokens):
                return lm.prefill(params, mcfg, {"tokens": tokens}, None, max_len=max_len)
            fn.__name__ = f"bench_prefill_{length}"
            self._prefill[length] = jax.jit(fn).lower(
                made, jax.ShapeDtypeStruct((1, length), jnp.int32)).compile()
        cache_s, _ = jax.eval_shape(
            lambda p, t: lm.prefill(p, mcfg, {"tokens": t}, None, max_len=max_len),
            made, jax.ShapeDtypeStruct((1, self.buckets[0]), jnp.int32))

        def bench_decode(params, cache, tok):
            return lm.decode_step(params, mcfg, cache, tok, None)

        self._decode = jax.jit(bench_decode).lower(
            made, cache_s, jax.ShapeDtypeStruct((1, 1), jnp.int32)).compile()
        self.compile_s = time.perf_counter() - t0

    def load(self, seed: int) -> None:
        """Make the weights of ``seed`` and run every program once."""
        from repro.models import greedy_sample

        self.params = self._make(ref.seed_key(seed))
        for length in self.buckets:
            cache, logits = self._prefill[length](
                self.params, np.zeros((1, length), np.int32))
            tok = greedy_sample(logits)
        cache, logits = self._decode(self.params, cache, tok)
        greedy_sample(logits).block_until_ready()
        del cache, logits

    def free(self) -> None:
        """Delete the weights from the device."""
        if self.params is not None:
            for leaf in self._jax.tree.leaves(self.params):
                leaf.delete()
        self.params = None
        gc.collect()

    # -- the window ------------------------------------------------------
    def serve(self, seed: int, seconds: float, *, trace: bool = False,
              rate: Optional[float] = None) -> Window:
        """Run the cell's traffic of ``seed`` through the engine and return
        what happened; with ``trace`` the profiler covers the last
        ``trace_seconds`` of the window."""
        import jax

        import repro
        from repro.models import greedy_sample
        from repro.serving import ContinuousBatchingEngine, Request

        spec = dict(self.spec)
        if rate is not None:
            spec["rate_per_s"] = rate
        gen = LMTraffic(spec, seed, self.shapes.vocab)
        backlog = gen.arrivals == "backlog"
        params = self.params
        prefill, decode = self._prefill, self._decode
        recs: Dict[int, Rec] = {}
        rid_of: Dict[int, int] = {}
        steps: List[Step] = []
        pending: List[Tuple[int, Any]] = []
        lock = threading.Lock()

        def prefill_fn(prompt):
            rid = rid_of[id(prompt)]
            rec = recs[rid]
            rec.admitted_s = time.perf_counter()
            steps[-1].prefills.append(rec.prompt_len)
            cache, logits = prefill[rec.prompt_len](params, prompt)
            return _Tag(cache, rid), _Tag(logits, rid, first=True)

        def decode_fn(cache, tok):
            rec = recs[cache.rid]
            context = rec.prompt_len + rec.decoded + 1
            rec.decoded += 1
            with lock:
                steps[-1].contexts.append(context)
            new_cache, logits = decode(params, cache.value, tok)
            return _Tag(new_cache, cache.rid), _Tag(logits, cache.rid)

        def sample_fn(logits):
            tok = greedy_sample(logits.value)
            if logits.first:
                tok.block_until_ready()
                rec = recs[logits.rid]
                rec.first_s = time.perf_counter()
                rec.tokens.append(tok)
                rec.times.append(rec.first_s)
                if rec.budget == 1:
                    rec.done_s = rec.first_s
            else:
                with lock:
                    pending.append((logits.rid, tok))
            return tok

        compiles = [0]

        def on_compile(event, duration, **kw):
            if event == _BACKEND_COMPILE:
                compiles[0] += 1

        trace_s = min(float(spec.get("trace_seconds", 4.0)), seconds)
        profile_dir = tempfile.mkdtemp(prefix="bench-profile-") if trace else None
        traced = None
        td = None
        session = repro.Session(self.workers)
        jax.monitoring.register_event_duration_secs_listener(on_compile)
        try:
            engine = ContinuousBatchingEngine(
                session, decode_fn, prefill_fn, max_batch=self.max_batch,
                admission_capacity=int(spec.get("admission_capacity", 2 * self.max_batch)),
                sample_fn=sample_fn)
            engine.prime()
            nxt = 0
            t_base = time.perf_counter()
            warmup = 0.0 if backlog else float(spec["warmup_s"])
            t_open = None if backlog else t_base + warmup
            t_close = None if backlog else t_open + seconds
            counting = False
            while True:
                now = time.perf_counter()
                while True:
                    req = gen[nxt]
                    due = t_base + req.arrival_s
                    if due > now:
                        break
                    rec = Rec(nxt, due, req.prompt, req.max_new_tokens)
                    recs[nxt] = rec
                    rid_of[id(req.prompt)] = nxt
                    if not engine.try_submit(Request(nxt, req.prompt, req.max_new_tokens,
                                                     arrival_s=req.arrival_s)):
                        del recs[nxt], rid_of[id(req.prompt)]
                        break
                    rec.submitted_s = time.perf_counter()
                    nxt += 1
                if (trace and traced is None and t_close is not None
                        and now >= t_close - trace_s):
                    jax.profiler.start_trace(profile_dir)
                    traced = jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN)
                    traced.__enter__()
                step = Step(time.perf_counter(), traced=traced is not None)
                steps.append(step)
                with jax.profiler.TraceAnnotation("bench.step"):
                    worked = engine.step()
                step.t1 = time.perf_counter()
                with lock:
                    done, pending[:] = list(pending), []
                for rid, tok in done:
                    rec = recs[rid]
                    rec.tokens.append(tok)
                    rec.times.append(step.t1)
                    if len(rec.tokens) == rec.budget:
                        rec.done_s = step.t1
                if backlog and t_open is None and engine.in_flight() == self.max_batch:
                    t_open = step.t1
                    t_close = t_open + seconds
                if t_open is not None and not counting and step.t1 >= t_open:
                    counting = True
                    compiles[0] = 0
                if t_close is not None and step.t1 >= t_close:
                    if traced is not None and td is None:
                        traced.__exit__(None, None, None)
                        jax.profiler.stop_trace()
                        td = trace_reduce.load(trace_reduce.find_xplane(profile_dir))
                    if backlog:
                        t_close = step.t1
                        break
                    owed = [r for r in recs.values()
                            if r.due_s <= t_close and r.first_s is None]
                    if (not owed and gen[nxt].arrival_s + t_base > t_close) \
                            or step.t1 > t_close + 60.0:
                        break
                if not worked:
                    gap = t_base + gen[nxt].arrival_s - time.perf_counter()
                    if gap > 0:
                        with jax.profiler.TraceAnnotation("bench.wait_arrival"):
                            time.sleep(min(gap, 2e-3))
            n_compiles = compiles[0]
        finally:
            jax.monitoring.unregister_event_duration_listener(on_compile)
            session.close()
            if profile_dir is not None:
                shutil.rmtree(profile_dir, ignore_errors=True)
        return Window(t_open, t_close, [recs[i] for i in sorted(recs)], steps, td, n_compiles)

    # -- the check -------------------------------------------------------
    def sample(self, window: Window, seed: int) -> List[Rec]:
        """Requests finished by the end of the run: the one with the most
        served tokens, and others drawn from the seed, up to
        ``check_requests`` of them."""
        done = [r for r in window.requests if r.done_s is not None]
        if not done:
            return []
        longest = max(done, key=lambda r: (len(r.tokens), -r.rid))
        rest = [r for r in done if r is not longest]
        rng = np.random.default_rng(np.random.SeedSequence([int(seed) % 2**64, 1]))
        n = min(len(rest), int(self.spec["check_requests"]) - 1)
        picked = [rest[i] for i in sorted(rng.choice(len(rest), n, replace=False))]
        return [longest] + picked


def check(cfg: Dict[str, Any], seed: int, sample: List[Rec], max_prompt: int,
          max_out: int, control: bool = False
          ) -> Tuple[Dict[str, float], Optional[Dict[str, float]]]:
    """By how much a served token's reference logit lies below the
    reference's best at its position, over every served token of the
    sample: the mean (``mean_logit_gap``) and the widest (``max_logit_gap``),
    with ``positions``, the tokens compared.  With ``control`` also the
    control's same numbers: at the same positions, the gap of the token
    that the float8 pass puts first.  Returns (program, control or None).
    The float32 logits of one sequence at a time are made, used and
    dropped, so the check's memory is one sequence's."""
    tokens = [np.array([int(np.asarray(t).reshape(())) for t in r.tokens], np.int64)
              for r in sample]
    vocab = int(cfg["vocab_size"])
    positions = float(sum(len(t) for t in tokens))
    if any(((t < 0) | (t >= vocab)).any() for t in tokens):
        bad = {"positions": positions, "mean_logit_gap": math.inf,
               "max_logit_gap": math.inf}
        return bad, None
    length = -(-(max_prompt + max_out) // 512) * 512
    n_rows = -(-max(len(t) for t in tokens) // 64) * 64
    seqs = [np.concatenate([r.prompt[0], t[:-1]]) for r, t in zip(sample, tokens)]
    rows = [ref.positions(r.prompt_len, len(t), n_rows) for r, t in zip(sample, tokens)]
    f32 = ref.Reference(cfg, seed)
    hid = f32.hidden(seqs, rows, length)
    if control:
        lo = ref.Reference(cfg, seed, mode="fp8")
        hid_lo = lo.hidden(seqs, rows, length)
    gaps, gaps_lo = [], []
    for i, t in enumerate(tokens):
        logits = f32.head(hid[i])
        gaps.append(ref.gaps(logits, t))
        if control:
            picks = ref.top_tokens(lo.head(hid_lo[i]), len(t))
            gaps_lo.append(ref.gaps(logits, picks))
        del logits

    def numbers(g):
        g = np.concatenate(g)
        return {"positions": positions, "mean_logit_gap": float(g.mean()),
                "max_logit_gap": float(g.max())}

    return numbers(gaps), (numbers(gaps_lo) if control else None)


# -- the numbers a run reports ------------------------------------------
def end_to_end(w: Window) -> Dict[str, float]:
    """Every end-to-end quantity a serving window gives; the cell reports
    those its ``BENCHMARK.json`` entry lists."""
    out: Dict[str, float] = {}
    emitted = sum(1 for r in w.requests for t in r.times if w.in_window(t))
    out["tok_s"] = emitted / w.seconds
    due = [r for r in w.requests if w.t_open <= r.due_s < w.t_close]
    if due:
        end = max(s.t1 for s in w.steps)
        ttft = [(r.first_s if r.first_s is not None else end) - r.due_s for r in due]
        out["ttft_p90_ms"] = stats.percentile(ttft, 90) * 1e3
    gaps = [b - a for r in w.requests for a, b in zip(r.times, r.times[1:])
            if w.in_window(b)]
    if gaps:
        out["itl_p95_ms"] = stats.percentile(gaps, 95) * 1e3
    return out


def attempted_failed(w: Window, backlog: bool) -> Tuple[int, int]:
    """Backlog: requests that emitted a token in the window, none failed
    (one in flight at the close is not a failure).  Open loop: requests due
    in the window; failed are those with no first token a minute past it."""
    if backlog:
        n = sum(1 for r in w.requests if any(w.in_window(t) for t in r.times))
        return n, 0
    due = [r for r in w.requests if w.t_open <= r.due_s < w.t_close]
    return len(due), sum(1 for r in due if r.first_s is None)


def run(ctx):
    """One run of a serving cell (see ``bench/run.py``)."""
    from bench.harness import Result

    import jax

    cell = ctx.cell
    sc = ServingCell(cell)
    sc.compile()
    sc.load(ctx.seed)
    w = sc.serve(ctx.seed, ctx.seconds, trace=ctx.trace)
    backlog = cell.traffic["arrivals"] == "backlog"
    # set-up runs from process start to the window's opening
    setup_s = w.t_open - ctx.t_process
    lags = [r.submitted_s - r.due_s for r in w.requests
            if r.submitted_s is not None and w.t_open <= r.due_s < w.t_close]
    ctx.log(f"generator late: p50 {stats.percentile(lags, 50) * 1e3:.3f} ms, max "
            f"{max(lags) * 1e3:.3f} ms over {len(lags)} requests due in the window"
            if lags else "generator late: no request due in the window")
    ctx.log(f"compiles inside the window: {w.compiles}; compile/load of programs "
            f"{sc.compile_s:.3f} s")
    memory_peak = ctx.memory_peak()
    attempted, failed = attempted_failed(w, backlog)
    sample = sc.sample(w, ctx.seed)
    run_data = LMRun(window=w, shapes=sc.shapes, device_kind=ctx.device_kind,
                     summary=(trace_reduce.summarize(w.trace) if w.trace else None))
    sc_buckets, sc_max_out = sc.buckets, sc.max_out
    sc.free()
    del sc
    gc.collect()
    if not sample:
        # nothing finished, so nothing can be compared: the run is not correct
        checks = [("unfinished_sample", 1.0, 0.0)]
    else:
        got, _ = check(cell.config, ctx.seed, sample, max(sc_buckets), sc_max_out)
        ctx.log(f"check: {len(sample)} finished requests, {int(got['positions'])} "
                "served tokens compared with the float32 reference")
        checks = ctx.checks(got)
    return Result(attempted=attempted, failed=failed, setup_s=setup_s,
                  end_to_end=end_to_end(w), run=run_data, checks=checks,
                  memory_peak_bytes=memory_peak, summary=run_data.summary)


@dataclasses.dataclass
class LMRun:
    """What the per-layer readers of a serving cell read."""

    window: Window
    shapes: LMShapes
    device_kind: str
    summary: Optional[trace_reduce.Summary]

    @property
    def peak(self) -> Dict[str, float]:
        return peaks(self.device_kind)

    def traced_steps(self) -> Optional[List[Tuple[Step, trace_reduce.Span]]]:
        """Each step inside the traced window with its ``bench.step`` span
        on the trace's clock; None when the two do not pair one to one."""
        if self.summary is None:
            return None
        steps = [s for s in self.window.steps if s.traced]
        spans = trace_reduce.host_spans(self.window.trace, "bench.step",
                                        self.summary.window_ns)
        if not steps or len(steps) != len(spans):
            return None
        return list(zip(steps, spans))

    def least_seconds(self, flops: float, nbytes: float) -> float:
        return least_seconds(flops, nbytes, self.peak)
