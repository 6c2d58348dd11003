"""Serving driver: batched prefill + decode with a KV cache.

Initializes a model from ``PRNGKey(0)``, prefills a batch of prompts, then
decodes N tokens per request.  By default the model is the architecture's
reduced CPU configuration; ``--layers N`` serves it at its published widths
and dtype instead, cut only in depth to N layers (the size for one chip).
Three decode schedulers:

* ``jit``     — the original monolithic jitted decode loop (no task graph);
* ``dynamic`` — each decode step is a task graph (per-shard decode/sample
  plus a gather join) executed by a ``Session(scheduler="dynamic")``;
* ``pool``    — the same graphs served by a ``Session(scheduler="pool")``
  (a persistent :class:`~repro.replay.ReplayPool` under the hood): step 1
  records, every later step replays on warm executor threads, drift
  triggers adaptive re-recording.

``--arrivals poisson`` switches from the fixed batch to the request-level
continuous-batching front end (:mod:`repro.serving`): a seeded Poisson
stream of single-prompt requests flows through a bounded admission queue
into per-step dynamically composed batches, with early exit on each
request's token budget and warm pool replays per batch shape.

``--procs N`` (poisson only) shards the request stream across N worker
processes (:mod:`repro.mp`), each hosting its own executor pool; children
rebuild the model from the same seed via :func:`make_serving_fns` and
adopt parent-seeded recordings through ``--cache-dir``, so the sharded
token streams stay bit-identical to single-process serving.  A chip
belongs to one process, so on a TPU ``--procs`` is refused.

Run:  PYTHONPATH=src python examples/serve_lm.py --tokens 32 --scheduler pool
      PYTHONPATH=src python examples/serve_lm.py --layers 8 --prompt-len 512
      PYTHONPATH=src python examples/serve_lm.py --arrivals poisson \
          --rate 100 --requests 12 --scheduler pool
      PYTHONPATH=src python examples/serve_lm.py --arrivals poisson \
          --rate 100 --requests 16 --scheduler pool --procs 2
"""

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp

import repro
from repro.configs import get_config
from repro.device import describe_device, enable_compile_cache
from repro.models import (build_decode_graph, decode_step, greedy_sample,
                          init_params, make_decode_state, prefill)
from repro.replay import GraphCache


def serving_config(arch, layers=0):
    """The served configuration: published widths and dtype cut to
    ``layers`` layers, or the reduced CPU configuration when ``layers`` is
    0."""
    if layers:
        return dataclasses.replace(get_config(arch), n_layers=layers).validate()
    return get_config(arch).reduced()


def make_serving_fns(arch="qwen3-14b", prompt_len=64, tokens=32, layers=0):
    """Engine-fns factory for ``--procs``: worker processes re-import this
    by reference (``serve_lm:make_serving_fns``) and rebuild the exact
    parent model — same config, same ``PRNGKey(0)`` params, same jitted
    step fns — so sharded token streams stay bit-identical to
    single-process serving."""
    cfg = serving_config(arch, layers)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = prompt_len + tokens + 1
    prefill_fn = jax.jit(
        lambda p, b: prefill(p, cfg, b, None, max_len=max_len))
    decode_fn = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t, None))
    return (lambda cache, tok: decode_fn(params, cache, tok),
            lambda prompt: prefill_fn(params, {"tokens": prompt}))


def serve_poisson(args, cfg, params, prefill_fn, decode_fn):
    """Continuous batching under streaming traffic (--arrivals poisson)."""
    from repro.serving import ContinuousBatchingEngine, PoissonWorkload

    lo, _, hi = args.max_new.partition(":")
    budget = (int(lo), int(hi or lo))
    if budget[1] > args.tokens:
        raise SystemExit(f"--max-new hi {budget[1]} exceeds --tokens "
                         f"{args.tokens} (the KV-cache budget)")
    workload = PoissonWorkload(args.rate, args.requests, seed=args.seed,
                               prompt_len=args.prompt_len,
                               max_new_tokens=budget,
                               vocab_size=cfg.vocab_size)
    print(f"arch={cfg.name} scheduler={args.scheduler} "
          f"workers={args.workers} max_batch={args.max_batch} "
          + (f"procs={args.procs} " if args.procs else "")
          + workload.describe())
    pool = args.scheduler == "pool"
    cache_store = (GraphCache(args.cache_dir)
                   if args.cache_dir and pool else None)
    kwargs = {"pool_kwargs": {"warmup_runs": 0}} if pool else {}
    engine_kwargs = {}
    if args.procs:
        kwargs["procs"] = args.procs
        # children rebuild the model by import reference — see
        # make_serving_fns; launch as `python examples/serve_lm.py` so the
        # examples dir is on sys.path for the spawned workers
        engine_kwargs = {
            "procs": args.procs,
            "fns_ref": ("serve_lm:make_serving_fns",
                        {"arch": args.arch, "prompt_len": args.prompt_len,
                         "tokens": args.tokens, "layers": args.layers}),
        }
    with repro.Session(args.workers, scheduler=args.scheduler,
                       cache=cache_store, trace=bool(args.trace),
                       **kwargs) as session:
        engine = ContinuousBatchingEngine(
            session,
            lambda cache, tok: decode_fn(params, cache, tok),
            lambda prompt: prefill_fn(params, {"tokens": prompt}),
            max_batch=args.max_batch, **engine_kwargs)
        if not args.procs:
            engine.prime()  # step graphs + keys built before traffic starts
        report = engine.run(workload.requests())
        if pool and not args.procs:
            for ckey, stats in session.pool.describe().items():
                print(f"pool[{ckey[:20]}…]: {stats}")
        if args.procs:
            for s in engine.mp_stats["per_proc"]:
                print(f"proc{s['proc']}[pid {s['pid']}]: "
                      f"{s['completed']} requests, {s['steps']} steps "
                      f"({s['warm_steps']} warm), {s['records']} records")
            if engine.mp_stats["dead"]:
                print(f"dead workers {engine.mp_stats['dead']}: "
                      f"{engine.mp_stats['fallback']} requests re-served "
                      "in-process")
    print(report.describe())
    s = report.summary()
    print(f"per-token p50/p99: {s['p50_tok_ms']:.2f}/{s['p99_tok_ms']:.2f} "
          f"ms, ttft p50/p99: {s['ttft_p50_ms']:.2f}/{s['ttft_p99_ms']:.2f} "
          f"ms, sustained {s['tok_s']:.0f} tok/s")
    if args.trace and report.trace is not None:
        from repro.obs import write_trace
        write_trace(report.trace, args.trace,
                    extra={"workers": args.workers, "arch": cfg.name,
                           "scheduler": args.scheduler,
                           "arrivals": "poisson"})
        m = report.trace.metrics()
        print(f"trace:   {args.trace} (every step of the session's window, "
              f"dispatch overhead "
              f"{m['dispatch_overhead_fraction']:.1%}, "
              "open in https://ui.perfetto.dev)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--layers", type=int, default=0,
                    help="serve the published widths and dtype cut to N "
                         "layers (default 0: the reduced CPU configuration)")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--scheduler", choices=("jit", "dynamic", "pool"),
                    default="pool")
    ap.add_argument("--workers", type=int, default=2,
                    help="runtime workers for dynamic/pool scheduling")
    ap.add_argument("--shards", type=int, default=0,
                    help="batch shards per decode graph (default: batch)")
    ap.add_argument("--cache-dir", default=None,
                    help="on-disk GraphCache dir (pool): recordings persist "
                         "across processes / ship to replicas")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="serve with the flight recorder on and export every "
                         "decode step it holds as Perfetto JSON here "
                         "(open in https://ui.perfetto.dev)")
    ap.add_argument("--arrivals", choices=("batch", "poisson"),
                    default="batch",
                    help="batch: fixed batch decoded to --tokens; poisson: "
                         "streaming requests through the continuous-"
                         "batching engine")
    ap.add_argument("--rate", type=float, default=100.0,
                    help="poisson arrival rate, requests/s")
    ap.add_argument("--requests", type=int, default=12,
                    help="poisson stream length")
    ap.add_argument("--max-new", default="2:8", metavar="LO:HI",
                    help="poisson per-request token budget span")
    ap.add_argument("--max-batch", type=int, default=4,
                    help="continuous-batching decode slots")
    ap.add_argument("--seed", type=int, default=0,
                    help="poisson workload seed (same seed, same stream)")
    ap.add_argument("--procs", type=int, default=0,
                    help="shard the poisson stream across N worker "
                         "processes (repro.mp), each with --workers "
                         "runtime workers; token streams stay bit-"
                         "identical to --procs 0")
    args = ap.parse_args()
    if args.trace and args.scheduler == "jit":
        ap.error("--trace needs a task-graph scheduler (dynamic or pool)")
    if args.arrivals == "poisson" and args.scheduler == "jit":
        ap.error("--arrivals poisson needs a task-graph scheduler")
    if args.procs and args.arrivals != "poisson":
        ap.error("--procs shards the streaming front end; add "
                 "--arrivals poisson")
    if args.procs and args.trace:
        ap.error("--trace is per-process; not supported with --procs")

    enable_compile_cache()
    print(f"device: {describe_device()}")
    cfg = serving_config(args.arch, args.layers)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_len = args.prompt_len + args.tokens + 1

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, cfg.n_patches, cfg.d_model),
            cfg.jdtype)
    if cfg.family == "encdec":
        batch["enc_input"] = jax.random.normal(
            jax.random.PRNGKey(2), (args.batch, 32, cfg.d_model), cfg.jdtype)

    prefill_fn = jax.jit(lambda p, b: prefill(p, cfg, b, None, max_len=max_len))
    decode_fn = jax.jit(lambda p, c, t: decode_step(p, cfg, c, t, None))

    if args.arrivals == "poisson":
        if cfg.family in ("vlm", "encdec"):
            ap.error("--arrivals poisson supports decoder-only families")
        serve_poisson(args, cfg, params, prefill_fn, decode_fn)
        return

    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len} "
          f"scheduler={args.scheduler}")

    if args.scheduler == "jit":
        t0 = time.perf_counter()
        cache, logits = prefill_fn(params, batch)
        logits.block_until_ready()
        t_prefill = time.perf_counter() - t0
        tok = greedy_sample(logits)
        out_tokens = [tok]
        t0 = time.perf_counter()
        for _ in range(args.tokens - 1):
            cache, logits = decode_fn(params, cache, tok)
            tok = greedy_sample(logits)
            out_tokens.append(tok)
        tok.block_until_ready()
        t_decode = time.perf_counter() - t0
        gen = jnp.concatenate(out_tokens, axis=1)
    else:
        n_shards = args.shards or args.batch
        t0 = time.perf_counter()
        state = make_decode_state(params, cfg, batch, n_shards=n_shards,
                                  max_len=max_len, prefill_fn=prefill_fn)
        state.step_tokens.block_until_ready()
        t_prefill = time.perf_counter() - t0

        cache_store = (GraphCache(args.cache_dir)
                       if args.cache_dir and args.scheduler == "pool" else None)
        session = repro.Session(args.workers, scheduler=args.scheduler,
                                cache=cache_store, trace=bool(args.trace))
        with session:
            t0 = time.perf_counter()
            for _ in range(args.tokens - 1):
                g = build_decode_graph(state, decode_fn)
                session.run(g)
            state.step_tokens.block_until_ready()
            t_decode = time.perf_counter() - t0
            gen = state.tokens()
            if args.scheduler == "pool":
                for ckey, stats in session.pool.describe().items():
                    print(f"pool[{ckey[:20]}…]: {stats}")
            # every decode step's events: the session recorder's window
            window = session.trace_window()
        if args.trace and window is not None and window.events:
            from repro.obs import write_trace
            trace = window.assemble()
            write_trace(trace, args.trace,
                        extra={"workers": args.workers, "arch": cfg.name,
                               "scheduler": args.scheduler})
            m = trace.metrics()
            print(f"trace:   {args.trace} "
                  f"(dispatch overhead {m['dispatch_overhead_fraction']:.1%}, "
                  f"open in https://ui.perfetto.dev)")

    print(f"prefill: {t_prefill*1e3:.1f} ms "
          f"({args.batch*args.prompt_len/t_prefill:.0f} tok/s)")
    print(f"decode:  {t_decode*1e3:.1f} ms for {args.tokens-1} steps "
          f"({args.batch*(args.tokens-1)/t_decode:.0f} tok/s)")
    print("sample token ids:", gen[0, :16].tolist())


if __name__ == "__main__":
    main()
