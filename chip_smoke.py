"""Smoke run of the system on one TPU chip, through the entry points a user calls.

Two phases, each on the normal path:

* serving: Qwen3-14B at its published widths and full vocabulary, in
  bfloat16, cut in depth to 8 layers, with random weights from
  ``PRNGKey(0)``.  A seeded Poisson stream of 8 requests with 512-token
  prompts and budgets of 16-32 tokens goes through
  ``Session(scheduler="pool")`` -> ``ContinuousBatchingEngine`` -> the lane
  step graph -> ``repro.models.lm``.  One request is then decoded again
  through its KV cache, and those logits are compared with a full forward
  pass over the same tokens.
* cholesky: the paper's small size, 40 x 40 tiles of 192 in float32,
  factored through ``Session`` by the tiled task graph, and checked by its
  residual ||A - L L^T|| / ||A||.

The numbers printed come from one smoke run; they are not a benchmark.  A
phase that fails raises, and the script exits non-zero.  Where JAX finds
no TPU the script exits non-zero before any phase runs.  The last line of
standard output, printed only when both phases passed, is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.

Run from the repository root:  python chip_smoke.py
"""

from __future__ import annotations

import dataclasses
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

ARCH = "qwen3-14b"
LAYERS = 8               # depth cut: ~7.8 GiB of bf16 weights on a 16 GB chip
PROMPT = 512
BUDGET = (16, 32)        # new tokens per request, the prefill token included
REQUESTS = 8
RATE = 8.0               # requests/s: arrivals overlap, so lanes batch
MAX_BATCH = 4
WORKERS = 2              # executor threads of the serving and factorization sessions

# Both sides of the logit comparison run in bfloat16, which rounds each
# result to 8 significant bits (relative step 2^-8), and they accumulate in
# different orders: prefill attends over the prompt in chunks, decode over
# the cache one token at a time.  Through eight residual layers that
# compounds to about 1e-2 of the logits' norm.  A wrong position, a stale
# cache entry or a wrong mask gives errors of order 1.
LOGIT_TOL = 5e-2         # max over positions of ||cached - full|| / ||full||

# float32 rounding is 6e-8.  A Cholesky factor's backward error grows like
# sqrt(n) eps in practice (5e-6 at n = 7680) and like n eps at worst
# (5e-4); tile products run as one bfloat16 pass leave about 1e-3.
RESIDUAL_BOUND = 1e-4


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or non-finite result."""


def _check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _compile(fn, *args):
    """``jit(fn)`` compiled for ``args`` (arrays or shapes), and the seconds
    that lowering and compiling took."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, time.perf_counter() - t0


def _pct_ms(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values), q)) * 1e3


def serve(cfg, *, prompt_len: int, budget, n_requests: int, rate: float,
          max_batch: int, workers: int, seed: int = 0) -> dict:
    """Serve a seeded Poisson stream through the pool scheduler, then check
    one request's cached-decode logits against a full forward pass."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import repro
    from repro.models import greedy_sample, lm
    from repro.serving import ContinuousBatchingEngine, PoissonWorkload

    max_len = prompt_len + budget[1] + 1
    key = jax.random.PRNGKey(seed)
    init, t_init = _compile(lambda k: lm.init_params(cfg, k), key)
    params = init(key)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"serving: {cfg.name}, {cfg.n_layers} layers, d_model {cfg.d_model}, "
          f"{cfg.n_heads}/{cfg.n_kv_heads} heads of {cfg.head_dim}, d_ff "
          f"{cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}: "
          f"{n_params / 1e9:.3f} B params")

    def prefill_py(p, t):
        return lm.prefill(p, cfg, {"tokens": t}, None, max_len=max_len)

    prompt_s = jax.ShapeDtypeStruct((1, prompt_len), jnp.int32)
    prefill, t_prefill = _compile(prefill_py, params, prompt_s)
    cache_s, _ = jax.eval_shape(prefill_py, params, prompt_s)
    decode, t_decode = _compile(
        lambda p, c, t: lm.decode_step(p, cfg, c, t, None),
        params, cache_s, jax.ShapeDtypeStruct((1, 1), jnp.int32))

    def prefill_fn(prompt):
        return prefill(params, prompt)

    def decode_fn(cache, tok):
        return decode(params, cache, tok)

    workload = PoissonWorkload(rate, n_requests, seed=seed,
                               prompt_len=prompt_len, max_new_tokens=budget,
                               vocab_size=cfg.vocab_size)
    requests = workload.requests()
    # one untimed request-shaped pass: the sampler's first dispatch happens
    # here, not inside the first request's time to first token
    cache, logits = prefill_fn(requests[0].prompt)
    decode_fn(cache, greedy_sample(logits))[1].block_until_ready()

    with repro.Session(workers, scheduler="pool",
                       pool_kwargs={"warmup_runs": 0}) as session:
        engine = ContinuousBatchingEngine(session, decode_fn, prefill_fn,
                                          max_batch=max_batch)
        engine.prime()
        report = engine.run(requests)
    _check(report.completed == n_requests,
           f"{report.completed} of {n_requests} requests completed")
    for req in requests:
        toks = report.records[req.rid].tokens
        _check(len(toks) == req.max_new_tokens,
               f"request {req.rid}: {len(toks)} tokens for a budget of "
               f"{req.max_new_tokens}")
        _check(all(0 <= t < cfg.vocab_size for t in toks),
               f"request {req.rid} emitted a token id outside the vocabulary")

    # the same request again, outside the engine: prefill, then decode
    # through the cache, feeding the tokens the engine served
    req = requests[0]
    served = np.asarray(report.records[req.rid].tokens, np.int32)
    cache, logits = prefill_fn(req.prompt)
    rows = [logits[:, -1]]
    for tok in served[:-1]:
        cache, logits = decode_fn(cache, jnp.full((1, 1), tok, jnp.int32))
        rows.append(logits[:, -1])
    cached = np.asarray(jnp.concatenate(rows).astype(jnp.float32))

    def full_py(p, t):
        h = lm.forward(p, cfg, {"tokens": t}, None, remat=False)
        return lm.logits_from_hidden(p, cfg, h[:, prompt_len - 1:])

    seq = np.concatenate([req.prompt[0], served[:-1]])[None]
    full_fn, t_full = _compile(full_py, params,
                               jax.ShapeDtypeStruct(seq.shape, jnp.int32))
    full = np.asarray(full_fn(params, seq)[0].astype(jnp.float32))

    _check(np.isfinite(cached).all(), "non-finite logit on the cached path")
    _check(np.isfinite(full).all(), "non-finite logit in the full forward")
    _check(cached.shape == full.shape == (len(served), cfg.vocab_size),
           f"logit shapes {cached.shape} and {full.shape}")
    _check(np.array_equal(cached.argmax(-1), served),
           "decoding the request again gave other tokens than the engine served")
    rel = (np.linalg.norm(cached - full, axis=-1)
           / np.linalg.norm(full, axis=-1))
    top1 = float((cached.argmax(-1) == full.argmax(-1)).mean())
    print(f"serving: request {req.rid}, {len(served)} positions: cached vs "
          f"full-forward logits, max relative error {rel.max():.3e} "
          f"(tolerance {LOGIT_TOL}), top-1 agreement {top1:.3f}")
    _check(rel.max() <= LOGIT_TOL,
           f"cached-decode logits differ from the full forward by "
           f"{rel.max():.3e} > {LOGIT_TOL}")

    s = report.summary()
    lags = [r.submit_lag_s for r in report.records.values()]
    stats = jax.devices()[0].memory_stats() or {}
    compile_s = {"init": t_init, "prefill": t_prefill, "decode": t_decode,
                 "full_forward": t_full}
    print("serving (one smoke run, not a benchmark): "
          f"{report.completed} requests, {report.total_tokens} tokens in "
          f"{report.steps} steps; TTFT p50 {s['ttft_p50_ms']} ms, p99 "
          f"{s['ttft_p99_ms']} ms; gap between tokens p50 {s['p50_tok_ms']} "
          f"ms, p99 {s['p99_tok_ms']} ms; {s['tok_s']} tok/s; generator "
          f"late by p50 {_pct_ms(lags, 50):.3f} ms, max "
          f"{max(lags) * 1e3:.3f} ms; peak_bytes_in_use "
          f"{stats.get('peak_bytes_in_use', 'not reported')}")
    print("serving: compile seconds "
          + ", ".join(f"{k} {v:.3f}" for k, v in compile_s.items()))
    return {"compile_s": sum(compile_s.values()), "max_rel_err": float(rel.max())}


def factor(nb: int, b: int, *, workers: int, seed: int = 0) -> dict:
    """Factor a seeded float32 SPD matrix of ``nb`` x ``nb`` tiles of ``b``
    through the task graph, and check the residual."""
    import jax
    import jax.numpy as jnp

    import repro
    from repro.linalg import (build_cholesky_graph, cholesky_extract,
                              random_spd, to_tiles)
    from repro.linalg.tiles import (TILE_PRECISION, tile_gemm_sub, tile_potrf,
                                    tile_trsm_right_lower_t)

    n = nb * b
    a = random_spd(n, seed=seed, dtype=jnp.float32)
    _check(a.dtype == jnp.float32, f"matrix built as {a.dtype}")
    # first calls of the three tile kernels: their compile, off the clock
    t0 = time.perf_counter()
    eye = jnp.eye(b, dtype=jnp.float32)
    tile_gemm_sub(eye, eye, eye).block_until_ready()
    tile_trsm_right_lower_t(eye, tile_potrf(eye)).block_until_ready()
    t_compile = time.perf_counter() - t0

    store = to_tiles(a, b)
    graph = build_cholesky_graph(nb, b, store=store)
    with repro.Session(workers, policy="hybrid") as session:
        t0 = time.perf_counter()
        report = session.run(graph, timeout=900.0)
        l = cholesky_extract(store).block_until_ready()
        wall = time.perf_counter() - t0
    with jax.default_matmul_precision("highest"):
        residual = float(jnp.linalg.norm(a - l @ l.T) / jnp.linalg.norm(a))
    _check(bool(jnp.isfinite(l).all()), "non-finite entry in the factor")
    print(f"cholesky (one smoke run, not a benchmark): n={n}, {nb}x{nb} "
          f"tiles of {b}, float32, {len(report.results)} tasks on {workers} "
          f"workers in {wall:.3f} s; tile matmul precision {TILE_PRECISION}; "
          f"residual ||A - LL^T||/||A|| = {residual:.3e} (bound "
          f"{RESIDUAL_BOUND}); tile-kernel compile seconds {t_compile:.3f}")
    _check(residual <= RESIDUAL_BOUND,
           f"Cholesky residual {residual:.3e} > {RESIDUAL_BOUND}")
    return {"compile_s": t_compile, "residual": residual}


def main() -> int:
    from benchmarks.common import B, SIZES
    from repro.configs import get_config
    from repro.device import describe_device, enable_compile_cache

    cache_dir = enable_compile_cache()
    dev = describe_device(require_tpu=True)
    print(f"device: {dev}; compile cache: {cache_dir}")
    cfg = dataclasses.replace(get_config(ARCH), n_layers=LAYERS).validate()
    served = serve(cfg, prompt_len=PROMPT, budget=BUDGET, n_requests=REQUESTS,
                   rate=RATE, max_batch=MAX_BATCH, workers=WORKERS)
    factored = factor(SIZES["small"], B, workers=WORKERS)
    print(f"compile seconds, both phases: "
          f"{served['compile_s'] + factored['compile_s']:.3f}")
    print(json.dumps({"ok": True, "device": dev.as_dict()}))
    return 0


if __name__ == "__main__":
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke.py runs from a checkout of the repository; "
                 f"{ROOT / 'src' / 'repro'} is missing")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.device import NoAcceleratorError

    try:
        code = main()
    except NoAcceleratorError as e:
        sys.exit(f"chip_smoke: {e}")
    sys.exit(code)
