"""A toy checkout for the benchmark's CPU tests: a copy of ``bench/`` with
toy cells added as files only (a configuration, a traffic mix and a limit
each), as a later change would add a cell."""

from __future__ import annotations

import json
import pathlib
import shutil
from typing import Dict

REPO = pathlib.Path(__file__).resolve().parents[1]

TOY_LM = dict(name="toy-qwen3", hidden_size=64, num_attention_heads=4,
              num_key_value_heads=2, head_dim=16, intermediate_size=128,
              vocab_size=300, num_hidden_layers=2)

#: cell -> (configuration, traffic, its end-to-end and per-layer metrics)
TOY_CELLS = {
    "toy.batch": ("toy-qwen3", "toy-batch", ["tok_s"],
                  ["serve_mfu", "decode_roofline", "device_idle_frac.batch"]),
    "toy.poisson": ("toy-qwen3", "toy-poisson", ["ttft_p90_ms", "itl_p95_ms"],
                    ["queue_wait_p90_ms", "prefill_device_frac", "serve_mfu.code",
                     "prefill_roofline"]),
    "toy.chol": ("toy-chol", "toy-tiles", ["chol_gflops"],
                 ["sched_gap_us", "device_idle_frac.chol"]),
}

#: the entries a toy cell adds for metrics that ``BENCHMARK.json`` lacks
_E2E = {"tok_s": ("tokens/s", "higher"), "ttft_p90_ms": ("ms", "lower"),
        "itl_p95_ms": ("ms", "lower"), "chol_gflops": ("GFLOP/s", "higher")}
_LAYER = {"serve_mfu": ("%", "tok_s"), "decode_roofline": ("%", "tok_s"),
          "device_idle_frac.batch": ("fraction", "tok_s"),
          "queue_wait_p90_ms": ("ms", "ttft_p90_ms"),
          "prefill_device_frac": ("fraction", "itl_p95_ms"),
          "serve_mfu.code": ("%", "ttft_p90_ms"), "prefill_roofline": ("%", "ttft_p90_ms"),
          "sched_gap_us": ("us", "chol_gflops"), "device_idle_frac.chol": ("fraction", "chol_gflops")}

#: between the toy's sound readings (mean gaps under 0.001, factor errors under
#: 5e-8) and its controls' (float8 mean gaps over 0.004, errors of products in
#: three bfloat16 passes over 3e-7, on the seeds test_bench_reference uses)
TOY_LIMITS = {"toy.batch": {"mean_logit_gap": {"limit": 0.0025}},
              "toy.poisson": {"mean_logit_gap": {"limit": 0.0025}},
              "toy.chol": {"factor_rel_error": {"limit": 1.2e-7}}}


def _write(path: pathlib.Path, obj) -> None:
    path.write_text(json.dumps(obj, indent=1))


def toy_root(tmp: pathlib.Path) -> pathlib.Path:
    """A checkout at ``tmp`` holding ``BENCHMARK.json`` and ``bench/`` with
    the toy cells added; returns its root."""
    root = pathlib.Path(tmp)
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    b = root / "bench"
    lm = json.loads((b / "configs/qwen3-14b-l8.json").read_text())
    lm.update(TOY_LM)
    _write(b / "configs/toy-qwen3.json", lm)
    chol = json.loads((b / "configs/cholesky-n7680.json").read_text())
    chol.update(name="toy-chol", n=64)
    _write(b / "configs/toy-chol.json", chol)
    batch = json.loads((b / "traffic/conv-batch.json").read_text())
    batch.update(prompt_buckets=[16, 32], prompt_weights=[0.5, 0.5],
                 output_tokens={"dist": "log_uniform", "min": 4, "max": 24},
                 block=4, max_batch=4, admission_capacity=8, check_requests=4,
                 trace_seconds=0.5)
    _write(b / "traffic/toy-batch.json", batch)
    poisson = json.loads((b / "traffic/code-poisson.json").read_text())
    poisson.update(prompt_buckets=[16, 32], prompt_weights=[0.6, 0.4],
                   output_tokens={"dist": "log_uniform", "min": 2, "max": 8},
                   max_batch=4, admission_capacity=8, rate_per_s=20.0,
                   warmup_s=0.3, trace_seconds=0.5)
    _write(b / "traffic/toy-poisson.json", poisson)
    tiles = json.loads((b / "traffic/tiles-b192.json").read_text())
    tiles.update(tile=16, trace_seconds=0.3)
    _write(b / "traffic/toy-tiles.json", tiles)
    bench["configs"] += [
        {"name": "toy-qwen3", "source": "toy", "file": "bench/configs/toy-qwen3.json",
         "reduced": [], "why": "toy"},
        {"name": "toy-chol", "source": "toy", "file": "bench/configs/toy-chol.json",
         "reduced": [], "why": "toy"}]
    for cell, (config, traffic, e2e, layer) in TOY_CELLS.items():
        bench["workloads"].append({"name": cell, "config": config, "traffic": traffic,
                                   "chips": 1, "why": "toy"})
        for kind, names in (("end_to_end", e2e), ("per_layer", layer)):
            have = {m["name"]: m for m in bench[kind]}
            for name in names:
                if name in have:
                    have[name]["workloads"].append(cell)
                elif kind == "end_to_end":
                    unit, better = _E2E[name]
                    bench[kind].append({"name": name, "unit": unit, "better": better,
                                        "bound": 0.25, "source": "host_clock",
                                        "workloads": [cell]})
                else:
                    unit, moves = _LAYER[name]
                    bench[kind].append({"name": name, "unit": unit, "better": "higher",
                                        "source": "device_trace", "layer": "toy",
                                        "moves": moves, "workloads": [cell]})
        _write(b / "limits" / f"{cell}.json", TOY_LIMITS[cell])
    _write(root / "BENCHMARK.json", bench)
    return root


def limits(root: pathlib.Path, cell: str) -> Dict:
    return json.loads((root / "bench/limits" / f"{cell}.json").read_text())


def last_json(text: str):
    """The last line of ``text`` that holds a JSON object, or None."""
    lines = [ln for ln in text.strip().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1]) if lines else None


def toy_run(root, cell, seed, trace, capsys):
    """One run of ``cell`` at ``root`` through ``bench/run.py``'s ``main``,
    without the look for a chip; returns its result line."""
    from bench import run

    rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", "1",
                   "--trace", str(trace)], root=root, require_chip=False)
    out = capsys.readouterr()
    assert rc == 0, out.err[-2000:]
    line = last_json(out.out)
    assert list(line)[-1] == "checks"
    assert out.err.strip().splitlines()[-1].startswith("check ")
    return line
