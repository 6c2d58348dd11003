"""Run one cell of the benchmark once, on the chip this process finds.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

run from the root of a checkout.  The cell's configuration, traffic, limits
and metric readers are found by the names in ``BENCHMARK.json`` (see
``bench/registry.py``); the driver of the configuration's kind sets the
cell up, measures ``--seconds`` of it, and checks what the timed path
produced against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number compared
with its limit.  The checks are also the last lines of standard error.

Where JAX finds no TPU, or fewer chips than the cell asks for, the command
exits non-zero and prints no result.  JAX's persistent compilation cache
is kept in ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR``
names another directory.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
from typing import Any, Dict, Optional  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench.harness import Context, NoChip, Result, verdict  # noqa: E402


def _args(argv):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def setup_jax(root: pathlib.Path, chips: int, *, require_chip: bool = True):
    """Import the program, keep the compile cache in the checkout, and find
    the chips; returns ``jax.devices()``."""
    src = str(pathlib.Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import jax

    if require_chip:
        from repro.device import enable_compile_cache

        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX computes on {devices[0].platform}; the "
                         "benchmark runs on the chip and does not fall back")
        if len(devices) < chips:
            raise NoChip(f"the cell needs {chips} chips; JAX finds {len(devices)}")
    return devices


def result_line(cell: registry.Cell, res: Result, devices, trace: bool,
                log=None) -> Dict[str, Any]:
    """The JSON object a run prints last."""
    metrics: Dict[str, Dict[str, Any]] = {}
    if not trace:
        for m in cell.end_to_end:
            value = res.setup_s if m["name"] == "setup_s" else res.end_to_end[m["name"]]
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        for m in cell.per_layer:
            value = registry.metric_reader(cell.root, m["name"])(res.run)
            if value is None:
                if log:
                    log(f"metric {m['name']}: nothing to read in this run")
                continue
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(res.memory_peak_bytes)}
    line: Dict[str, Any] = {
        "correct": verdict(res.checks),
        "attempted": int(res.attempted), "failed": int(res.failed),
        "metrics": metrics, "device": device,
    }
    if trace and res.summary is not None:
        device["busy_s"] = res.summary.busy_s
        device["window_s"] = res.summary.window_s
        line["breakdown"] = {"device_ops": [list(x) for x in res.summary.top_ops],
                             "idle_gaps": [list(x) for x in res.summary.idle_gaps]}
    line["checks"] = {name: {"value": v, "limit": lim} for name, v, lim in res.checks}
    return line


def main(argv=None, *, root: pathlib.Path = ROOT, require_chip: bool = True,
         t_process: Optional[float] = None) -> int:
    args = _args(argv)
    cell = registry.resolve(root, args.workload)
    limits = json.loads((pathlib.Path(root) / registry.BENCH_DIR / "limits"
                         / f"{cell.name}.json").read_text())
    try:
        devices = setup_jax(root, cell.chips, require_chip=require_chip)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    ctx = Context(cell=cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
                  t_process=T_PROCESS if t_process is None else t_process,
                  device_kind=devices[0].device_kind, limits=limits)
    res = registry.driver(cell).run(ctx)
    line = result_line(cell, res, devices, ctx.trace, log=ctx.log)
    for name, v, lim in res.checks:
        ctx.log(f"check {name}: {v!r} (limit {lim!r})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
