"""Read the correctness numbers of a cell over many seeds in one process,
with the control beside them, and judge both by the cell's limits.

    python bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s> [--control 1]

For each seed it sets the cell up, runs the window as a benchmark run
does (untraced), and reads the numbers a run compares:

* serving: over every served token of the sample of finished requests,
  the mean and the widest gap by which the token's float32 reference logit
  lies below the reference's best (``mean_logit_gap``,
  ``max_logit_gap``);
* factorization: the largest relative error of a sampled factor against
  float64 LAPACK (``factor_rel_error``).

With ``--control 1`` it also reads the control on the same inputs, the
reference in the next precision down: for a bfloat16 model the pass
computed in float8 e4m3 (weights per output column, activations per
token), read at the same positions for the token it puts first; for
float32 at ``highest`` the tiled factorization with its products in three
bfloat16 passes (what ``high`` computes).  Each row carries the verdict
that ``bench/run.py`` would give the program's numbers (``correct``) and
the control's (``control_correct``) under the limits in
``bench/limits/<cell>.json``; the control has to read false.  One JSON line
per seed goes to standard output.  The limits are set from these readings
(see ``PERF.md``); the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench.harness import compare, verdict  # noqa: E402
from bench.run import setup_jax  # noqa: E402


def serving(cell, seeds, seconds, control):
    from bench.drivers.lm_serve import ServingCell, check, end_to_end

    sc = ServingCell(cell)
    sc.compile()
    for seed in seeds:
        t0 = time.perf_counter()
        sc.load(seed)
        w = sc.serve(seed, seconds)
        sample = sc.sample(w, seed)
        e2e = end_to_end(w)
        sc.free()
        t1 = time.perf_counter()
        if not sample:
            yield {"seed": seed, "requests": 0, "end_to_end": e2e}, None, None
            continue
        got, lo = check(cell.config, seed, sample, max(sc.buckets), sc.max_out, control)
        row = {"seed": seed, **got, "requests": len(sample), "end_to_end": e2e,
               "serve_s": t1 - t0, "check_s": time.perf_counter() - t1}
        if lo is not None:
            row.update({f"control_{k}": v for k, v in lo.items() if k != "positions"})
        yield row, got, lo


def factorization(cell, seeds, seconds, control):
    import numpy as np

    from bench.drivers.cholesky import CholeskyCell
    from bench.reference import cholesky as ref

    cc = CholeskyCell(cell)
    keep = int(cell.traffic.get("check_factorizations", 2))
    for seed in seeds:
        t0 = time.perf_counter()
        cc.load(seed)
        w = cc.serve(seed, seconds, keep=keep)
        t1 = time.perf_counter()
        got = {"factor_rel_error": cc.check(w.kept)}
        out = {"seed": seed, "factorizations": w.count, **got}
        lo = None
        if control:
            a0 = np.asarray(cc.a0, np.float64)
            errs = []
            for _, shift, _l in w.kept:
                import jax.numpy as jnp
                l_c = ref.tiled_cholesky(ref.shifted(cc.a0, jnp.float32(shift)), cc.b, "high")
                errs.append(ref.relative_error(np.asarray(l_c), ref.reference_factor(
                    a0 + shift * np.eye(cc.n))))
            lo = {"factor_rel_error": max(errs)}
            out["control_factor_rel_error"] = lo["factor_rel_error"]
        w.kept = []
        cc.free()
        out.update(serve_s=t1 - t0, check_s=time.perf_counter() - t1)
        yield out, got, lo


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = p.parse_args(argv)
    cell = registry.resolve(ROOT, args.workload)
    setup_jax(ROOT, cell.chips)
    seeds = [int(s) for s in args.seeds.split(",")]
    limits = json.loads((ROOT / registry.BENCH_DIR / "limits"
                         / f"{cell.name}.json").read_text())
    reader = serving if cell.kind == "lm_serve" else factorization
    for row, got, lo in reader(cell, seeds, args.seconds, bool(args.control)):
        if got is not None:
            row["correct"] = verdict(compare(got, limits))
        if lo is not None:
            row["control_correct"] = verdict(compare(lo, limits))
        print(json.dumps({"cell": cell.name, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
