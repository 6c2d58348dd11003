"""Sweep the arrival rate of an open-loop serving cell, to find once the
highest rate the system sustains (its knee).

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> --rates 2,3,4

One process sets the cell up once, then serves a window at each rate and
prints one JSON line per rate: the cell's end-to-end numbers, and whether
the backlog grew.  The backlog grew when requests due in the last third
of the window waited for admission (due time to prefill) more than twice
as long at the median as those due in the first third, and by more than
half a second.  The cell's traffic file then fixes its rate as a number;
the benchmark's own runs never sweep.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench.run import setup_jax  # noqa: E402


def backlog_grew(window) -> dict:
    w = window
    due = sorted((r for r in w.requests if w.t_open <= r.due_s < w.t_close),
                 key=lambda r: r.due_s)
    third = w.seconds / 3
    end = max(s.t1 for s in w.steps)

    def waits(lo, hi):
        return [((r.admitted_s if r.admitted_s is not None else end) - r.due_s)
                for r in due if w.t_open + lo <= r.due_s < w.t_open + hi]

    first, last = waits(0, third), waits(2 * third, 3 * third)
    if not first or not last:
        return {"grew": None}
    a, b = statistics.median(first), statistics.median(last)
    return {"wait_first_third_s": a, "wait_last_third_s": b,
            "grew": b > 2 * a and b - a > 0.5}


def main(argv=None) -> int:
    from bench.drivers.lm_serve import ServingCell, end_to_end

    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--rates", required=True, help="comma-separated requests/s")
    args = p.parse_args(argv)
    cell = registry.resolve(ROOT, args.workload)
    setup_jax(ROOT, cell.chips)
    sc = ServingCell(cell)
    sc.compile()
    sc.load(args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        w = sc.serve(args.seed, args.seconds, rate=rate)
        due = [r for r in w.requests if w.t_open <= r.due_s < w.t_close]
        print(json.dumps({"cell": cell.name, "rate_per_s": rate, "due": len(due),
                          "no_first_token": sum(r.first_s is None for r in due),
                          **end_to_end(w), **backlog_grew(w)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
