"""``bench/control.py`` for Qwen3-Next serving cells: read the check's
numbers over many seeds in one process, with the float8 control beside
them, and judge both by the cell's limits.

    python bench/control_qwen3_next.py --workload <cell> --seeds 1,2,3 --seconds <s> [--control 1]

One JSON line per seed, as ``bench/control.py`` prints for a serving cell,
with the run's mean routing counters besides.  The limits are set from
these readings (see ``PERF.md``); the benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry  # noqa: E402
from bench.harness import compare, verdict  # noqa: E402
from bench.run import setup_jax  # noqa: E402


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True, help="comma-separated")
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--control", type=int, choices=(0, 1), default=1)
    args = p.parse_args(argv)
    cell = registry.resolve(ROOT, args.workload)
    setup_jax(ROOT, cell.chips)
    limits = json.loads((ROOT / registry.BENCH_DIR / "limits"
                         / f"{cell.name}.json").read_text())
    seeds = [int(s) for s in args.seeds.split(",")]
    rows = registry.driver(cell).control_rows(cell, seeds, args.seconds, bool(args.control))
    for row, got, lo in rows:
        if got is not None:
            row["correct"] = verdict(compare(got, limits))
        if lo is not None:
            row["control_correct"] = verdict(compare(lo, limits))
        print(json.dumps({"cell": cell.name, **row}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
