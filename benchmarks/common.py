"""Shared benchmark plumbing: paper-parity configurations and CSV output."""

from __future__ import annotations

from typing import Dict, List

from repro.core import Simulator
from repro.device import describe_device, enable_compile_cache
from repro.linalg.dist import build_dist_cholesky_graph, build_dist_panel_graph
from repro.linalg.tiles import CostModel

# Paper testbed (Table 1): dual-socket 20C Skylake per node.
# LU/QR: 4 ranks x 10 threads; Cholesky: 2 ranks x 20 threads (paper §5).
LU_QR_CONFIG = dict(ranks=4, workers=40)
CHOL_CONFIG = dict(ranks=2, workers=40)
CHOL_MULTI = dict(ranks=4, workers=40)      # 4-rank (multi-node analogue)

# matrix sizes (tiles of b=192): "small" ~ 7.7k, "large" ~ 12.3k, "xl" ~ 18.4k
SIZES = {"small": 40, "large": 64, "xl": 96}
B = 192

COST = CostModel(comm_bw=3e9, comm_latency=20e-6)


def build(kernel: str, nb: int, ranks: int) -> object:
    if kernel == "cholesky":
        return build_dist_cholesky_graph(nb, B, ranks=ranks, cost=COST)
    return build_dist_panel_graph(kernel, nb, B, ranks=ranks, cost=COST)


def run(graph, workers: int, ranks: int, *, policy="hybrid", mode="gang",
        seed=0):
    sim = Simulator(workers, ranks=ranks, policy=policy, mode=mode, seed=seed)
    return sim.run(graph)


def start(name: str) -> Dict:
    """Entry-point set-up shared by the benchmarks: place the compile cache
    and name the device every printed result comes from."""
    enable_compile_cache()
    dev = describe_device()
    print(f"# {name} on {dev}")
    return dev.as_dict()


def emit(rows: List[Dict], header: bool = True) -> None:
    if header and rows:
        print(",".join(rows[0].keys()))
    for r in rows:
        print(",".join(str(v) for v in r.values()))
