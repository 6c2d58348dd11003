"""Tiled-matrix utilities and the analytical cost model for the SLATE-style
factorization task graphs."""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Key = Tuple[int, int]


class TileStore:
    """Shared tile storage mutated by task bodies.  Task-graph dependencies
    guarantee exclusive access ordering; dict item assignment is atomic."""

    def __init__(self, tiles: Dict[Key, jnp.ndarray], nb: int, b: int):
        self.tiles = tiles
        self.nb = nb
        self.b = b

    def __getitem__(self, k: Key) -> jnp.ndarray:
        return self.tiles[k]

    def __setitem__(self, k: Key, v: jnp.ndarray) -> None:
        self.tiles[k] = v

    def assemble(self) -> jnp.ndarray:
        rows = []
        for i in range(self.nb):
            rows.append(jnp.concatenate([self.tiles[(i, j)] for j in range(self.nb)], axis=1))
        return jnp.concatenate(rows, axis=0)


class ShapeOnlyStore:
    """Stand-in for a :class:`TileStore` carrying only ``(nb, b)``.  Task
    bodies never run against it — it exists so the *numeric* variant of a
    factorization graph can be built purely for its structural
    :func:`~repro.replay.graph_key` (numeric and cost-model builds differ
    structurally)."""

    def __init__(self, nb: int, b: int):
        self.nb = nb
        self.b = b


@functools.partial(jax.jit, static_argnames="b")
def _tile_row(a: jnp.ndarray, i: int, b: int) -> Tuple[jnp.ndarray, ...]:
    """The ``a.shape[1] // b`` tiles of tile row ``i`` of ``a``."""
    row = jax.lax.dynamic_slice_in_dim(a, i * b, b, axis=0)
    return tuple(jax.lax.split(row, [b] * (a.shape[1] // b), axis=1))


def to_tiles(a: jnp.ndarray, b: int) -> TileStore:
    """Split the square matrix ``a`` into a :class:`TileStore` of ``b × b``
    tiles, keyed ``(i, j)`` by tile row and column.

    Every tile is a device buffer of its own, holding exactly the values of
    ``a[i*b:(i+1)*b, j*b:(j+1)*b]``.  A NumPy ``a`` is moved to the device
    once.  The split runs one device program per tile row, ``nb = n // b``
    dispatches in all rather than one per tile: at nb = 40 the host cost of
    nb² dispatches is a large part of a factorization.  The row program is
    one compiled program for every row (the row index is an argument), of
    nb outputs.  A single program with all nb² tiles as outputs would
    dispatch once, but its compile time grows with nb²: seconds at nb = 40.

    Raises ``ValueError`` unless ``a`` is square with order divisible by
    ``b``.
    """
    n = a.shape[0]
    if a.shape[0] != a.shape[1] or n % b != 0:
        raise ValueError(f"need square matrix with dim divisible by {b}, got {a.shape}")
    nb = n // b
    a = jnp.asarray(a)
    tiles = {(i, j): t for i in range(nb) for j, t in enumerate(_tile_row(a, i, b=b))}
    return TileStore(tiles, nb, b)


@dataclasses.dataclass
class CostModel:
    """Analytical per-task costs for the simulator / static scheduler.

    Defaults approximate one Skylake core (paper's testbed: 2x20C Skylake)
    and EDR InfiniBand: the absolute scale is irrelevant for the relative
    policy comparisons; the compute/comm *ratio* is what matters.
    """

    flop_rate: float = 40e9        # effective flops/s per worker (DGEMM-ish)
    panel_flop_rate: float = 12e9  # panel kernels are bandwidth/latency bound
    comm_bw: float = 10e9          # bytes/s inter-rank link
    comm_latency: float = 15e-6    # per-message latency
    dtype_bytes: int = 8

    def gemm(self, b: int) -> float:
        return 2.0 * b ** 3 / self.flop_rate

    def syrk(self, b: int) -> float:
        return 1.0 * b ** 3 / self.flop_rate

    def trsm(self, b: int) -> float:
        return 1.0 * b ** 3 / self.flop_rate

    def potrf(self, b: int) -> float:
        return (b ** 3 / 3.0) / self.panel_flop_rate

    def panel_lu(self, m_tiles: int, b: int) -> float:
        # left-looking panel on m_tiles*b x b block column
        return (m_tiles * b * b * b) / self.panel_flop_rate

    def panel_qr(self, m_tiles: int, b: int) -> float:
        return (2.0 * m_tiles * b * b * b) / self.panel_flop_rate

    def tile_bytes(self, b: int) -> int:
        return b * b * self.dtype_bytes

    def bcast(self, n_tiles: int, b: int, ranks: int = 4) -> float:
        # pipelined broadcast of a factored block column to the other ranks
        return self.comm_latency * max(1, ranks - 1) + \
            n_tiles * self.tile_bytes(b) / self.comm_bw


# ---------------------------------------------------------------------------
# jitted tile kernels
# ---------------------------------------------------------------------------
#: Precision of the tile kernels' matrix products.  On a TPU a float32
#: product otherwise runs as one bfloat16 pass, which leaves a factorization
#: about 1e-3 away from its input instead of at float32 rounding.
TILE_PRECISION = "highest"


@jax.jit
def tile_potrf(a: jnp.ndarray) -> jnp.ndarray:
    return jnp.linalg.cholesky(a)


@jax.jit
def tile_trsm_right_lower_t(a: jnp.ndarray, l: jnp.ndarray) -> jnp.ndarray:
    """Solve X L^T = A for X (the Cholesky column update)."""
    # X = A L^{-T}  =>  X^T = L^{-1} A^T
    return jax.scipy.linalg.solve_triangular(l, a.T, lower=True).T


@jax.jit
def tile_gemm_sub(c: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C - A @ B^T (trailing update)."""
    return c - jnp.matmul(a, b.T, precision=TILE_PRECISION)


@jax.jit
def tile_gemm_nn_sub(c: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """C - A @ B."""
    return c - jnp.matmul(a, b, precision=TILE_PRECISION)


@jax.jit
def tile_trsm_left_lower_unit(l: jnp.ndarray, a: jnp.ndarray) -> jnp.ndarray:
    """Solve L X = A with unit-diagonal lower L (LU row update)."""
    return jax.scipy.linalg.solve_triangular(l, a, lower=True, unit_diagonal=True)
