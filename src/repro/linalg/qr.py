"""Tiled Householder QR as a SLATE-style task graph with gang-scheduled
panel regions (communication-avoiding flavor: per-column reductions are the
only panel synchronization; no pivoting — paper §5.2: "the panel
factorization is the most critical task to the task graph of QR").

Structure per step ``k``: like LU — gang-scheduled ``panel[k]`` (4 blocking
barriers per column), ``bcast[k]`` shipping {V, T}, a lookahead column task
and a trailing parent/children/join family applying
``A_j <- (I - V T V^T)^T A_j``.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..api.graph import Graph
from ..compile.fuse import FuseSpec
from ..core.taskgraph import ParallelSpec, TaskGraph
from .cholesky import SPAWN_COST
from .panels import qr_form_t, qr_panel_region
from .tiles import CostModel, ShapeOnlyStore, TileStore


class _QrFuseState:
    """Fuse-state adapter: tile keys ``(i, j)`` resolve to the tile store,
    ``("vt", k)`` to the panel-reflector side store."""

    __slots__ = ("store",)

    def __init__(self, store: TileStore):
        self.store = store

    def __getitem__(self, k):
        if k[0] == "vt":
            return self.store.vt_store[k[1]]
        return self.store[k]

    def __setitem__(self, k, v):
        if k[0] == "vt":
            self.store.vt_store[k[1]] = v
        else:
            self.store[k] = v


@jax.jit
def _qr_col_fused(vt, *tiles):
    """Fused trailing-column update ``A_j <- (I - V T V^T)^T A_j`` over the
    stacked tiles of block column ``j``.  One program, launched by the
    column task's body; module-level so that compiled plans and batched
    launches cache their programs on it."""
    V, T = vt
    b = tiles[0].shape[0]
    a = jnp.concatenate(tiles, axis=0)
    a = a - V @ (T.T @ (V.T @ a))
    if len(tiles) == 1:
        return a
    return tuple(a[i * b:(i + 1) * b] for i in range(len(tiles)))


def build_qr_graph(
    nb: int,
    b: int = 64,
    *,
    store: Optional[TileStore] = None,
    cost: Optional[CostModel] = None,
    ranks: int = 4,
    panel_threads: int = 4,
    gang_panels: Optional[bool] = None,
    comm: bool = True,
) -> TaskGraph:
    cm = cost or CostModel()
    g = Graph(f"qr[{nb}x{nb},b={b}]")
    numeric = store is not None
    noop = (lambda ctx: None) if numeric else None
    # side store for the panel reflectors: k -> (V, T) with V (m x b)
    vt_store: Dict[int, tuple] = {}
    if store is not None:
        store.vt_store = vt_store  # exposed for validation

    def panel_body_factory(k: int, n_threads: int):
        def fn(ctx):
            panel = np.concatenate(
                [np.asarray(store[(i, k)]) for i in range(k, store.nb)], axis=0)
            body, taus = qr_panel_region(panel, store.b, n_threads)
            ctx.parallel(n_threads, body, gang=gang_panels)
            T = qr_form_t(panel, taus)
            V = np.tril(panel, -1)[:, :store.b] + np.eye(panel.shape[0], store.b)
            vt_store[k] = (jnp.asarray(V), jnp.asarray(T))
            # write back: R on/above the diagonal of the top tile, zeros below
            store[(k, k)] = jnp.asarray(np.triu(panel[:store.b]))
            for i in range(k + 1, store.nb):
                store[(i, k)] = jnp.zeros_like(store[(i, k)])
        return fn

    if numeric:
        g.fuse_state = _QrFuseState(store)

    def col_body(j: int, k: int):
        def fn(ctx):
            rows = range(k, store.nb)
            out = _qr_col_fused(vt_store[k], *[store[(i, j)] for i in rows])
            for i, tile in zip(rows, out if len(rows) > 1 else (out,)):
                store[(i, j)] = tile
        return fn if numeric else None

    def col_fuse(j: int, k: int):
        if not numeric:
            return None
        keys = [(i, j) for i in range(k, nb)]
        return FuseSpec(_qr_col_fused, (("vt", k),) + tuple(keys), tuple(keys))

    def col_cost(k: int) -> float:
        return 4.0 * (nb - k) * b ** 3 / cm.flop_rate

    join_look = None
    join_trail = None

    for k in range(nb):
        m_tiles = nb - k
        n_threads = max(1, min(panel_threads, m_tiles))
        pdeps = [join_look] if join_look is not None else []
        if numeric:
            p = g.add(panel_body_factory(k, n_threads), name=f"panel[{k}]",
                      kind="panel", cost=cm.panel_qr(m_tiles, b), priority=3,
                      deps=pdeps, step=k)
        else:
            p = g.add(None, name=f"panel[{k}]", kind="panel",
                      cost=0.05 * cm.panel_qr(m_tiles, b), priority=3, deps=pdeps,
                      parallel=ParallelSpec(
                          n_threads=n_threads,
                          cost_per_thread=cm.panel_qr(m_tiles, b) / n_threads,
                          n_barriers=4 * b, blocking=True),
                      step=k)

        col_dep = p
        if comm:
            col_dep = g.add(noop, name=f"bcast[{k}]", kind="comm",
                            cost=cm.bcast(m_tiles + 1, b, ranks), priority=3,
                            deps=[p], step=k)
        base_deps = [col_dep] + ([join_trail] if join_trail is not None else [])

        if k + 1 < nb:
            join_look = g.add(col_body(k + 1, k), name=f"col[{k + 1},{k}]",
                              kind="lookahead", cost=col_cost(k), priority=2,
                              deps=base_deps, step=k, fuse=col_fuse(k + 1, k))
        else:
            join_look = None

        if k + 2 < nb:
            tparent = g.add(noop, name=f"trail*[{k}]", kind="compute",
                            cost=SPAWN_COST * (nb - k - 2), priority=0,
                            deps=base_deps, step=k)
            tchildren = [
                g.add(col_body(j, k), name=f"col[{j},{k}]", kind="compute",
                      cost=col_cost(k), priority=0, deps=[tparent], step=k,
                      fuse=col_fuse(j, k))
                for j in range(k + 2, nb)
            ]
            join_trail = g.add(noop, name=f"trail.join[{k}]", kind="compute",
                               cost=0.0, priority=0, deps=tchildren, step=k)
        else:
            join_trail = None
    return g


def qr_graph_key(
    nb: int,
    b: int = 64,
    *,
    cost: Optional[CostModel] = None,
    ranks: int = 4,
    panel_threads: int = 4,
    comm: bool = True,
):
    """Structural replay-cache key for :func:`build_qr_graph` (cost-model
    shape; see the note on :func:`repro.linalg.lu.lu_graph_key` about
    numeric-vs-cost-model panel structure)."""
    from ..replay import graph_key
    return graph_key(build_qr_graph(nb, b, cost=cost, ranks=ranks,
                                    panel_threads=panel_threads, comm=comm))


def qr_static_recording(
    nb: int,
    b: int = 64,
    *,
    n_workers: int,
    cost: Optional[CostModel] = None,
    ranks: int = 4,
    panel_threads: int = 4,
    comm: bool = True,
    policy: str = "hybrid",
    seed: int = 0,
):
    """QR analogue of :func:`repro.linalg.lu.lu_static_recording`: simulate
    the cost-model twin, carry its gang reservations into the recording as
    placements, key it to the numeric build's digest."""
    from ..core.static_schedule import ListScheduler
    from ..replay.graph_key import graph_key
    from ..replay.recording import Recording

    kwargs = dict(cost=cost, ranks=ranks, panel_threads=panel_threads,
                  comm=comm)
    twin = build_qr_graph(nb, b, **kwargs)
    sched = ListScheduler(n_workers, policy=policy, seed=seed).schedule(twin)
    numeric_key = graph_key(
        build_qr_graph(nb, b, store=ShapeOnlyStore(nb, b), **kwargs))
    return Recording.from_static_schedule(sched, twin, key=numeric_key)


def qr_extract_r(store: TileStore) -> jnp.ndarray:
    return jnp.triu(store.assemble())


def qr_reconstruct(store: TileStore) -> jnp.ndarray:
    """Apply the stored panel transforms to R to reconstruct A = Q R:
    A = H_0 H_1 ... H_{nb-1} R with H_k = I - V_k T_k V_k^T acting on the
    trailing rows."""
    n = store.nb * store.b
    a = np.array(qr_extract_r(store))  # writable copy
    for k in reversed(range(store.nb)):
        V, T = (np.asarray(x) for x in store.vt_store[k])
        rows = slice(k * store.b, n)
        blk = a[rows]
        a[rows] = blk - V @ (T @ (V.T @ blk))
    return jnp.asarray(a)
