"""Numeric validation of the tiled factorizations executed through the
gang-scheduling/work-stealing runtime, under every victim policy.

Schedule independence — the factorization result must not depend on the
scheduling policy — is the core correctness invariant of the scheduler.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

jax.config.update("jax_enable_x64", True)

import repro
from repro.core import run_graph
from repro.linalg import (
    build_cholesky_graph,
    build_lu_graph,
    build_qr_graph,
    cholesky_extract,
    lu_extract,
    qr_extract_r,
    qr_reconstruct,
    random_diagdom,
    random_spd,
    TileStore,
    to_tiles,
)
from repro.linalg.panels import lu_panel_region, qr_form_t, qr_panel_region


class _SerialRegion:
    def barrier(self):
        pass


def _tiles_by_slice(a, b):
    """The store as one slice per tile: what ``to_tiles`` must equal."""
    nb = a.shape[0] // b
    return TileStore({(i, j): jnp.asarray(a[i * b:(i + 1) * b, j * b:(j + 1) * b])
                      for i in range(nb) for j in range(nb)}, nb, b)


# ---------------------------------------------------------------------------
# the tile store
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("shape,b,dtype,host", [
    ((4, 4), 4, np.float32, False),
    ((15, 15), 5, np.float32, False),
    ((12, 12), 4, np.float64, False),
    ((80, 80), 2, np.float32, False),
    ((120, 120), 3, np.float64, False),
    ((12, 12), 4, np.float64, True),
    ((12, 12), 4, np.float32, True),
    ((12, 8), 4, np.float32, False),
    ((12, 12), 5, np.float32, False),
    ((12, 12), 5, np.float64, True),
])
def test_to_tiles_matches_per_tile_slices(shape, b, dtype, host):
    a = np.random.default_rng(sum(shape) + b).standard_normal(shape).astype(dtype)
    if not host:
        a = jnp.asarray(a)
    if shape[0] != shape[1] or shape[0] % b:
        with pytest.raises(ValueError, match="square matrix"):
            to_tiles(a, b)
        return
    store = to_tiles(a, b)
    ref = _tiles_by_slice(a, b)
    assert (store.nb, store.b) == (shape[0] // b, b)
    assert store.tiles.keys() == ref.tiles.keys()
    for k, t in store.tiles.items():
        assert isinstance(t, jax.Array)
        assert t.dtype == ref[k].dtype == dtype and t.shape == (b, b)
        assert np.asarray(t).tobytes() == np.asarray(ref[k]).tobytes(), k
    # one device buffer per tile
    ptrs = {t.unsafe_buffer_pointer() for t in store.tiles.values()}
    assert len(ptrs) == store.nb ** 2


def _factor(kind, store):
    if kind == "cholesky":
        g = build_cholesky_graph(store.nb, store.b, store=store)
    elif kind == "lu":
        g = build_lu_graph(store.nb, store.b, store=store, panel_threads=2)
    else:
        g = build_qr_graph(store.nb, store.b, store=store, panel_threads=2)
    with repro.Session(3) as s:
        s.run(g, timeout=120.0)
    if kind == "cholesky":
        return [cholesky_extract(store)]
    if kind == "lu":
        return list(lu_extract(store))
    return [qr_extract_r(store), qr_reconstruct(store)]


@pytest.mark.parametrize("kind", ["cholesky", "lu", "qr"])
def test_factors_from_to_tiles_match_per_tile_store(kind):
    """A factorization of ``to_tiles``'s store equals, bit for bit, the
    same factorization of a store built one slice per tile."""
    n, b = 96, 24
    if kind == "cholesky":
        a = random_spd(n, seed=7)
    elif kind == "lu":
        a = random_diagdom(n, seed=7)
    else:
        a = jnp.asarray(np.random.default_rng(7).standard_normal((n, n)))
    got = _factor(kind, to_tiles(a, b))
    want = _factor(kind, _tiles_by_slice(a, b))
    for x, y in zip(got, want):
        assert np.asarray(x).tobytes() == np.asarray(y).tobytes()


# ---------------------------------------------------------------------------
# panel kernels in isolation (serial region)
# ---------------------------------------------------------------------------
def test_lu_panel_matches_reference():
    rng = np.random.default_rng(0)
    p = rng.standard_normal((96, 16))
    p[:16] += np.diag(np.abs(p).sum(axis=0) + 1.0)[:16, :16] @ np.eye(16)
    ref = p.copy()
    body = lu_panel_region(p, 16, 1)
    body(0, _SerialRegion())
    l = np.tril(p[:16], -1) + np.eye(16)
    u = np.triu(p[:16])
    l_full = np.vstack([l, p[16:]])
    np.testing.assert_allclose(l_full @ u, ref, rtol=1e-10, atol=1e-10)


def test_qr_panel_matches_reference():
    rng = np.random.default_rng(1)
    p = rng.standard_normal((64, 16))
    ref = p.copy()
    body, taus = qr_panel_region(p, 16, 1)
    body(0, _SerialRegion())
    r = np.triu(p[:16])
    # reconstruct via compact WY
    T = qr_form_t(p, taus)
    V = np.tril(p, -1)[:, :16] + np.eye(64, 16)
    a = np.vstack([r, np.zeros((48, 16))])
    a = a - V @ (T @ (V.T @ a))
    np.testing.assert_allclose(a, ref, rtol=1e-9, atol=1e-9)
    # R has the right magnitude structure
    np.testing.assert_allclose(np.abs(np.linalg.svd(r, compute_uv=False)),
                               np.linalg.svd(ref, compute_uv=False), rtol=1e-9)


# ---------------------------------------------------------------------------
# full factorizations through the runtime
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("policy", ["history", "random", "hybrid"])
def test_cholesky_numeric_all_policies(policy):
    n, b = 192, 48
    a = random_spd(n, seed=2)
    store = to_tiles(a, b)
    g = build_cholesky_graph(store.nb, b, store=store)
    run_graph(g, 4, policy=policy, seed=0, timeout=120.0)
    l = cholesky_extract(store)
    np.testing.assert_allclose(np.asarray(l @ l.T), np.asarray(a), rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("policy", ["history", "hybrid"])
def test_lu_numeric_gang_panels(policy):
    n, b = 128, 32
    a = random_diagdom(n, seed=3)
    store = to_tiles(a, b)
    g = build_lu_graph(store.nb, b, store=store, panel_threads=3)
    run_graph(g, 4, policy=policy, seed=0, timeout=120.0)
    l, u = lu_extract(store)
    np.testing.assert_allclose(np.asarray(l @ u), np.asarray(a), rtol=1e-8, atol=1e-8)


@pytest.mark.parametrize("policy", ["history", "hybrid"])
def test_qr_numeric_gang_panels(policy):
    n, b = 128, 32
    rng = np.random.default_rng(4)
    a = jnp.asarray(rng.standard_normal((n, n)))
    store = to_tiles(a, b)
    g = build_qr_graph(store.nb, b, store=store, panel_threads=3)
    run_graph(g, 4, policy=policy, seed=0, timeout=120.0)
    r = qr_extract_r(store)
    # R upper triangular by construction; reconstruction must give A back
    recon = qr_reconstruct(store)
    np.testing.assert_allclose(np.asarray(recon), np.asarray(a), rtol=1e-8, atol=1e-8)
    # orthogonal invariance of singular values
    np.testing.assert_allclose(
        np.linalg.svd(np.asarray(r), compute_uv=False),
        np.linalg.svd(np.asarray(a), compute_uv=False), rtol=1e-8)


def test_schedule_independence_cholesky():
    """The same input must factor to the same L under different policies,
    seeds and worker counts."""
    n, b = 96, 32
    a = random_spd(n, seed=5)
    results = []
    for policy, workers, seed in [("history", 2, 0), ("hybrid", 4, 1), ("random", 3, 2)]:
        store = to_tiles(a, b)
        g = build_cholesky_graph(store.nb, b, store=store)
        run_graph(g, workers, policy=policy, seed=seed, timeout=120.0)
        results.append(np.asarray(cholesky_extract(store)))
    for r in results[1:]:
        np.testing.assert_allclose(r, results[0], rtol=1e-12, atol=1e-12)


def test_lu_graph_cost_mode_structure():
    g = build_lu_graph(6, 64, store=None)
    kinds = g.subgraph_kinds()
    assert kinds["panel"] == 6
    assert kinds["comm"] == 6
    # lookahead column per step except the last
    assert kinds["lookahead"] == 5
    # panels carry nested-parallel specs for the simulator
    panels = [t for t in g if t.kind == "panel"]
    assert all(t.parallel is not None for t in panels)
    length, path = g.critical_path()
    assert length > 0
