"""Seeded SPD matrices, the float64 reference Cholesky factor, and a plain
tiled Cholesky for the control.  Nothing here imports the program.

The matrix of a seed is ``A0 = G G^T / n + I`` with ``G`` uniform of unit
variance, made on the device in float32; its eigenvalues lie in about
``[1, 5]``.  Factorization ``j`` of a run factors ``A0 + s_j I``, with the
shift ``s_j`` drawn from the seed, so every factorization has its own
answer.  The reference factors the same float32 matrix in float64 with
LAPACK (``numpy.linalg.cholesky``).

:func:`tiled_cholesky` is the right-looking tiled algorithm in its plainest
form, one jitted kernel per tile operation, with the trailing products at a
chosen matrix-multiply precision.  At ``precision="high"`` (three bfloat16
products, :func:`dot_bf16x3`) it is the control: the step below float32 at
``highest``.  The diagonal factorizations and triangular solves are the
same calls as at any precision.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnums=1)
def make_spd(key: jax.Array, n: int) -> jax.Array:
    g = jax.random.uniform(key, (n, n), jnp.float32, -math.sqrt(3.0), math.sqrt(3.0))
    a = jnp.matmul(g, g.T, precision="highest") / n
    return a + jnp.eye(n, dtype=jnp.float32)


@jax.jit
def shifted(a0: jax.Array, shift: jax.Array) -> jax.Array:
    return a0 + shift * jnp.eye(a0.shape[0], dtype=a0.dtype)


def reference_factor(a: np.ndarray) -> np.ndarray:
    """The lower Cholesky factor of ``a`` in float64."""
    return np.linalg.cholesky(np.asarray(a, np.float64))


def relative_error(l: np.ndarray, l_ref: np.ndarray) -> float:
    """``||L - L_ref||_F / ||L_ref||_F`` in float64; not finite when ``l``
    holds a non-finite entry."""
    l = np.asarray(l, np.float64)
    return float(np.linalg.norm(l - l_ref) / np.linalg.norm(l_ref))


def dot_bf16x3(a: jax.Array, b: jax.Array) -> jax.Array:
    """``a @ b`` of float32 matrices from three bfloat16 products (each
    operand split into a bfloat16 head and tail; the tail times tail is
    dropped), accumulated in float32: what ``precision="high"`` computes
    on a TPU, written out so that it computes the same on any backend."""
    def split(x):
        hi = x.astype(jnp.bfloat16)
        return hi, (x - hi.astype(jnp.float32)).astype(jnp.bfloat16)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    return dot(a_hi, b_hi) + (dot(a_hi, b_lo) + dot(a_lo, b_hi))


@functools.lru_cache(maxsize=None)
def _kernels(precision: str):
    @jax.jit
    def potrf(a):
        return jnp.linalg.cholesky(a)

    @jax.jit
    def trsm(a, l):
        return jax.scipy.linalg.solve_triangular(l, a.T, lower=True).T

    @jax.jit
    def update(c, a, b):
        if precision == "high":
            return c - dot_bf16x3(a, b.T)
        return c - jnp.matmul(a, b.T, precision=precision)

    return potrf, trsm, update


def tiled_cholesky(a: jax.Array, b: int, precision: str) -> jax.Array:
    """The lower factor of ``a`` by the tiled right-looking algorithm."""
    n = a.shape[0]
    nb = n // b
    potrf, trsm, update = _kernels(precision)
    t = {(i, j): a[i * b:(i + 1) * b, j * b:(j + 1) * b]
         for i in range(nb) for j in range(i + 1)}
    for k in range(nb):
        t[k, k] = potrf(t[k, k])
        for i in range(k + 1, nb):
            t[i, k] = trsm(t[i, k], t[k, k])
        for j in range(k + 1, nb):
            for i in range(j, nb):
                t[i, j] = update(t[i, j], t[i, k], t[j, k])
    zero = jnp.zeros((b, b), a.dtype)
    rows = [jnp.concatenate([t[i, j] if j <= i else zero for j in range(nb)], axis=1)
            for i in range(nb)]
    return jnp.tril(jnp.concatenate(rows, axis=0))
