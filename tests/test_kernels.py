"""Pallas kernel validation: interpret-mode execution on CPU swept over
shapes/dtypes against the pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.ssd_scan import ssd_scan_ref


def rnd(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,d", [(1, 2, 256, 64), (2, 1, 512, 128)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 128), (False, 0)])
def test_flash_attention_matches_ref(B, H, S, d, dtype, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = rnd(ks[0], (B, H, S, d), dtype)
    k = rnd(ks[1], (B, H, S, d), dtype)
    v = rnd(ks[2], (B, H, S, d), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              mode="interpret", bq=128, bk=128)
    expect = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,S,d,length", [(2, 2, 1024, 64, 700),
                                            (1, 4, 2048, 128, 2048)])
def test_decode_attention_matches_ref(B, H, S, d, length, dtype):
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = rnd(ks[0], (B, H, d), dtype)
    k = rnd(ks[1], (B, S, H, d), dtype)
    v = rnd(ks[2], (B, S, H, d), dtype)
    out = ops.decode_attention(q, k, v, length, mode="interpret", bk=256)
    expect = ref.decode_attention_ref(q, k, v, length)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# tile matmul
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("M,K,N,bm,bn,bk", [
    (256, 256, 256, 128, 128, 128),
    (512, 256, 128, 256, 128, 256),
])
def test_tile_matmul_matches_ref(M, K, N, bm, bn, bk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    a = rnd(ks[0], (M, K), dtype)
    b = rnd(ks[1], (K, N), dtype)
    out = ops.tile_matmul(a, b, mode="interpret", bm=bm, bn=bn, bk=bk)
    expect = ref.tile_matmul_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(expect, np.float32), **TOL[dtype])


# ---------------------------------------------------------------------------
# SSD chunk scan
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", [jnp.float32])
@pytest.mark.parametrize("B,nc,L,H,N,P", [(1, 3, 32, 4, 16, 32),
                                          (2, 2, 64, 2, 32, 64)])
def test_ssd_scan_matches_ref(B, nc, L, H, N, P, dtype):
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xdt = rnd(ks[0], (B, nc, L, H, P), dtype) * 0.2
    # negative cumulative log-decay (monotone decreasing within chunk)
    la = -jnp.abs(rnd(ks[1], (B, nc, L, H), jnp.float32)) * 0.05
    cs = jnp.cumsum(la, axis=2)
    Bm = rnd(ks[2], (B, nc, L, N), dtype) * 0.3
    Cm = rnd(ks[3], (B, nc, L, N), dtype) * 0.3
    y, s = ops.ssd_scan(xdt, cs, Bm, Cm, mode="interpret")
    y_ref, s_ref = ssd_scan_ref(xdt, cs, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# the ssd oracle itself vs the model's chunked implementation
# ---------------------------------------------------------------------------
def test_ssd_ref_consistent_with_model_ssd():
    """kernels.ref and models.ssm implement the same recurrence."""
    from repro.models.ssm import ssd_chunked
    B, T, H, P, N = 1, 96, 2, 16, 8
    L = 32
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    xs = rnd(ks[0], (B, T, H, P), jnp.float32) * 0.3
    dt = jnp.abs(rnd(ks[1], (B, T, H), jnp.float32)) * 0.1 + 0.01
    a = -jnp.abs(rnd(ks[2], (H,), jnp.float32)) - 0.1
    Bm = rnd(ks[3], (B, T, N), jnp.float32) * 0.3
    Cm = rnd(ks[0], (B, T, N), jnp.float32) * 0.3

    y_model, s_model = ssd_chunked(xs, dt, a, Bm, Cm, chunk=L)

    # rebuild the kernel layout
    nc = T // L
    la = (dt * a).reshape(B, nc, L, H)
    cs = jnp.cumsum(la, axis=2)
    xdt = (xs * dt[..., None]).reshape(B, nc, L, H, P)
    y_k, s_k = ssd_scan_ref(xdt, cs, Bm.reshape(B, nc, L, N),
                            Cm.reshape(B, nc, L, N))
    np.testing.assert_allclose(np.asarray(y_model),
                               np.asarray(y_k.reshape(B, T, H, P)),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s_model), np.asarray(s_k),
                               rtol=1e-4, atol=1e-4)


def test_kernel_mode_must_be_named():
    """No mode is chosen from the device: an unnamed or unknown mode is an
    error, never a silent switch to the reference."""
    a = jnp.ones((8, 8), jnp.float32)
    with pytest.raises(TypeError):
        ops.tile_matmul(a, a)
    with pytest.raises(ValueError, match="unknown kernel mode"):
        ops.tile_matmul(a, a, mode="auto")
