"""Gated DeltaNet: the linear-attention mixer of Qwen3-Next.

Per token ``t`` and value head ``j`` (key head ``j // (Hv / Hk)``):

    [q, k, v, z] = x W_qkvz;   [b, a] = x W_ba
    [q, k, v] <- silu(causal depthwise conv1d([q, k, v]))
    q, k <- q / |q|, k / |k|;   q <- q / sqrt(dk)
    beta = sigmoid(b);   g = -exp(A_log) * softplus(a + dt_bias)
    S' = exp(g) S;   S = S' + k (beta (v - S'^T k))^T;   o = S^T q
    out = (rmsnorm(o) * w * silu(z)) W_out

with the recurrent state ``S`` (dk x dv per value head) in float32.  A
prefill runs the chunked (WY) form, as ``torch_chunk_gated_delta_rule`` in
Hugging Face ``transformers`` computes it: within a chunk of :data:`CHUNK`
tokens every product is a matrix product, and only the state passes from
chunk to chunk.  A decode step runs the recurrence once on the state in
the cache.  The state of a sequence is ``conv`` (the last ``conv_width - 1``
inputs of the convolution, in the model's dtype) and ``state`` (float32).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.scipy.linalg import solve_triangular

_HIGHEST = lax.Precision.HIGHEST
_EPS = 1e-6
#: tokens per chunk of the prefill's chunked form (as ``transformers``)
CHUNK = 64


def gdn_spec(cfg) -> Dict:
    d, hk, hv, dh = cfg.d_model, cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_head_dim
    return {
        "w_qkvz": ((d, (2 * hk + 2 * hv) * dh), ("embed", "heads")),
        "w_ba": ((d, 2 * hv), ("embed", None)),
        "conv": ((cfg.conv_width, cfg.lin_conv_dim), (None, "heads")),
        "A_log": ((hv,), (None,)),
        "dt_bias": ((hv,), (None,)),
        "norm": ((dh,), (None,)),
        "w_out": ((hv * dh, d), ("heads", "embed")),
    }


def state_struct(cfg, batch: int) -> Dict[str, jax.ShapeDtypeStruct]:
    """One layer's recurrent state for ``batch`` sequences."""
    dh = cfg.lin_head_dim
    return {
        "conv": jax.ShapeDtypeStruct((batch, cfg.conv_width - 1, cfg.lin_conv_dim),
                                     cfg.jdtype),
        "state": jax.ShapeDtypeStruct((batch, cfg.lin_v_heads, dh, dh), jnp.float32),
    }


def _inputs(p, cfg, x, conv_state):
    """Projections, the causal convolution (continuing ``conv_state``) and
    the per-head gates.  Returns float32 q, k (expanded to value heads),
    v, z, beta, g and the new convolution state."""
    B, S, _ = x.shape
    hk, hv, dh = cfg.lin_k_heads, cfg.lin_v_heads, cfg.lin_head_dim
    nq = hk * dh
    proj = x @ p["w_qkvz"]
    qkv, z = proj[..., :2 * nq + hv * dh], proj[..., 2 * nq + hv * dh:]
    ba = (x @ p["w_ba"]).astype(jnp.float32)
    b, a = ba[..., :hv], ba[..., hv:]

    width = cfg.conv_width
    seq = jnp.concatenate([conv_state.astype(qkv.dtype), qkv], axis=1)  # (B, w-1+S, C)
    w = p["conv"].astype(jnp.float32)
    mixed = sum(seq[:, i:i + S].astype(jnp.float32) * w[i] for i in range(width))
    mixed = jax.nn.silu(mixed)
    new_conv = seq[:, S:]

    q = mixed[..., :nq].reshape(B, S, hk, dh)
    k = mixed[..., nq:2 * nq].reshape(B, S, hk, dh)
    v = mixed[..., 2 * nq:].reshape(B, S, hv, dh)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + _EPS) / jnp.sqrt(jnp.float32(dh))
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + _EPS)
    rep = hv // hk
    q = jnp.repeat(q, rep, axis=2)
    k = jnp.repeat(k, rep, axis=2)
    beta = jax.nn.sigmoid(b)
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
        a + p["dt_bias"].astype(jnp.float32))
    return q, k, v, z.reshape(B, S, hv, dh), beta, g, new_conv


def _output(p, cfg, o, z, dtype):
    """Gated RMSNorm per value head (scale not zero-centred), then W_out."""
    B, S = o.shape[:2]
    o = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + cfg.norm_eps)
    o = (o * p["norm"].astype(jnp.float32)).astype(dtype).astype(jnp.float32)
    o = o * jax.nn.silu(z.astype(jnp.float32))
    return o.astype(dtype).reshape(B, S, -1) @ p["w_out"]


def chunked_delta_rule(q, k, v, g, beta, state, chunk: int):
    """The gated delta rule over a whole sequence in chunks.

    q, k: (B, S, H, dk); v: (B, S, H, dv); g, beta: (B, S, H); state
    (B, H, dk, dv); all float32.  Returns (o (B, S, H, dv), final state).
    The sequence is padded at its end to whole chunks with beta = 0 and
    g = 0, which leave the state and the earlier outputs unchanged."""
    B, S, H, dk = k.shape
    C = chunk
    n = -(-S // C)
    pad = n * C - S

    def chunks(t):
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((B, n, C) + t.shape[2:])
        return jnp.moveaxis(t, 3, 1)                   # (B, H, n, C, ...)

    q, k, v, g, beta = (chunks(t) for t in (q, k, v, g, beta))
    mm = lambda eq, x, y: jnp.einsum(eq, x, y, precision=_HIGHEST)  # noqa: E731
    kb = k * beta[..., None]
    vb = v * beta[..., None]
    gc = jnp.cumsum(g, axis=-1)                         # (B, H, n, C)
    lower = jnp.tril(jnp.ones((C, C), bool))
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))   # exp(g_i - g_j), i >= j
    strict = jnp.tril(jnp.ones((C, C), bool), -1)
    m = jnp.where(strict, mm("...id,...jd->...ij", kb, k) * decay, 0.0)
    eye = jnp.eye(C, dtype=jnp.float32)
    t = solve_triangular(eye + m, jnp.broadcast_to(eye, m.shape), lower=True,
                         unit_diagonal=True)
    u = mm("...ij,...jd->...id", t, vb)                 # the chunk's corrected values
    w = mm("...ij,...jd->...id", t, kb * jnp.exp(gc)[..., None])
    attn = mm("...id,...jd->...ij", q, k) * decay       # causal within the chunk
    q_dec = q * jnp.exp(gc)[..., None]
    k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    last = jnp.exp(gc[..., -1])                         # (B, H, n)

    def step(s, xs):
        u_i, w_i, attn_i, q_i, k_i, last_i = xs
        v_new = u_i - mm("bhid,bhde->bhie", w_i, s)
        o_i = mm("bhid,bhde->bhie", q_i, s) + mm("bhij,bhje->bhie", attn_i, v_new)
        s = s * last_i[..., None, None] + mm("bhid,bhie->bhde", k_i, v_new)
        return s, o_i

    xs = tuple(jnp.moveaxis(x, 2, 0) for x in (u, w, attn, q_dec, k_dec, last))
    state, o = lax.scan(step, state, xs)                # o: (n, B, H, C, dv)
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * C, -1)[:, :, :S]
    return jnp.moveaxis(o, 1, 2), state


def recurrent_step(q, k, v, g, beta, state):
    """One token of the gated delta rule: q, k (B, H, dk), v (B, H, dv),
    g, beta (B, H), state (B, H, dk, dv); elementwise float32."""
    s = state * jnp.exp(g)[..., None, None]
    kv = jnp.sum(s * k[..., :, None], axis=-2)
    delta = (v - kv) * beta[..., None]
    s = s + k[..., :, None] * delta[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def gdn_prefill(p, cfg, x) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """A whole prompt from empty state: (out, {"conv", "state"})."""
    B = x.shape[0]
    st = state_struct(cfg, B)
    conv0 = jnp.zeros(st["conv"].shape, st["conv"].dtype)
    q, k, v, z, beta, g, conv = _inputs(p, cfg, x, conv0)
    s0 = jnp.zeros(st["state"].shape, jnp.float32)
    o, s = chunked_delta_rule(q, k, v, g, beta, s0, CHUNK)
    return _output(p, cfg, o, z, x.dtype), {"conv": conv, "state": s}


def gdn_decode(p, cfg, x, st) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """One token (x: (B, 1, D)) on the state ``st``: (out, new state)."""
    q, k, v, z, beta, g, conv = _inputs(p, cfg, x, st["conv"])
    o, s = recurrent_step(q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0], st["state"])
    return _output(p, cfg, o[:, None], z, x.dtype), {"conv": conv, "state": s}
