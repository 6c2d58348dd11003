"""Transformer / MoE layer primitives as pure functions over param pytrees.

Conventions
-----------
* params are nested dicts of jnp arrays; every leaf has a parallel entry in
  the *spec tree* built by the ``*_spec`` functions: ``(shape, logical_axes)``
  where logical axes are drawn from LOGICAL_AXES and mapped to mesh axes by
  ``repro.sharding.rules``.
* activations are (batch, seq, d_model); batch shards over ("pod","data"),
  d_model is unsharded (Megatron TP), heads/ff/vocab/experts shard on
  "model".
* attention uses a two-level chunked lazy-softmax sweep (pure XLA; memory
  O(q_chunk x kv_chunk)) so 32k-sequence prefill fits HBM without Pallas —
  the Pallas flash kernel in ``repro.kernels`` is the TPU fast path.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax, shard_map

# logical axis vocabulary (mapped to mesh axes in repro.sharding.rules)
LOGICAL_AXES = ("batch", "seq", "embed", "heads", "kv_heads", "ff", "vocab",
                "experts", "ssm_inner", "state", None)

Spec = Dict[str, Any]          # nested dict: leaf = (shape, axes)
Params = Dict[str, Any]        # nested dict: leaf = jnp.ndarray


# ---------------------------------------------------------------------------
# spec/materialize machinery
# ---------------------------------------------------------------------------
def materialize(spec: Spec, key: jax.Array, dtype, scale_rule=None) -> Params:
    """Initialize a param tree from a spec tree: norm scales (``ln*``,
    ``*norm``, ``gamma*``, ``scale``) at one, whatever their rank once
    stacked; other matrices trunc-normal with fan-in scaling; other
    vectors at zero."""
    leaves = []

    def _walk(s, path):
        if isinstance(s, dict):
            return {k: _walk(v, path + (k,)) for k, v in s.items()}
        leaves.append((path, s))
        return None

    _walk(spec, ())
    keys = jax.random.split(key, max(1, len(leaves)))
    out: Dict = {}
    for (path, (shape, axes)), k in zip(leaves, keys):
        name = path[-1]
        if name.startswith(("ln", "gamma")) or name.endswith("norm") or name == "scale":
            v = jnp.ones(shape, dtype)
        elif len(shape) >= 2:
            fan_in = shape[-2] if len(shape) == 2 else math.prod(shape[:-1])
            std = 1.0 / math.sqrt(fan_in)
            v = (jax.random.truncated_normal(k, -2.0, 2.0, shape, jnp.float32) * std).astype(dtype)
        else:
            v = jnp.zeros(shape, dtype)
        node = out
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = v
    return out


def abstract(spec: Spec, dtype) -> Params:
    """ShapeDtypeStruct tree from a spec tree (for dry-run lowering)."""
    if isinstance(spec, dict):
        return {k: abstract(v, dtype) for k, v in spec.items()}
    shape, _ = spec
    return jax.ShapeDtypeStruct(shape, dtype)


def spec_axes(spec: Spec):
    """Logical-axes tree parallel to the param tree."""
    if isinstance(spec, dict):
        return {k: spec_axes(v) for k, v in spec.items()}
    _, axes = spec
    return axes


def stack_spec(spec: Spec, n: int) -> Spec:
    """Prepend a layer axis of size n to every leaf (for scan stacks)."""
    if isinstance(spec, dict):
        return {k: stack_spec(v, n) for k, v in spec.items()}
    shape, axes = spec
    return ((n,) + tuple(shape), ("layers",) + tuple(axes))


# ---------------------------------------------------------------------------
# norms / rope
# ---------------------------------------------------------------------------
def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-6,
            zero_centred: bool = False) -> jnp.ndarray:
    """RMSNorm in float32; ``zero_centred`` scales by ``1 + scale``
    (Qwen3-Next), so a scale of zero is the identity."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    scale = scale.astype(jnp.float32)
    if zero_centred:
        scale = 1.0 + scale
    return (x * scale).astype(dt)


def rope(x: jnp.ndarray, positions: jnp.ndarray, theta) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: (..., S).  ``theta`` may be a traced
    scalar (heterogeneous stacks pass per-layer theta through scan)."""
    hd = x.shape[-1]
    half = hd // 2
    log_theta = jnp.log(jnp.asarray(theta, jnp.float32))
    freqs = jnp.exp(-jnp.arange(0, half, dtype=jnp.float32) * (log_theta / half))
    ang = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    cos = cos[..., :, None, :]
    sin = sin[..., :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------
def attn_spec(cfg) -> Spec:
    hd, d = cfg.head_dim, cfg.d_model
    q_width = cfg.n_heads * hd * (2 if cfg.attn_output_gate else 1)
    s: Spec = {
        "wq": ((d, q_width), ("embed", "heads")),
        "wk": ((d, cfg.n_kv_heads * hd), ("embed", "kv_heads")),
        "wv": ((d, cfg.n_kv_heads * hd), ("embed", "kv_heads")),
        "wo": ((cfg.n_heads * hd, d), ("heads", "embed")),
    }
    if cfg.qk_norm:
        s["gamma_q"] = ((hd,), (None,))
        s["gamma_k"] = ((hd,), (None,))
    return s


def _chunked_attn(q, k, v, *, causal: bool, window: int, q_offset,
                  q_chunk: int = 512, kv_chunk: int = 1024):
    """Lazy-softmax chunked attention.

    q: (B, Sq, H, hd); k/v: (B, Sk, KV, hd); returns (B, Sq, H, hd).
    ``q_offset``: absolute position of q[0] (decode/prefill continuation).
    Memory: O(q_chunk * kv_chunk) per (batch, head).
    """
    B, Sq, H, hd = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    rep = H // KV
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = 1.0 / math.sqrt(hd)
    qc = min(q_chunk, Sq)
    kc = min(kv_chunk, Sk)
    nq = -(-Sq // qc)
    nk = -(-Sk // kc)
    # pad to chunk multiples
    q = jnp.pad(q, ((0, 0), (0, nq * qc - Sq), (0, 0), (0, 0)))
    k = jnp.pad(k, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, 0), (0, nk * kc - Sk), (0, 0), (0, 0)))
    q = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 3, 2, 4)   # (nq,B,H,qc,hd)
    k = k.reshape(B, nk, kc, H, hd).transpose(1, 0, 3, 2, 4)
    v = v.reshape(B, nk, kc, H, hd).transpose(1, 0, 3, 2, 4)

    q_pos_base = jnp.arange(qc)
    k_pos_base = jnp.arange(kc)

    def q_block(qi_and_qb):
        qi, qb = qi_and_qb
        q_pos = q_offset + qi * qc + q_pos_base          # (qc,)

        def kv_step(carry, kj_and_kv):
            m, l, acc = carry
            kj, kb, vb = kj_and_kv
            k_pos = kj * kc + k_pos_base                 # (kc,)
            s = jnp.einsum("bhqd,bhkd->bhqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((qc, kc), dtype=bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            win = jnp.asarray(window)
            mask &= (win <= 0) | (q_pos[:, None] - k_pos[None, :] < win)
            s = jnp.where(mask[None, None], s, -jnp.inf)
            m_new = jnp.maximum(m, s.max(axis=-1))
            # guard fully-masked rows (m_new = -inf)
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p = jnp.exp(s - m_safe[..., None])          # masked -> exp(-inf) = 0
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, H, qc), -jnp.inf, jnp.float32)
        l0 = jnp.zeros((B, H, qc), jnp.float32)
        a0 = jnp.zeros((B, H, qc, hd), jnp.float32)
        # checkpoint the kv step: backward re-materializes s/p per chunk
        # instead of saving O(qc*kc) residuals for every chunk pair
        (m, l, acc), _ = lax.scan(
            jax.checkpoint(kv_step,
                           policy=jax.checkpoint_policies.nothing_saveable),
            (m0, l0, a0), (jnp.arange(nk), k, v))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out                                        # (B,H,qc,hd)

    outs = lax.map(q_block, (jnp.arange(nq), q))          # (nq,B,H,qc,hd)
    out = outs.transpose(1, 0, 3, 2, 4).reshape(B, nq * qc, H, hd)
    return out[:, :Sq].astype(v.dtype)


def attention(p: Params, cfg, x: jnp.ndarray, *,
              causal: bool = True,
              window: int = 0,
              theta=None,
              positions: Optional[jnp.ndarray] = None,
              memory: Optional[jnp.ndarray] = None,
              cache: Optional[Dict[str, jnp.ndarray]] = None,
              cache_index: Optional[jnp.ndarray] = None,
              use_rope: bool = True,
              ) -> Tuple[jnp.ndarray, Optional[Dict[str, jnp.ndarray]]]:
    """Self- or cross-attention with optional KV cache.

    * training/prefill: ``cache is None`` -> returns (out, new_kv) where
      new_kv is the full K/V (for prefill cache construction).
    * decode: ``cache={'k','v'}`` (B, S_max, KV, hd), ``cache_index`` the
      current length; x is (B, 1, D); returns (out, updated_cache).
    * cross-attention: ``memory`` (B, M, D) supplies K/V (no cache logic,
      no causal mask).
    """
    B, S, D = x.shape
    hd = cfg.head_dim
    if theta is None:
        theta = cfg.rope_theta
    kv_src = memory if memory is not None else x
    gate = None
    if cfg.attn_output_gate:
        # per head [q | g]: the second half gates the head's output
        qg = (x @ p["wq"]).reshape(B, S, cfg.n_heads, 2 * hd)
        q, gate = qg[..., :hd], qg[..., hd:]
    else:
        q = (x @ p["wq"]).reshape(B, S, cfg.n_heads, hd)
    k = (kv_src @ p["wk"]).reshape(B, kv_src.shape[1], cfg.n_kv_heads, hd)
    v = (kv_src @ p["wv"]).reshape(B, kv_src.shape[1], cfg.n_kv_heads, hd)
    if cfg.qk_norm:
        zc = cfg.norm_zero_centred
        q = rmsnorm(q, p["gamma_q"], cfg.norm_eps, zc)
        k = rmsnorm(k, p["gamma_k"], cfg.norm_eps, zc)

    def out_proj(o):
        if gate is not None:
            o = o * jax.nn.sigmoid(gate)
        return o.reshape(B, S, cfg.n_heads * hd) @ p["wo"]

    rotate = rope
    if cfg.rotary_dim < hd:
        rot = cfg.rotary_dim

        def rotate(t, pos, th):
            return jnp.concatenate([rope(t[..., :rot], pos, th), t[..., rot:]], axis=-1)

    if memory is not None:
        # cross attention: full, non-causal, no rope
        out = _chunked_attn(q, k, v, causal=False, window=0, q_offset=0)
        return out_proj(out), None

    if cache is None:
        if positions is None:
            positions = jnp.arange(S)[None, :]
        if use_rope:
            q = rotate(q, positions, theta)
            k = rotate(k, positions, theta)
        out = _chunked_attn(q, k, v, causal=causal, window=window, q_offset=0)
        return out_proj(out), {"k": k, "v": v}

    # -- decode step ------------------------------------------------------
    idx = cache_index  # scalar int32: current cache fill
    if use_rope:
        q = rotate(q, jnp.full((B, S), idx, jnp.int32), theta)
        k = rotate(k, jnp.full((B, S), idx, jnp.int32), theta)
    z = jnp.zeros((), jnp.int32)
    idx32 = jnp.asarray(idx, jnp.int32)
    ck = lax.dynamic_update_slice(cache["k"], k.astype(cache["k"].dtype),
                                  (z, idx32, z, z))
    cv = lax.dynamic_update_slice(cache["v"], v.astype(cache["v"].dtype),
                                  (z, idx32, z, z))
    out = decode_attention(q, ck, cv, idx + S, window=window)
    return out_proj(out), {"k": ck, "v": cv}


def decode_attention(q, ck, cv, length, *, window: int = 0):
    """Single-step attention against a (possibly longer-than-filled) cache.

    q: (B, 1, H, hd); ck/cv: (B, S_max, KV, hd); `length` = #valid entries.
    O(S_max) memory — fine for decode.  Sequence-sharded variant lives in
    repro.sharding.sp (flash-decoding split-K with LSE combine).
    """
    B, _, H, hd = q.shape
    S_max, KV = ck.shape[1], ck.shape[2]
    rep = H // KV
    scale = 1.0 / math.sqrt(hd)
    qh = q[:, 0].reshape(B, KV, rep, hd)
    s = jnp.einsum("bgrd,bsgd->bgrs", qh, ck,
                   preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(S_max)
    mask = pos[None, None, None, :] < length
    win = jnp.asarray(window)
    mask &= (win <= 0) | (pos[None, None, None, :] > length - 1 - win)
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrs,bsgd->bgrd", p.astype(cv.dtype), cv,
                     preferred_element_type=jnp.float32)
    return out.reshape(B, 1, H, hd).astype(cv.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU)
# ---------------------------------------------------------------------------
def mlp_spec(cfg, d_ff: Optional[int] = None) -> Spec:
    f = d_ff or cfg.d_ff
    d = cfg.d_model
    return {
        "wg": ((d, f), ("embed", "ff")),
        "wu": ((d, f), ("embed", "ff")),
        "wd": ((f, d), ("ff", "embed")),
    }


def mlp(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


# ---------------------------------------------------------------------------
# MoE (dropless-ish: per-expert static capacity, EP over 'model')
# ---------------------------------------------------------------------------
def moe_spec(cfg) -> Spec:
    """The router spans all ``n_experts``; the expert stacks hold
    ``cfg.n_held_experts`` of them."""
    d, e, fe = cfg.d_model, cfg.n_experts, cfg.d_expert
    held = cfg.n_held_experts
    s: Spec = {
        "router": ((d, e), ("embed", None)),
        "wg": ((held, d, fe), ("experts", "embed", None)),
        "wu": ((held, d, fe), ("experts", "embed", None)),
        "wd": ((held, fe, d), ("experts", None, "embed")),
    }
    if cfg.shared_expert:
        s["shared"] = mlp_spec(cfg, cfg.d_ff or cfg.d_expert)
        if cfg.shared_expert_gate:
            s["shared_gate"] = ((d, 1), ("embed", None))
    return s


def _moe_compute(x_flat, ids, wts, wg, wu, wd, e_offset, n_local, capacity):
    """Compute contributions of experts [e_offset, e_offset+n_local) to the
    tokens in x_flat.  ids/wts: (T, k) global routing.  Returns (T, D)."""
    T, D = x_flat.shape
    capacity = min(capacity, T)
    y = jnp.zeros((T, D), jnp.float32)
    for le in range(n_local):
        ge = e_offset + le
        match = (ids == ge)                      # (T, k)
        weight = jnp.sum(jnp.where(match, wts, 0.0), axis=1)   # (T,)
        assigned = weight > 0
        # top-`capacity` assigned token slots (ties keep lowest index)
        score = assigned.astype(jnp.float32)
        _, token_idx = lax.top_k(score, capacity)             # (C,)
        valid = assigned[token_idx]
        xe = x_flat[token_idx]                                 # (C, D)
        h = jax.nn.silu(xe @ wg[le]) * (xe @ wu[le])
        ye = (h @ wd[le]).astype(jnp.float32)
        ye = ye * (weight[token_idx] * valid)[:, None]
        y = y.at[token_idx].add(jnp.where(valid[:, None], ye, 0.0))
    return y


def moe(p: Params, cfg, x: jnp.ndarray, *, shard_ctx=None) -> jnp.ndarray:
    """Top-k MoE FFN.  With ``shard_ctx`` (repro.sharding.rules.ShardCtx):
    experts shard over the model axis via shard_map — tokens stay sharded on
    the batch axes and replicated on the model axis; per-chip experts compute
    their capacity-cropped assignments and outputs psum-combine over the
    model axis (Megatron-style EP).  Without: single-device reference path."""
    B, S, D = x.shape
    T = B * S
    x_flat = x.reshape(T, D)
    logits = (x_flat @ p["router"]).astype(jnp.float32)        # (T, E)
    wts, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), cfg.top_k)
    wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)

    if shard_ctx is None or shard_ctx.mesh is None:
        cap = _moe_capacity(T, cfg)
        y = _moe_compute(x_flat, ids, wts, p["wg"], p["wu"], p["wd"],
                         0, cfg.n_experts, cap)
    else:
        from jax.sharding import PartitionSpec as P
        mesh = shard_ctx.mesh
        ep_axis = shard_ctx.model_axis
        batch_axes = shard_ctx.batch_axes
        ep = mesh.shape[ep_axis]
        dp = 1
        for a in batch_axes:
            dp *= mesh.shape[a]
        n_local = cfg.n_experts // ep
        t_local = T // dp
        cap = _moe_capacity(t_local, cfg)

        wire_bf16 = bool(getattr(shard_ctx, "moe_wire_bf16", False))
        gather_tokens = bool(getattr(shard_ctx, "moe_gather_tokens", False))

        if gather_tokens:
            # Beyond-baseline EP (EXPERIMENTS §Perf cell C): expert weights
            # stay 2D-sharded (experts on model, d_model on data) and are
            # NEVER gathered; instead the (much smaller) tokens all-gather
            # over the data axes and the expert contractions run partial
            # over the d_model shard + psum.  Collective volume per layer
            # drops from O(expert_params) to O(tokens x d_model).
            cap_g = min(_moe_capacity(T, cfg), T)

            def _shard_fn_g(xf, idl, wtl, wg, wu, wd):
                eidx = lax.axis_index(ep_axis)
                xg = lax.all_gather(xf, batch_axes, axis=0, tiled=True)
                idg = lax.all_gather(idl, batch_axes, axis=0, tiled=True)
                wtg = lax.all_gather(wtl, batch_axes, axis=0, tiled=True)
                Tg, _ = xg.shape
                dloc = wg.shape[1]
                didx = lax.axis_index(batch_axes) if len(batch_axes) == 1 else (
                    lax.axis_index(batch_axes[0]) * mesh.shape[batch_axes[1]]
                    + lax.axis_index(batch_axes[1]))
                # accumulate each expert's output in the chip's LOCAL d_model
                # columns only — (Tg, dloc) instead of (Tg, D)
                y = jnp.zeros((Tg, dloc), jnp.float32)
                for le in range(n_local):
                    ge = eidx * n_local + le
                    match = (idg == ge)
                    weight = jnp.sum(jnp.where(match, wtg, 0.0), axis=1)
                    assigned = weight > 0
                    _, token_idx = lax.top_k(assigned.astype(jnp.float32), cap_g)
                    valid = assigned[token_idx]
                    xe = xg[token_idx]                       # (C, D) full D
                    xe_part = lax.dynamic_slice(xe, (0, didx * dloc),
                                                (cap_g, dloc))
                    # partial contraction over the local d_model shard
                    hg = lax.psum((xe_part @ wg[le]).astype(jnp.float32),
                                  batch_axes)
                    hu = lax.psum((xe_part @ wu[le]).astype(jnp.float32),
                                  batch_axes)
                    h = jax.nn.silu(hg) * hu                 # (C, Fe) complete
                    ye = (h.astype(xg.dtype) @ wd[le]).astype(jnp.float32)
                    ye = ye * (weight[token_idx] * valid)[:, None]
                    y = y.at[token_idx].add(jnp.where(valid[:, None], ye, 0.0))
                # redistribute rows<->cols: (Tg, dloc) -> (T_local, D): one
                # all-to-all over the batch axes, then combine experts over
                # the model axis on local rows only
                wire = y.astype(jnp.bfloat16) if wire_bf16 else y
                yl = lax.all_to_all(wire, batch_axes, split_axis=0,
                                    concat_axis=1, tiled=True)
                return lax.psum(yl, ep_axis).astype(jnp.float32)

            y = shard_map(
                _shard_fn_g, mesh=mesh,
                in_specs=(P(batch_axes, None), P(batch_axes, None),
                          P(batch_axes, None),
                          P(ep_axis, batch_axes, None),
                          P(ep_axis, batch_axes, None),
                          P(ep_axis, None, batch_axes)),
                out_specs=P(batch_axes, None),
                check_vma=False,
            )(x_flat, ids, wts, p["wg"], p["wu"], p["wd"])
            y = y.astype(x.dtype).reshape(B, S, D)
            if cfg.shared_expert and "shared" in p:
                y = y + mlp(p["shared"], x)
            return y

        def _shard_fn(xf, idl, wtl, wg, wu, wd):
            eidx = lax.axis_index(ep_axis)
            y = _moe_compute(xf, idl, wtl, wg, wu, wd,
                             eidx * n_local, n_local, cap)
            if wire_bf16:
                # EP combine on the wire in bf16 (halves the all-reduce)
                return lax.psum(y.astype(jnp.bfloat16), ep_axis).astype(jnp.float32)
            return lax.psum(y, ep_axis)

        y = shard_map(
            _shard_fn, mesh=mesh,
            in_specs=(P(batch_axes, None), P(batch_axes, None), P(batch_axes, None),
                      P(ep_axis), P(ep_axis), P(ep_axis)),
            out_specs=P(batch_axes, None),
            check_vma=False,
        )(x_flat, ids, wts, p["wg"], p["wu"], p["wd"])

    y = y.astype(x.dtype).reshape(B, S, D)
    if cfg.shared_expert and "shared" in p:
        y = y + mlp(p["shared"], x)
    return y


#: routing counters of one dropless expert layer, in this order
ROUTING_COUNTERS = ("held_assignments", "max_held_load", "held_touched")


def moe_dropless(p: Params, cfg, x: jnp.ndarray, layer
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k expert layer that holds experts ``[cfg.expert_offset,
    + cfg.n_held_experts)`` of the ``cfg.n_experts`` it routes over, with
    no capacity: every token-expert pair that lands on a held expert is
    computed, and pairs routed to experts held elsewhere do no work here
    (their part is left out, as on a chip of an expert-parallel group).

    Routing is softmax in float32 over all experts, top-k, renormalised.
    Many pairs (prefill) are sorted by expert and run as grouped products
    (``lax.ragged_dot``); a few (decode) loop over the held experts they
    routed to, reading only those experts' weights.  The shared expert,
    behind its sigmoid gate where the configuration has one, is added
    whole.  Returns ``(y, counters)``, ``counters`` an int32 vector in
    :data:`ROUTING_COUNTERS` order: pairs on held experts, the largest
    held expert's load, held experts touched.

    The expert stacks ``wg``/``wu``/``wd`` hold every layer's experts
    (layers, held, ...) and ``layer`` picks one: a decode step inside a
    layer scan then reads single experts from the whole stack, where a
    sliced layer would be copied whole into the loop."""
    B, S, D = x.shape
    T, k = B * S, cfg.top_k
    n = cfg.n_held_experts
    xf = x.reshape(T, D)
    with jax.named_scope("moe.route"):
        # logits leave the product in float32: bf16 logits would flip
        # near-tied choices of the top k
        logits = jnp.matmul(xf, p["router"], preferred_element_type=jnp.float32)
        wts, ids = lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        wts = wts / jnp.maximum(wts.sum(-1, keepdims=True), 1e-9)
        local = ids.reshape(-1) - cfg.expert_offset               # (T*k,)
        held = (local >= 0) & (local < n)
        group = jnp.where(held, local, n).astype(jnp.int32)       # n: held elsewhere
        load = jnp.zeros((n + 1,), jnp.int32).at[group].add(1)[:n]
        counters = jnp.stack([jnp.sum(held, dtype=jnp.int32), jnp.max(load),
                              jnp.sum(load > 0, dtype=jnp.int32)])
    with jax.named_scope("moe.experts"):
        pair_w = jnp.where(held, wts.reshape(-1), 0.0)
        if T * k < n:
            y = _held_pairs_loop(xf, group, pair_w, held, p, k, layer)
        else:
            stacks = {name: lax.dynamic_index_in_dim(p[name], layer, 0, keepdims=False)
                      for name in ("wg", "wu", "wd")}
            y = _held_pairs_grouped(xf, group, pair_w, load, stacks, k)
    y = y.astype(x.dtype).reshape(B, S, D)
    if cfg.shared_expert:
        shared = mlp(p["shared"], x)
        if cfg.shared_expert_gate:
            shared = jax.nn.sigmoid(x @ p["shared_gate"]) * shared
        y = y + shared
    return y, counters


def _expert(xe, wg, wu, wd):
    h = jax.nn.silu(xe @ wg) * (xe @ wu)
    return jnp.matmul(h, wd, preferred_element_type=jnp.float32)


def _held_pairs_grouped(xf, group, pair_w, load, p, k):
    """Every held pair at once: pairs sorted by expert (those held
    elsewhere last, outside every group), grouped products over the held
    experts' stacks, weighted rows scattered back to their tokens."""
    T, D = xf.shape
    order = jnp.argsort(group, stable=True)
    tok = order // k
    xs = xf[tok]
    h = jax.nn.silu(lax.ragged_dot(xs, p["wg"], load)) * lax.ragged_dot(xs, p["wu"], load)
    ys = lax.ragged_dot(h, p["wd"], load, preferred_element_type=jnp.float32)
    ys = ys * pair_w[order][:, None]
    return jnp.zeros((T, D), jnp.float32).at[tok].add(ys)


def _held_pairs_loop(xf, group, pair_w, held, p, k, layer):
    """A few pairs: one loop trip per held pair, each reading its own
    expert's weights (pairs held elsewhere sort last and never run)."""
    T, D = xf.shape
    order = jnp.argsort(jnp.where(held, 0, 1), stable=True)

    def one(name, e):
        w = p[name]
        z = jnp.zeros((), jnp.int32)
        start = (jnp.asarray(layer, jnp.int32), e, z, z)
        return lax.dynamic_slice(w, start, (1, 1) + w.shape[2:])[0, 0]

    def body(i, y):
        j = order[i]
        e, t = group[j], j // k
        w = [one(name, e) for name in ("wg", "wu", "wd")]
        ye = _expert(lax.dynamic_index_in_dim(xf, t, 0), *w)
        return y.at[t].add(ye[0] * pair_w[j])

    return lax.fori_loop(0, jnp.sum(held, dtype=jnp.int32), body,
                         jnp.zeros((T, D), jnp.float32))


def _moe_capacity(t_local: int, cfg) -> int:
    return max(1, int(t_local * cfg.top_k * cfg.capacity_factor / cfg.n_experts))


# ---------------------------------------------------------------------------
# embedding / unembedding / loss (vocab-sharded via shard_map at model level)
# ---------------------------------------------------------------------------
def embed_spec(cfg) -> Spec:
    return {"table": ((cfg.vocab_size, cfg.d_model), ("vocab", "embed"))}


def unembed_spec(cfg) -> Spec:
    return {"out": ((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))}
