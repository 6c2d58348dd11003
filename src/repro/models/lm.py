"""Unified LM assembly for all assigned architecture families.

One repeating *block* per family, stacked along a leading ``layers`` axis and
driven by ``lax.scan`` (MaxText-style: HLO size and compile time independent
of depth).  Heterogeneous stacks (gemma3 local:global, zamba2 shared
attention, llama-vision cross-attention) use per-layer flag arrays as scan
xs — one compiled body, no per-layer HLO.

The Gated DeltaNet hybrid (``gdn``, Qwen3-Next) has two kinds of layer,
each with its own weights: ``full_attn_every - 1`` Gated DeltaNet layers
(``models/gdn.py``) then one gated full-attention layer, repeating, with a
dropless expert layer in every layer.  It scans over periods, with the
DeltaNet layers of a period unrolled; its cache holds K/V for the full
layers and convolution and recurrent state for the others.

Entry points (all pure; jit/shard them from repro.launch):

* ``model_spec(cfg)`` / ``init_params(cfg, key)`` / ``abstract_params(cfg)``
* ``forward(params, cfg, batch, ctx)``           -> final hidden states
* ``loss_fn(params, cfg, batch, ctx)``           -> scalar CE loss
* ``zeros_cache(cfg, batch, max_len, ctx)``      -> decode cache pytree
* ``prefill(params, cfg, batch, ctx, max_len)``  -> (cache, last logits)
* ``decode_step(params, cfg, cache, tok, ctx)``  -> (cache, logits)
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from . import gdn as G
from . import layers as L
from . import ssm as S
from .config import ModelConfig


def padded_vocab(cfg: ModelConfig) -> int:
    """Vocab rounded up so the vocab axis shards evenly (CE masks padding)."""
    return -(-cfg.vocab_size // 256) * 256


# ---------------------------------------------------------------------------
# specs
# ---------------------------------------------------------------------------
def block_spec(cfg: ModelConfig) -> Dict:
    s: Dict = {}
    fam = cfg.family
    if fam in ("dense", "moe", "encdec", "vlm"):
        s["ln1"] = ((cfg.d_model,), ("embed",))
        s["attn"] = L.attn_spec(cfg)
        s["ln2"] = ((cfg.d_model,), ("embed",))
        if fam == "moe":
            s["moe"] = L.moe_spec(cfg)
        else:
            s["mlp"] = L.mlp_spec(cfg)
        if fam == "vlm":
            s["lnx"] = ((cfg.d_model,), ("embed",))
            s["xattn"] = L.attn_spec(cfg)
            s["xgate"] = ((1,), (None,))
        if fam == "encdec":
            s["lnx"] = ((cfg.d_model,), ("embed",))
            s["xattn"] = L.attn_spec(cfg)
    elif fam in ("ssm", "hybrid"):
        s["ln1"] = ((cfg.d_model,), ("embed",))
        s["ssm"] = S.ssm_spec(cfg)
    return s


def _gdn_block_specs(cfg: ModelConfig):
    """(Gated DeltaNet layer, full-attention layer) of the gdn family."""
    d = cfg.d_model
    common = {"ln1": ((d,), ("embed",)), "ln2": ((d,), ("embed",)),
              "moe": L.moe_spec(cfg)}
    return dict(common, gdn=G.gdn_spec(cfg)), dict(common, attn=L.attn_spec(cfg))


def model_spec(cfg: ModelConfig) -> Dict:
    v = padded_vocab(cfg)
    d = cfg.d_model
    if cfg.family == "gdn":
        lin, full = _gdn_block_specs(cfg)
        n_full = cfg.n_full_layers
        blocks = {"lin": L.stack_spec(lin, cfg.n_layers - n_full),
                  "full": L.stack_spec(full, n_full)}
    else:
        blocks = L.stack_spec(block_spec(cfg), cfg.n_layers)
    spec: Dict = {
        "embed": {"table": ((v, d), ("vocab", "embed"))},
        "final_norm": ((d,), ("embed",)),
        "blocks": blocks,
    }
    if not cfg.tie_embeddings:
        spec["unembed"] = {"out": ((d, v), ("embed", "vocab"))}
    if cfg.family == "hybrid":
        spec["shared"] = {
            "ln1": ((d,), ("embed",)),
            "attn": L.attn_spec(cfg),
            "ln2": ((d,), ("embed",)),
            "mlp": L.mlp_spec(cfg),
        }
    if cfg.family == "encdec":
        enc_block = {
            "ln1": ((d,), ("embed",)),
            "attn": L.attn_spec(cfg),
            "ln2": ((d,), ("embed",)),
            "mlp": L.mlp_spec(cfg),
        }
        spec["enc_blocks"] = L.stack_spec(enc_block, cfg.enc_layers)
    return spec


@functools.partial(jax.jit, static_argnums=0)
def init_params(cfg: ModelConfig, key: jax.Array):
    """Random parameters for ``cfg``.  Jitted, so each leaf is drawn and
    cast on the device without a float32 copy of the whole tree: at full
    width those copies alone would not fit beside the weights."""
    return L.materialize(model_spec(cfg), key, cfg.jdtype)


def abstract_params(cfg: ModelConfig):
    return L.abstract(model_spec(cfg), cfg.jdtype)


def param_pspecs(cfg: ModelConfig, ctx):
    from ..sharding.rules import params_pspecs
    return params_pspecs(L.spec_axes(model_spec(cfg)), ctx)


def n_attn_slots(cfg: ModelConfig) -> int:
    return cfg.n_layers // max(1, cfg.attn_every) if cfg.family == "hybrid" else cfg.n_layers


# ---------------------------------------------------------------------------
# per-layer flags (scan xs)
# ---------------------------------------------------------------------------
def layer_flags(cfg: ModelConfig) -> Dict[str, jnp.ndarray]:
    n = cfg.n_layers
    fam = cfg.family
    flags: Dict[str, jnp.ndarray] = {}
    if fam in ("dense", "moe", "vlm", "encdec"):
        if cfg.local_global_ratio:
            r = cfg.local_global_ratio
            is_global = (jnp.arange(n) % (r + 1)) == r
            flags["window"] = jnp.where(is_global, 0, cfg.window).astype(jnp.int32)
            flags["theta"] = jnp.where(is_global, 1e6, cfg.rope_theta).astype(jnp.float32)
        else:
            flags["window"] = jnp.full((n,), cfg.window, jnp.int32)
            flags["theta"] = jnp.full((n,), cfg.rope_theta, jnp.float32)
    if fam == "hybrid" and cfg.attn_every:
        use = (jnp.arange(n) % cfg.attn_every) == cfg.attn_every - 1
        flags["use_attn"] = use
        flags["attn_slot"] = jnp.maximum(jnp.cumsum(use) - 1, 0).astype(jnp.int32)
    if fam == "vlm" and cfg.cross_attn_every:
        flags["use_cross"] = ((jnp.arange(n) % cfg.cross_attn_every)
                              == cfg.cross_attn_every - 1)
    return flags


# ---------------------------------------------------------------------------
# embedding / loss (vocab-sharded shard_map paths)
# ---------------------------------------------------------------------------
def _usable_batch_axes(ctx, batch_size: int):
    """Batch axes only when the batch divides the DP extent (a batch-1
    decode step keeps activations replicated over the data axes)."""
    dp = 1
    for a in ctx.batch_axes:
        dp *= ctx.mesh.shape[a]
    return ctx.batch_axes if batch_size % dp == 0 else None


def embed_lookup(table: jnp.ndarray, ids: jnp.ndarray, ctx) -> jnp.ndarray:
    if ctx is None or ctx.mesh is None:
        return table[ids]
    mesh = ctx.mesh
    v_local = table.shape[0] // mesh.shape[ctx.model_axis]
    batch_axes = _usable_batch_axes(ctx, ids.shape[0])

    def f(tab, idl):
        start = lax.axis_index(ctx.model_axis) * v_local
        local = idl - start
        ok = (local >= 0) & (local < v_local)
        safe = jnp.clip(local, 0, v_local - 1)
        out = jnp.where(ok[..., None], tab[safe], 0).astype(tab.dtype)
        return lax.psum(out, ctx.model_axis)

    return shard_map(
        f, mesh=mesh,
        in_specs=(P(ctx.model_axis, None), P(batch_axes, None)),
        out_specs=P(batch_axes, None, None), check_vma=False,
    )(table, ids)


def sharded_ce_loss(h: jnp.ndarray, wout: jnp.ndarray, labels: jnp.ndarray,
                    cfg: ModelConfig, ctx) -> jnp.ndarray:
    """Token-mean cross entropy with vocab-sharded logits (the full logit
    matrix never materializes on one device).  labels < 0 are masked."""
    v_real = cfg.vocab_size

    if ctx is None or ctx.mesh is None:
        logits = (h @ wout).astype(jnp.float32)
        gidx = jnp.arange(logits.shape[-1])
        logits = jnp.where(gidx < v_real, logits, -jnp.inf)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(
            logits, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
        mask = labels >= 0
        return jnp.sum(jnp.where(mask, lse - picked, 0.0)) / jnp.maximum(mask.sum(), 1)

    mesh = ctx.mesh
    v_local = wout.shape[-1] // mesh.shape[ctx.model_axis]
    batch_axes = _usable_batch_axes(ctx, h.shape[0])
    CE_CHUNK = 2048   # tokens per chunk: bounds the f32 logit buffer

    def f(hs, w, lab):
        start = lax.axis_index(ctx.model_axis) * v_local
        gidx = start + jnp.arange(v_local)
        neg = jnp.float32(-1e30)
        B, S, D = hs.shape
        T = B * S
        tc = min(CE_CHUNK, T)
        nc = -(-T // tc)
        hflat = hs.reshape(T, D)
        lflat = lab.reshape(T)
        if nc * tc != T:
            hflat = jnp.pad(hflat, ((0, nc * tc - T), (0, 0)))
            lflat = jnp.pad(lflat, (0, nc * tc - T), constant_values=-1)
        hflat = hflat.reshape(nc, tc, D)
        lflat = lflat.reshape(nc, tc)

        def chunk(carry, inp):
            num, cnt = carry
            hc, lc = inp
            logits = (hc @ w).astype(jnp.float32)            # (tc, v_local)
            logits = jnp.where(gidx < v_real, logits, neg)
            # stop_gradient BEFORE pmax: the shift is stability-only and
            # gradient-neutral (pmax has no differentiation rule; a
            # symbolically-zero tangent never invokes it).
            lmax = lax.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)),
                            ctx.model_axis)
            z = jnp.exp(logits - lmax[:, None])
            denom = lax.psum(jnp.sum(z, -1), ctx.model_axis)
            lse = jnp.log(denom) + lmax
            onloc = (lc[:, None] == gidx)
            picked = lax.psum(jnp.sum(jnp.where(onloc, logits, 0.0), -1),
                              ctx.model_axis)
            mask = lc >= 0
            num = num + jnp.sum(jnp.where(mask, lse - picked, 0.0))
            cnt = cnt + jnp.sum(mask)
            return (num, cnt), None

        (num, cnt), _ = lax.scan(
            jax.checkpoint(chunk, policy=jax.checkpoint_policies.nothing_saveable),
            (jnp.zeros((1,), jnp.float32), jnp.zeros((1,), jnp.int32)),
            (hflat, lflat))
        if batch_axes:
            num = lax.psum(num, batch_axes)
            cnt = lax.psum(cnt, batch_axes)
        return num / jnp.maximum(cnt, 1)

    loss = shard_map(
        f, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, ctx.model_axis),
                  P(batch_axes, None)),
        out_specs=P(None), check_vma=False,
    )(h, wout, labels)
    return loss[0]


# ---------------------------------------------------------------------------
# block pieces
# ---------------------------------------------------------------------------
def _self_attn(bp, cfg, x, *, window, theta, positions, cache=None,
               cache_index=None):
    h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
    out, kv = L.attention(bp["attn"], cfg, h, causal=True, window=window,
                          theta=theta, positions=positions, cache=cache,
                          cache_index=cache_index)
    return x + out, kv


def _cross_attn(bp, cfg, x, memory, gated: bool):
    h = L.rmsnorm(x, bp["lnx"], cfg.norm_eps)
    out, _ = L.attention(bp["xattn"], cfg, h, memory=memory)
    if gated:
        out = jnp.tanh(bp["xgate"]) * out
    return x + out


def _ffn(bp, cfg, x, ctx):
    h = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
    if "moe" in bp:
        return x + L.moe(bp["moe"], cfg, h, shard_ctx=ctx)
    return x + L.mlp(bp["mlp"], h)


def _shared_attn_block(sp, cfg, x, positions, cache=None, cache_index=None):
    h = L.rmsnorm(x, sp["ln1"], cfg.norm_eps)
    out, kv = L.attention(sp["attn"], cfg, h, causal=True, positions=positions,
                          cache=cache, cache_index=cache_index)
    x = x + out
    h = L.rmsnorm(x, sp["ln2"], cfg.norm_eps)
    return x + L.mlp(sp["mlp"], h), kv


def _maybe_remat(fn, remat: bool):
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable) \
        if remat else fn


def _block_constrainer(cfg: ModelConfig, ctx, spec=None):
    """Returns a function constraining a per-layer param slice to its
    sharding INSIDE the scan body.  with_sharding_constraint transposes to
    itself, so the per-layer *cotangent* (the backward while-loop's gradient
    accumulator update) inherits the sharding — without this XLA leaves the
    full stacked-gradient accumulator replicated (~4x param bytes per
    device)."""
    if ctx is None or ctx.mesh is None:
        return lambda bp: bp
    from jax.sharding import NamedSharding
    from ..sharding.rules import params_pspecs
    from . import layers as LL
    pspec_tree = params_pspecs(LL.spec_axes(spec or block_spec(cfg)), ctx)
    sh_tree = jax.tree.map(lambda p: NamedSharding(ctx.mesh, p), pspec_tree,
                           is_leaf=lambda x: isinstance(x, P))

    def constrain(bp):
        return jax.tree.map(lax.with_sharding_constraint, bp, sh_tree,
                            is_leaf=lambda x: not isinstance(x, dict))

    return constrain


# ---------------------------------------------------------------------------
# forward (training / scoring)
# ---------------------------------------------------------------------------
def forward(params, cfg: ModelConfig, batch: Dict[str, jnp.ndarray], ctx=None,
            *, remat: bool = True) -> jnp.ndarray:
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    x = embed_lookup(params["embed"]["table"], tokens, ctx)
    positions = jnp.arange(Sq)[None, :]
    if cfg.family == "gdn":
        x, _ = _gdn_stack(params, cfg, x, positions, remat=remat)
        return _gdn_norm(cfg, x, params["final_norm"])
    flags = layer_flags(cfg)
    fam = cfg.family

    memory = None
    if fam == "encdec":
        memory = _encode(params, cfg, batch["enc_input"], ctx, remat=remat)
    elif fam == "vlm":
        memory = batch["patches"]

    constrain = _block_constrainer(cfg, ctx)

    if fam in ("ssm", "hybrid"):
        def body(x, scanned):
            bp, fl = scanned
            bp = constrain(bp)
            if fam == "hybrid":
                x = lax.cond(
                    fl["use_attn"],
                    lambda v: _shared_attn_block(params["shared"], cfg, v, positions)[0],
                    lambda v: v, x)
            h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
            out, _ = S.ssm_block(bp["ssm"], cfg, h)
            return x + out, None
    else:
        def body(x, scanned):
            bp, fl = scanned
            bp = constrain(bp)
            x, _ = _self_attn(bp, cfg, x, window=fl["window"],
                              theta=fl["theta"], positions=positions)
            if fam == "vlm":
                x = lax.cond(fl["use_cross"],
                             lambda v: _cross_attn(bp, cfg, v, memory, gated=True),
                             lambda v: v, x)
            if fam == "encdec":
                x = _cross_attn(bp, cfg, x, memory, gated=False)
            return _ffn(bp, cfg, x, ctx), None

    group = getattr(ctx, "remat_group", 1) if ctx is not None else 1
    if remat and group > 1 and cfg.n_layers % group == 0:
        # 2-level remat: checkpoint at group boundaries only — the saved
        # carry stash shrinks by ~group at the cost of re-running `group`
        # layers per backward step (memory<->recompute trade, §Perf).
        ng = cfg.n_layers // group
        blocks_g = jax.tree.map(
            lambda a: a.reshape((ng, group) + a.shape[1:]), params["blocks"])
        flags_g = {k: v.reshape((ng, group) + v.shape[1:])
                   for k, v in flags.items()}

        def group_body(xc, scanned):
            bpg, flg = scanned
            xc, _ = lax.scan(body, xc, (bpg, flg))
            return xc, None

        x, _ = lax.scan(_maybe_remat(group_body, True), x, (blocks_g, flags_g))
    else:
        x, _ = lax.scan(_maybe_remat(body, remat), x, (params["blocks"], flags))
    return L.rmsnorm(x, params["final_norm"], cfg.norm_eps)


def _encode(params, cfg: ModelConfig, enc_input, ctx, *, remat=True):
    x = enc_input
    positions = jnp.arange(x.shape[1])[None, :]
    enc_spec = {
        "ln1": ((cfg.d_model,), ("embed",)),
        "attn": L.attn_spec(cfg),
        "ln2": ((cfg.d_model,), ("embed",)),
        "mlp": L.mlp_spec(cfg),
    }
    constrain = _block_constrainer(cfg, ctx, spec=enc_spec)

    def body(x, bp):
        bp = constrain(bp)
        h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
        out, _ = L.attention(bp["attn"], cfg, h, causal=False, positions=positions)
        x = x + out
        h = L.rmsnorm(x, bp["ln2"], cfg.norm_eps)
        return x + L.mlp(bp["mlp"], h), None

    x, _ = lax.scan(_maybe_remat(body, remat), x, params["enc_blocks"])
    return x


def logits_from_hidden(params, cfg: ModelConfig, h: jnp.ndarray) -> jnp.ndarray:
    """Logits over the real vocabulary: the columns that pad it to
    :func:`padded_vocab` hold random weights and name no token, so they
    are cut off before any sampler sees them."""
    wout = params["unembed"]["out"] if "unembed" in params \
        else params["embed"]["table"].T
    return (h @ wout)[..., :cfg.vocab_size]


def loss_fn(params, cfg: ModelConfig, batch, ctx=None, *, remat: bool = True):
    h = forward(params, cfg, batch, ctx, remat=remat)
    wout = params["unembed"]["out"] if "unembed" in params \
        else params["embed"]["table"].T
    return sharded_ce_loss(h, wout, batch["labels"], cfg, ctx)


# ---------------------------------------------------------------------------
# Gated DeltaNet hybrid (gdn)
# ---------------------------------------------------------------------------
def _gdn_norm(cfg: ModelConfig, x, w):
    return L.rmsnorm(x, w, cfg.norm_eps, cfg.norm_zero_centred)


def _gdn_experts(bp, cfg: ModelConfig, x, stacks, layer):
    h = _gdn_norm(cfg, x, bp["ln2"])
    with jax.named_scope("moe"):
        y, counters = L.moe_dropless(dict(bp["moe"], **stacks), cfg, h, layer)
    return x + y, counters


def _gdn_stack(params, cfg: ModelConfig, x, positions, cache=None, *,
               remat: bool = False):
    """The whole gdn stack over ``x``: from empty state when ``cache`` is
    None (the prompt's K/V, convolution and recurrent state come back), or
    one decode step on ``cache``.  Returns ``(x, (state, k, v,
    counters))``: state stacked over the DeltaNet layers, K/V over the
    full layers, the routing counters (n_layers, 3) in layer order.

    One scan step is one period; its DeltaNet layers are unrolled and read
    their weights from the whole stack by layer index, and the expert
    stacks stay whole too: a weight slice then feeds its products
    directly, where a nested scan would copy each period's slice first."""
    per = cfg.full_attn_every - 1
    n_per = cfg.n_full_layers
    experts = {}
    blocks = {}
    for kind in ("lin", "full"):
        moe = dict(params["blocks"][kind]["moe"])
        experts[kind] = {n: moe.pop(n) for n in ("wg", "wu", "wd")}
        blocks[kind] = dict(params["blocks"][kind], moe=moe)
    decoding = cache is not None
    states = kbuf = vbuf = None
    if decoding:
        states = {n: cache[n].reshape((n_per, per) + cache[n].shape[1:])
                  for n in ("conv", "state")}
        kbuf, vbuf = cache["k"], cache["v"]

    def lin_layer(x, st, layer):
        bp = jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, layer, 0, keepdims=False),
                          blocks["lin"])
        h = _gdn_norm(cfg, x, bp["ln1"])
        with jax.named_scope("gdn"):
            if decoding:
                out, st = G.gdn_decode(bp["gdn"], cfg, h, st)
            else:
                out, st = G.gdn_prefill(bp["gdn"], cfg, h)
        x, counters = _gdn_experts(bp, cfg, x + out, experts["lin"], layer)
        return x, st, counters

    def period(x, scanned):
        fp, st, ck, cv, i = scanned
        new_st, counts = [], []
        for j in range(per):
            st_j = None if st is None else jax.tree.map(lambda a: a[j], st)
            x, st_j, c = lin_layer(x, st_j, i * per + j)
            new_st.append(st_j)
            counts.append(c)
        h = _gdn_norm(cfg, x, fp["ln1"])
        with jax.named_scope("gated_attn"):
            if decoding:
                out, kv = L.attention(fp["attn"], cfg, h, cache={"k": ck, "v": cv},
                                      cache_index=cache["index"])
            else:
                out, kv = L.attention(fp["attn"], cfg, h, positions=positions)
        x, c = _gdn_experts(fp, cfg, x + out, experts["full"], i)
        new_st = jax.tree.map(lambda *a: jnp.stack(a), *new_st)
        return x, (new_st, kv["k"], kv["v"], jnp.stack(counts + [c]))

    x, (st, ks, vs, counters) = lax.scan(
        _maybe_remat(period, remat), x,
        (blocks["full"], states, kbuf, vbuf, jnp.arange(n_per, dtype=jnp.int32)))
    st = jax.tree.map(lambda a: a.reshape((n_per * per,) + a.shape[2:]), st)
    return x, (st, ks, vs, counters.reshape(cfg.n_layers, -1))


def _gdn_prefill(params, cfg: ModelConfig, tokens, ctx, max_len: int):
    B, Sq = tokens.shape
    cache = zeros_cache(cfg, B, max_len)
    x = embed_lookup(params["embed"]["table"], tokens, ctx)
    x, (st, ks, vs, counters) = _gdn_stack(params, cfg, x, jnp.arange(Sq)[None, :])
    cache.update(st)
    for name, new in (("k", ks), ("v", vs)):
        cache[name] = lax.dynamic_update_slice(cache[name], new.astype(cache[name].dtype),
                                               (0, 0, 0, 0, 0))
    cache["index"] = jnp.int32(Sq)
    h = _gdn_norm(cfg, x[:, -1:], params["final_norm"])
    return cache, logits_from_hidden(params, cfg, h), counters


def _gdn_decode(params, cfg: ModelConfig, cache, tokens, ctx):
    x = embed_lookup(params["embed"]["table"], tokens, ctx)
    x, (st, ks, vs, counters) = _gdn_stack(params, cfg, x, None, cache)
    new_cache = dict(cache, k=ks, v=vs, index=cache["index"] + 1, **st)
    h = _gdn_norm(cfg, x, params["final_norm"])
    return new_cache, logits_from_hidden(params, cfg, h), counters


# ---------------------------------------------------------------------------
# decode caches
# ---------------------------------------------------------------------------
def cache_struct(cfg: ModelConfig, batch: int, max_len: int,
                 n_patches: int = 0):
    fam = cfg.family
    dt = cfg.jdtype
    caches: Dict[str, Any] = {}
    if fam == "gdn":
        kv = jax.ShapeDtypeStruct(
            (cfg.n_full_layers, batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt)
        n_lin = cfg.n_layers - cfg.n_full_layers
        caches = {n: jax.ShapeDtypeStruct((n_lin,) + v.shape, v.dtype)
                  for n, v in G.state_struct(cfg, batch).items()}
        caches.update(k=kv, v=kv, index=jax.ShapeDtypeStruct((), jnp.int32))
        return caches
    if fam in ("dense", "moe", "encdec", "vlm", "hybrid"):
        kv = jax.ShapeDtypeStruct(
            (n_attn_slots(cfg), batch, max_len, cfg.n_kv_heads, cfg.head_dim), dt)
        caches["k"] = kv
        caches["v"] = kv
    if fam in ("ssm", "hybrid"):
        per = S.ssm_state_spec(cfg, batch, dt)
        caches["ssm"] = {
            k: jax.ShapeDtypeStruct((cfg.n_layers,) + v.shape, v.dtype)
            for k, v in per.items()
        }
    if fam in ("encdec", "vlm"):
        m = max(1, n_patches or cfg.n_patches)
        caches["memory"] = jax.ShapeDtypeStruct((batch, m, cfg.d_model), dt)
    caches["index"] = jax.ShapeDtypeStruct((), jnp.int32)
    return caches


def zeros_cache(cfg, batch, max_len, ctx=None, n_patches: int = 0):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_struct(cfg, batch, max_len, n_patches))


def cache_pspecs(cfg: ModelConfig, ctx):
    """PartitionSpecs for the decode cache.

    * standard decode: batch on batch axes; KV heads on "model" when they
      divide evenly, otherwise the cache *sequence* shards on "model"
      (flash-decoding split-K: partial softmax + psum — pjit input shardings
      cannot pad, and replicating 32k caches does not fit the big archs);
    * long-context (seq_shard_cache): the sequence dim shards over the batch
      axes — plus "model" too when the KV heads cannot use it; batch (=1) is
      unsharded.
    """
    if ctx is None or ctx.mesh is None:
        return jax.tree.map(lambda s: None, cache_struct(cfg, 1, 1))
    kv_div = bool(cfg.n_kv_heads) and cfg.n_kv_heads % ctx.model_size == 0
    if ctx.seq_shard_cache:
        seq_axes = tuple(ctx.batch_axes) + (() if kv_div else (ctx.model_axis,))
        kv_spec = P(None, None, seq_axes, ctx.model_axis if kv_div else None, None)
    else:
        kv_spec = P(None, ctx.batch_axes,
                    None if kv_div else ctx.model_axis,
                    ctx.model_axis if kv_div else None, None)
    out: Dict[str, Any] = {}
    fam = cfg.family
    if fam in ("dense", "moe", "encdec", "vlm", "hybrid"):
        out["k"] = kv_spec
        out["v"] = kv_spec
    if fam in ("ssm", "hybrid"):
        b_ax = None if ctx.seq_shard_cache else ctx.batch_axes
        inner_ax = ctx.model_axis
        out["ssm"] = {
            "ssm": P(None, b_ax, inner_ax, None, None),
            "conv_x": P(None, b_ax, None, inner_ax),
            "conv_b": P(None, b_ax, None, None),
            "conv_c": P(None, b_ax, None, None),
        }
    if fam in ("encdec", "vlm"):
        out["memory"] = P(None if ctx.seq_shard_cache else ctx.batch_axes, None, None)
    out["index"] = P()
    return out


# ---------------------------------------------------------------------------
# prefill
# ---------------------------------------------------------------------------
def prefill(params, cfg: ModelConfig, batch, ctx=None, max_len: int = 0, *,
            routing: bool = False):
    """Returns ``(cache, last logits)``; with ``routing`` (gdn family only)
    also the routing counters of every expert layer, (n_layers, 3) in
    ``layers.ROUTING_COUNTERS`` order."""
    tokens = batch["tokens"]
    B, Sq = tokens.shape
    max_len = max_len or Sq + 1
    if cfg.family == "gdn":
        out = _gdn_prefill(params, cfg, tokens, ctx, max_len)
        return out if routing else out[:2]
    if routing:
        raise ValueError(f"{cfg.name}: routing counters need a dropless expert family")
    n_patches = 0
    if cfg.family == "vlm":
        n_patches = batch["patches"].shape[1]
    elif cfg.family == "encdec":
        n_patches = batch["enc_input"].shape[1]
    cache = zeros_cache(cfg, B, max_len, ctx, n_patches=n_patches)
    x = embed_lookup(params["embed"]["table"], tokens, ctx)
    positions = jnp.arange(Sq)[None, :]
    flags = layer_flags(cfg)
    fam = cfg.family

    memory = None
    if fam == "encdec":
        memory = _encode(params, cfg, batch["enc_input"], ctx)
        cache["memory"] = memory
    elif fam == "vlm":
        memory = batch["patches"]
        cache["memory"] = memory

    if fam in ("ssm", "hybrid"):
        def body(carry, scanned):
            x, kbuf, vbuf = carry
            bp, fl = scanned
            if fam == "hybrid":
                def do_attn(args):
                    v, kb, vb = args
                    v2, kv = _shared_attn_block(params["shared"], cfg, v, positions)
                    slot = jnp.asarray(fl["attn_slot"], jnp.int32)
                    z = jnp.zeros((), jnp.int32)
                    kb = lax.dynamic_update_slice(
                        kb, kv["k"].astype(kb.dtype)[None], (slot, z, z, z, z))
                    vb = lax.dynamic_update_slice(
                        vb, kv["v"].astype(vb.dtype)[None], (slot, z, z, z, z))
                    return v2, kb, vb
                x, kbuf, vbuf = lax.cond(fl["use_attn"], do_attn,
                                         lambda a: a, (x, kbuf, vbuf))
            h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
            out, st = S.ssm_block(bp["ssm"], cfg, h)
            return (x + out, kbuf, vbuf), st

        kbuf = cache.get("k")
        vbuf = cache.get("v")
        if fam == "ssm":
            kbuf = jnp.zeros((1,), cfg.jdtype)   # dummy carries
            vbuf = jnp.zeros((1,), cfg.jdtype)
        (x, kbuf, vbuf), states = lax.scan(body, (x, kbuf, vbuf),
                                           (params["blocks"], flags))
        cache["ssm"] = states
        if fam == "hybrid":
            # buffers hold the prompt K/V in [:Sq]
            cache["k"], cache["v"] = kbuf, vbuf
    else:
        def body(x, scanned):
            bp, fl = scanned
            x, kv = _self_attn(bp, cfg, x, window=fl["window"],
                               theta=fl["theta"], positions=positions)
            if fam == "vlm":
                x = lax.cond(fl["use_cross"],
                             lambda v: _cross_attn(bp, cfg, v, memory, gated=True),
                             lambda v: v, x)
            if fam == "encdec":
                x = _cross_attn(bp, cfg, x, memory, gated=False)
            return _ffn(bp, cfg, x, ctx), (kv["k"], kv["v"])

        x, (ks, vs) = lax.scan(body, x, (params["blocks"], flags))
        cache["k"] = lax.dynamic_update_slice(
            cache["k"], ks.astype(cache["k"].dtype), (0, 0, 0, 0, 0))
        cache["v"] = lax.dynamic_update_slice(
            cache["v"], vs.astype(cache["v"].dtype), (0, 0, 0, 0, 0))

    cache["index"] = jnp.int32(Sq)
    h = L.rmsnorm(x[:, -1:], params["final_norm"], cfg.norm_eps)
    return cache, logits_from_hidden(params, cfg, h)


# ---------------------------------------------------------------------------
# decode
# ---------------------------------------------------------------------------
def decode_step(params, cfg: ModelConfig, cache, tokens: jnp.ndarray, ctx=None, *,
                routing: bool = False):
    """One decode step.  tokens: (B, 1).  Returns (new_cache, logits), and
    the routing counters with ``routing`` (see :func:`prefill`)."""
    if cfg.family == "gdn":
        out = _gdn_decode(params, cfg, cache, tokens, ctx)
        return out if routing else out[:2]
    if routing:
        raise ValueError(f"{cfg.name}: routing counters need a dropless expert family")
    x = embed_lookup(params["embed"]["table"], tokens, ctx)
    idx = cache["index"]
    fam = cfg.family
    flags = layer_flags(cfg)
    new_cache = dict(cache)

    if fam in ("ssm", "hybrid"):
        def body(carry, scanned):
            x, kbuf, vbuf = carry
            bp, fl, st = scanned
            if fam == "hybrid":
                def do_attn(args):
                    v, kb, vb = args
                    slot = jnp.asarray(fl["attn_slot"], jnp.int32)
                    z = jnp.zeros((), jnp.int32)
                    ck = lax.dynamic_index_in_dim(kb, slot, 0, keepdims=False)
                    cv = lax.dynamic_index_in_dim(vb, slot, 0, keepdims=False)
                    v2, kv = _shared_attn_block(params["shared"], cfg, v, None,
                                                cache={"k": ck, "v": cv},
                                                cache_index=idx)
                    kb = lax.dynamic_update_slice(kb, kv["k"][None],
                                                  (slot, z, z, z, z))
                    vb = lax.dynamic_update_slice(vb, kv["v"][None],
                                                  (slot, z, z, z, z))
                    return v2, kb, vb
                x, kbuf, vbuf = lax.cond(fl["use_attn"], do_attn,
                                         lambda a: a, (x, kbuf, vbuf))
            h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
            out, new_st = S.ssm_block(bp["ssm"], cfg, h, state=st)
            return (x + out, kbuf, vbuf), new_st

        kbuf = cache.get("k") if fam == "hybrid" else jnp.zeros((1,), cfg.jdtype)
        vbuf = cache.get("v") if fam == "hybrid" else jnp.zeros((1,), cfg.jdtype)
        (x, kbuf, vbuf), new_states = lax.scan(
            body, (x, kbuf, vbuf), (params["blocks"], flags, cache["ssm"]))
        new_cache["ssm"] = new_states
        if fam == "hybrid":
            new_cache["k"], new_cache["v"] = kbuf, vbuf
    else:
        memory = cache.get("memory")

        def body(x, scanned):
            bp, fl, ck, cv = scanned
            h = L.rmsnorm(x, bp["ln1"], cfg.norm_eps)
            out, upd = L.attention(bp["attn"], cfg, h, window=fl["window"],
                                   theta=fl["theta"],
                                   cache={"k": ck, "v": cv}, cache_index=idx)
            x = x + out
            if fam == "vlm":
                x = lax.cond(fl["use_cross"],
                             lambda v: _cross_attn(bp, cfg, v, memory, gated=True),
                             lambda v: v, x)
            if fam == "encdec":
                x = _cross_attn(bp, cfg, x, memory, gated=False)
            return _ffn(bp, cfg, x, ctx), (upd["k"], upd["v"])

        x, (nk, nv) = lax.scan(body, x, (params["blocks"], flags,
                                         cache["k"], cache["v"]))
        new_cache["k"], new_cache["v"] = nk, nv

    new_cache["index"] = idx + 1
    h = L.rmsnorm(x, params["final_norm"], cfg.norm_eps)
    return new_cache, logits_from_hidden(params, cfg, h)
