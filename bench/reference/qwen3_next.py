"""Seeded weights and a plain float32 forward pass of Qwen3-Next: Gated
DeltaNet layers with a gated full-attention layer every
``full_attention_interval``-th, and an expert layer in every layer.

Nothing here imports the program.  The weights are drawn leaf by leaf from
the seed, as ``dense_lm.py`` draws them: leaf ``name`` of layer ``l`` (the
layer's place in the whole stack) comes from
``fold_in(fold_in(key, LEAF_IDS[name]), l)``, and expert ``e`` (its number
among all ``router_experts``) of an expert leaf from one more
``fold_in(..., e)``; matrices are uniform with the standard deviation of
their fan-in, rounded to the served dtype.

The layer follows ``Qwen3NextDecoderLayer`` in Hugging Face
``transformers`` (``modeling_qwen3_next.py``), with ``norm(x) = x /
sqrt(mean(x^2) + eps) * (1 + w)`` (zero-centred):

    h = x + mixer(norm1(x));   y = h + moe(norm2(h))

* gated attention: per head ``[q | g]`` from one projection, k and v;
  zero-centred RMSNorm over each q and k head; rotate-half RoPE with base
  ``rope_theta`` on the first ``partial_rotary_factor`` of each head;
  causal softmax attention scaled by ``1/sqrt(head_dim)`` with grouped K/V
  heads; ``(o * sigmoid(g)) W_o``;
* Gated DeltaNet: ``[q, k, v, z] = x W_qkvz`` (in that order), ``[b, a] =
  x W_ba``; ``silu`` of a causal depthwise convolution of width
  ``linear_conv_kernel_dim`` over ``[q, k, v]`` (no bias); q, k divided by
  their L2 norms, q by ``sqrt(dk)``; value head ``j`` reads key head ``j //
  (Hv/Hk)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a +
  dt_bias)``; the token recurrence ``S' = exp(g) S; S = S' + k (beta (v -
  S'^T k))^T; o = S^T q`` with S in float32, one token at a time (not the
  chunked form the program uses); then RMSNorm of o per head with a scale
  that is not zero-centred, times ``silu(z)``, and ``W_out``;
* experts: softmax over all ``router_experts`` in float32, top
  ``num_experts_per_tok``, renormalised (``norm_topk_prob``); the routed
  part sums, over the held experts ``[expert_offset, + num_experts)`` only,
  ``p_e W_d,e(silu(W_g,e x) * W_u,e x)``: what experts held on other chips
  would add is left out, as in the program; plus the shared expert (the
  same SwiGLU) times ``sigmoid(x w_sg)``.

Departures from the published model: no multi-token-prediction head; the
fused projections' layout is this file's own (only the per-leaf
mathematics is fixed).

It runs in float32 with matrix products at ``highest`` precision, one
sequence and one layer at a time; the expert part computes every held
expert on every token and weights it by the token's routing (zero where
the token did not choose it).  ``mode="fp8"`` is the control, computed as
``dense_lm.py``'s is: every weight matrix (projections, router, experts,
embedding and head) rounded to float8 e4m3 with one scale per output
column, and every matrix product's activations to float8 with one scale
per token.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import dense_lm
from bench.reference.dense_lm import _fp8, _fp8_rows, _served

_GLOBAL = ("embed", "final_norm", "unembed")
_MIXER_LIN = ("w_qkvz", "w_ba", "conv", "A_log", "dt_bias", "lin_norm", "w_out")
_MIXER_FULL = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")
_MOE = ("router", "wg", "wu", "wd", "shared_wg", "shared_wu", "shared_wd", "shared_gate")
LEAF_IDS = {n: i for i, n in enumerate(_GLOBAL + ("ln1", "ln2") + _MIXER_LIN
                                       + _MIXER_FULL + _MOE)}
#: norm scales applied as (1 + w): drawn as 0.2 U(-1, 1)
ZERO_CENTRED = ("ln1", "ln2", "final_norm", "q_norm", "k_norm")
#: the one scale applied as w: drawn as 1 + 0.2 U(-1, 1)
PLAIN_NORMS = ("lin_norm",)
EXPERT_LEAVES = ("wg", "wu", "wd")
#: leaves that are no matrix product's weight: never rounded by the control
_NOT_MATRICES = ZERO_CENTRED + PLAIN_NORMS + ("conv", "A_log", "dt_bias")


def is_full(cfg: Mapping, layer: int) -> bool:
    return (layer + 1) % int(cfg["full_attention_interval"]) == 0


def layers_of(cfg: Mapping, full: bool) -> List[int]:
    return [l for l in range(int(cfg["num_hidden_layers"])) if is_full(cfg, l) == full]


def layer_leaves(full: bool) -> Tuple[str, ...]:
    return ("ln1", "ln2") + (_MIXER_FULL if full else _MIXER_LIN) + _MOE


def shapes(cfg: Mapping) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape of one layer's leaf, fan-in); an expert leaf's shape
    is one expert's."""
    d, v = int(cfg["hidden_size"]), int(cfg["vocab_size"])
    h, kv, hd = (int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"]),
                 int(cfg["head_dim"]))
    hk, hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
    dk, dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
    conv_dim = 2 * hk * dk + hv * dv
    f, fs = int(cfg["moe_intermediate_size"]), int(cfg["shared_expert_intermediate_size"])
    e = int(cfg["router_experts"])
    out = {
        "embed": ((v, d), 1), "final_norm": ((d,), 0), "unembed": ((d, v), d),
        "ln1": ((d,), 0), "ln2": ((d,), 0),
        "w_qkvz": ((d, 2 * hk * dk + 2 * hv * dv), d), "w_ba": ((d, 2 * hv), d),
        "conv": ((int(cfg["linear_conv_kernel_dim"]), conv_dim),
                 int(cfg["linear_conv_kernel_dim"])),
        "A_log": ((hv,), 0), "dt_bias": ((hv,), 0), "lin_norm": ((dv,), 0),
        "w_out": ((hv * dv, d), hv * dv),
        "wq": ((d, 2 * h * hd), d), "wk": ((d, kv * hd), d), "wv": ((d, kv * hd), d),
        "wo": ((h * hd, d), h * hd), "q_norm": ((hd,), 0), "k_norm": ((hd,), 0),
        "router": ((d, e), d), "wg": ((d, f), d), "wu": ((d, f), d), "wd": ((f, d), f),
        "shared_wg": ((d, fs), d), "shared_wu": ((d, fs), d), "shared_wd": ((fs, d), fs),
        "shared_gate": ((d, 1), d),
    }
    if bool(cfg["tie_word_embeddings"]):
        del out["unembed"]
    return out


def held(cfg: Mapping) -> Tuple[int, int]:
    """(first expert held, experts held) of each layer."""
    return int(cfg["expert_offset"]), int(cfg["num_experts"])


def draw(key: jax.Array, name: str, layer, shape, fan_in: int, dtype,
         expert=None) -> jax.Array:
    """One leaf of one layer (of one expert, for an expert leaf)."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    if expert is not None:
        k = jax.random.fold_in(k, expert)
    u = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
    if name in ZERO_CENTRED:
        return (0.2 * u).astype(dtype)
    if name in PLAIN_NORMS:
        return (1.0 + 0.2 * u).astype(dtype)
    if name == "A_log":          # A log-uniform on [0.05, 1]: memory of tens of tokens
        return (0.5 * (u - 1.0) * math.log(20.0)).astype(dtype)
    if name == "dt_bias":
        return u.astype(dtype)
    return (u * (math.sqrt(3.0) / math.sqrt(fan_in))).astype(dtype)


def draw_layer(key, cfg: Mapping, layer, full: bool, dtype) -> Dict[str, jax.Array]:
    """Every leaf of one layer; an expert leaf stacks the held experts."""
    offset, n = held(cfg)
    out = {}
    for name in layer_leaves(full):
        shape, fan_in = shapes(cfg)[name]
        if name in EXPERT_LEAVES:
            out[name] = jax.vmap(lambda e, name=name, shape=shape, fan_in=fan_in: draw(
                key, name, layer, shape, fan_in, dtype, e))(offset + jnp.arange(n))
        else:
            out[name] = draw(key, name, layer, shape, fan_in, dtype)
    return out


def draw_all(key: jax.Array, cfg: Mapping, dtype) -> Dict[str, object]:
    """Every leaf: the global ones, and ``lin``/``full``, each a dict of
    leaves stacked over that kind's layers in order.  Call under
    ``jax.jit``."""
    out: Dict[str, object] = {}
    for name in _GLOBAL:
        if name in shapes(cfg):
            shape, fan_in = shapes(cfg)[name]
            out[name] = draw(key, name, 0, shape, fan_in, dtype)
    for kind, full in (("lin", False), ("full", True)):
        ls = jnp.asarray(layers_of(cfg, full))
        out[kind] = jax.vmap(lambda l, full=full: draw_layer(key, cfg, l, full, dtype))(ls)
    return out


def _rms(x, w, eps, zero_centred=True):
    return dense_lm._rms(x, (1.0 + w) if zero_centred else w, eps)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
               "vocab_size", "num_hidden_layers", "rms_norm_eps", "rope_theta",
               "partial_rotary_factor", "full_attention_interval", "linear_num_key_heads",
               "linear_num_value_heads", "linear_key_head_dim", "linear_value_head_dim",
               "linear_conv_kernel_dim", "num_experts", "router_experts", "expert_offset",
               "num_experts_per_tok", "moe_intermediate_size",
               "shared_expert_intermediate_size", "norm_topk_prob", "torch_dtype",
               "tie_word_embeddings")


@functools.lru_cache(maxsize=8)
def _pieces(cfg_items: Tuple, mode: str, q_block: int) -> "_Pieces":
    return _Pieces(dict(cfg_items), mode, q_block)


class Reference:
    """The float32 forward pass of one configuration over the weights of one
    seed, in blocks that fit one chip (API of ``dense_lm.Reference``)."""

    def __init__(self, cfg: Mapping, seed: int, *, mode: str = "f32", q_block: int = 512):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.key = dense_lm.seed_key(seed)
        self.pieces = _pieces(tuple((k, cfg[k]) for k in _SHAPE_KEYS), mode, q_block)

    def hidden(self, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
               length: int) -> List[jax.Array]:
        """The final hidden state at ``rows[i]`` of sequence ``i``, each
        padded at its end to ``length`` (a multiple of the query block)."""
        p = self.pieces
        xs = []
        for s in seqs:
            toks = np.zeros(length, np.int32)
            toks[:len(s)] = s
            xs.append(p.embed(self.key, jnp.asarray(toks)))
        for layer in range(p.n_layers):
            full = p.is_full(layer)
            w = p.layer_weights[full](self.key, jnp.int32(layer))
            xs = [p.layer[full](w, x) for x in xs]
            del w
        return [p.final(self.key, x, jnp.asarray(r, jnp.int32)) for x, r in zip(xs, rows)]

    def head(self, hidden: jax.Array) -> jax.Array:
        return self.pieces.head(self.key, hidden)

    def logits(self, seqs, rows, length) -> List[jax.Array]:
        return [self.head(h) for h in self.hidden(seqs, rows, length)]

    def moe(self, layer: int, x: jax.Array) -> jax.Array:
        """The expert part of layer ``layer`` (routed over the held experts,
        plus the gated shared expert) on rows ``x`` (already normed)."""
        p = self.pieces
        full = p.is_full(layer)
        return p.moe_fn(p.layer_weights[full](self.key, jnp.int32(layer)), x)


class _Pieces:
    """The jitted pieces of the pass for one configuration and mode."""

    def __init__(self, cfg: Mapping, mode: str, q_block: int):
        self.cfg = cfg
        self.mode = mode
        self.q_block = q_block
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.shapes = shapes(cfg)
        self.n_layers = int(cfg["num_hidden_layers"])
        self.heads, self.kv_heads = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg["head_dim"])
        self.rot = int(self.head_dim * float(cfg["partial_rotary_factor"]))
        self.hk, self.hv = int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"])
        self.dk, self.dv = int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"])
        self.width = int(cfg["linear_conv_kernel_dim"])
        self.top_k = int(cfg["num_experts_per_tok"])
        self.offset, self.n_held = held(cfg)
        self.norm_topk = bool(cfg["norm_topk_prob"])
        self.layer_weights = {f: jax.jit(functools.partial(self._layer_weights, full=f))
                              for f in (False, True)}
        self.layer = {False: jax.jit(self._lin_layer), True: jax.jit(self._full_layer)}
        self.moe_fn = jax.jit(self._moe_part)
        self.embed = jax.jit(self._embed_fn)
        self.final = jax.jit(self._final_fn)
        self.head = jax.jit(self._head_fn)

    def is_full(self, layer: int) -> bool:
        return is_full(self.cfg, layer)

    # -- weights, as served, in float32 --------------------------------
    def _round(self, name, w):
        w = _served(w, self.dtype)
        if self.mode == "fp8" and name not in _NOT_MATRICES:
            w = jax.vmap(_fp8)(w) if name in EXPERT_LEAVES else _fp8(w)
        return w

    def _layer_weights(self, key, layer, full):
        w = draw_layer(key, self.cfg, layer, full, self.dtype)
        return {name: self._round(name, v) for name, v in w.items()}

    def _global(self, key, name):
        shape, fan_in = self.shapes[name]
        return self._round(name, draw(key, name, 0, shape, fan_in, self.dtype))

    def _mm(self, x, w):
        if self.mode == "fp8":
            x = _fp8_rows(x)
        return x @ w

    # -- the pieces ------------------------------------------------------
    def _embed_fn(self, key, tokens):
        return self._global(key, "embed")[tokens]

    def _moe_part(self, w, h):
        with jax.default_matmul_precision("highest"):
            probs = jax.nn.softmax(self._mm(h, w["router"]), axis=-1)
            top, ids = jax.lax.top_k(probs, self.top_k)
            if self.norm_topk:
                top = top / jnp.sum(top, -1, keepdims=True)
            local = ids - self.offset
            # (L, held): a token's weight on each held expert, zero where not chosen
            route = jnp.sum(jnp.where(local[..., None] == jnp.arange(self.n_held),
                                      top[..., None], 0.0), axis=1)

            def one(y, e):
                wg, wu, wd, r = e
                g = jax.nn.silu(self._mm(h, wg)) * self._mm(h, wu)
                return y + r[:, None] * self._mm(g, wd), None

            y, _ = jax.lax.scan(one, jnp.zeros_like(h),
                                (w["wg"], w["wu"], w["wd"], route.T))
            shared = self._mm(jax.nn.silu(self._mm(h, w["shared_wg"]))
                              * self._mm(h, w["shared_wu"]), w["shared_wd"])
            return y + jax.nn.sigmoid(self._mm(h, w["shared_gate"])) * shared

    def _ffn(self, w, x):
        return x + self._moe_part(w, _rms(x, w["ln2"], self.eps))

    def _full_layer(self, w, x):
        with jax.default_matmul_precision("highest"):
            seq, hd = x.shape[0], self.head_dim
            h = _rms(x, w["ln1"], self.eps)
            qg = self._mm(h, w["wq"]).reshape(seq, self.heads, 2 * hd)
            q, gate = qg[..., :hd], qg[..., hd:]
            k = self._mm(h, w["wk"]).reshape(seq, self.kv_heads, hd)
            v = self._mm(h, w["wv"]).reshape(seq, self.kv_heads, hd)
            q, k = _rms(q, w["q_norm"], self.eps), _rms(k, w["k_norm"], self.eps)
            r = self.rot
            q = jnp.concatenate([dense_lm._rope(q[..., :r], self.theta), q[..., r:]], -1)
            k = jnp.concatenate([dense_lm._rope(k[..., :r], self.theta), k[..., r:]], -1)
            a = dense_lm._attention(q, k, v, min(self.q_block, seq))
            a = a * jax.nn.sigmoid(gate)
            return self._ffn(w, x + self._mm(a.reshape(seq, -1), w["wo"]))

    def _lin_layer(self, w, x):
        with jax.default_matmul_precision("highest"):
            seq = x.shape[0]
            hk, hv, dk, dv = self.hk, self.hv, self.dk, self.dv
            h = _rms(x, w["ln1"], self.eps)
            proj = self._mm(h, w["w_qkvz"])
            ba = self._mm(h, w["w_ba"])
            qkv, z = proj[:, :2 * hk * dk + hv * dv], proj[:, 2 * hk * dk + hv * dv:]
            padded = jnp.pad(qkv, ((self.width - 1, 0), (0, 0)))
            conv = sum(padded[i:i + seq] * w["conv"][i] for i in range(self.width))
            conv = jax.nn.silu(conv)
            q = conv[:, :hk * dk].reshape(seq, hk, dk)
            k = conv[:, hk * dk:2 * hk * dk].reshape(seq, hk, dk)
            v = conv[:, 2 * hk * dk:].reshape(seq, hv, dv)
            q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(dk)
            k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
            head_of = jnp.arange(hv) // (hv // hk)
            q, k = q[:, head_of], k[:, head_of]
            beta = jax.nn.sigmoid(ba[:, :hv])
            g = -jnp.exp(w["A_log"]) * jax.nn.softplus(ba[:, hv:] + w["dt_bias"])

            def token(s, t):
                qt, kt, vt, gt, bt = t
                s = s * jnp.exp(gt)[:, None, None]
                kv_mem = jnp.einsum("hkv,hk->hv", s, kt)
                s = s + jnp.einsum("hk,hv->hkv", kt, (vt - kv_mem) * bt[:, None])
                return s, jnp.einsum("hkv,hk->hv", s, qt)

            _, o = jax.lax.scan(token, jnp.zeros((hv, dk, dv), jnp.float32),
                                (q, k, v, g, beta))
            o = _rms(o, w["lin_norm"], self.eps, zero_centred=False)
            o = o * jax.nn.silu(z.reshape(seq, hv, dv))
            return self._ffn(w, x + self._mm(o.reshape(seq, -1), w["w_out"]))

    def _final_fn(self, key, x, rows):
        return _rms(x[rows], self._global(key, "final_norm"), self.eps)

    def _head_fn(self, key, h):
        with jax.default_matmul_precision("highest"):
            if "unembed" in self.shapes:
                return self._mm(h, self._global(key, "unembed"))
            return self._mm(h, self._global(key, "embed").T)


positions = dense_lm.positions
gaps = dense_lm.gaps
top_tokens = dense_lm.top_tokens
