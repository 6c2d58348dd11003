"""Seeded weights and a plain float32 forward pass of a dense decoder with
grouped-query attention (Qwen3, Llama and their kin).

Nothing here imports the program.  The weights are drawn leaf by leaf from
the seed: leaf ``name`` of layer ``l`` comes from
``fold_in(fold_in(key, LEAF_IDS[name]), l)``, uniform with the standard
deviation of its fan-in, rounded to the served dtype.  The benchmark draws
them all in one jitted call for the program; the reference draws one layer
at a time, the same values, when it needs them.

The forward pass follows the published decoder layer (``Qwen3DecoderLayer``
and ``LlamaDecoderLayer`` in Hugging Face ``transformers``):

    h = x + o(attn(rope(qnorm(q(rms1(x)))), rope(knorm(k(rms1(x)))), v(rms1(x))))
    y = h + down(silu(gate(rms2(h))) * up(rms2(h)))

with RMSNorm ``w * x / sqrt(mean(x^2) + eps)`` in float32, q/k RMSNorm over
each head where the configuration's ``qk_norm`` is true (Qwen3; Llama has
none), rotate-half RoPE with base ``rope_theta``, causal attention scaled
by ``1/sqrt(head_dim)`` with grouped K/V heads, a final RMSNorm, and a head
that is the embedding's transpose where ``tie_word_embeddings`` is true.
It runs in float32 with matrix products at ``highest`` precision, one
sequence and one layer at a time, so that it fits beside nothing on one
chip.

``mode="fp8"`` is the control, the pass computed in float8: every weight
matrix (embedding and head included) rounded to float8 e4m3 with one scale
per output column, and every matrix product's activations rounded to
float8 e4m3 with one scale per token, accumulated in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import seed_key

_ALL_LAYER_LEAVES = ("ln1", "wq", "wk", "wv", "wo", "gamma_q", "gamma_k", "ln2",
                     "wg", "wu", "wd")
_ALL_GLOBAL_LEAVES = ("embed", "final_norm", "unembed")
LEAF_IDS = {name: i for i, name in enumerate(_ALL_GLOBAL_LEAVES + _ALL_LAYER_LEAVES)}
NORMS = ("ln1", "ln2", "gamma_q", "gamma_k", "final_norm")
_FP8_MAX = 448.0


def layer_leaves(cfg: Mapping) -> Tuple[str, ...]:
    """The leaves of one layer; the q/k norms only where ``qk_norm``."""
    qk = bool(cfg["qk_norm"])
    return tuple(n for n in _ALL_LAYER_LEAVES if qk or n not in ("gamma_q", "gamma_k"))


def shapes(cfg: Mapping) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """leaf -> (shape of one layer's leaf, fan-in); norms have fan-in 0.
    No ``unembed`` where the head is tied to the embedding."""
    d = int(cfg["hidden_size"])
    h, kv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    hd, f, v = int(cfg["head_dim"]), int(cfg["intermediate_size"]), int(cfg["vocab_size"])
    out = {
        "embed": ((v, d), 1), "final_norm": ((d,), 0), "unembed": ((d, v), d),
        "ln1": ((d,), 0), "wq": ((d, h * hd), d), "wk": ((d, kv * hd), d),
        "wv": ((d, kv * hd), d), "wo": ((h * hd, d), h * hd),
        "gamma_q": ((hd,), 0), "gamma_k": ((hd,), 0), "ln2": ((d,), 0),
        "wg": ((d, f), d), "wu": ((d, f), d), "wd": ((f, d), f),
    }
    keep = set(layer_leaves(cfg)) | {"embed", "final_norm"}
    if not bool(cfg["tie_word_embeddings"]):
        keep.add("unembed")
    return {k: s for k, s in out.items() if k in keep}


def draw(key: jax.Array, name: str, layer, shape, fan_in: int, dtype) -> jax.Array:
    """One leaf of one layer.  Norm scales are ``1 + U(-0.2, 0.2)`` (not 1,
    so that a norm left out shows); matrices are uniform with standard
    deviation ``1/sqrt(fan_in)``."""
    k = jax.random.fold_in(jax.random.fold_in(key, LEAF_IDS[name]), layer)
    u = jax.random.uniform(k, shape, jnp.float32, -1.0, 1.0)
    if name in NORMS:
        return (1.0 + 0.2 * u).astype(dtype)
    return (u * (math.sqrt(3.0) / math.sqrt(fan_in))).astype(dtype)


def draw_all(key: jax.Array, cfg: Mapping, dtype) -> Dict[str, jax.Array]:
    """Every leaf; per-layer leaves stacked on a leading layer axis.  Call
    under ``jax.jit`` so that no float32 copy of the model is made."""
    n = int(cfg["num_hidden_layers"])
    per_layer = layer_leaves(cfg)
    out = {}
    for name, (shape, fan_in) in shapes(cfg).items():
        if name in per_layer:
            out[name] = jax.vmap(
                lambda l, name=name, shape=shape, fan_in=fan_in:
                draw(key, name, l, shape, fan_in, dtype))(jnp.arange(n))
        else:
            out[name] = draw(key, name, 0, shape, fan_in, dtype)
    return out


def _served(x: jax.Array, dtype) -> jax.Array:
    return x.astype(dtype).astype(jnp.float32)


def _fp8(w: jax.Array) -> jax.Array:
    """Round a float32 matrix to float8 e4m3 with one scale per output
    column (the last axis), and back."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / _FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _fp8_rows(x: jax.Array) -> jax.Array:
    """Round activations to float8 e4m3 with one scale per row (token),
    and back."""
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True) / _FP8_MAX
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (L, H, hd), positions 0..L-1, rotate-half convention."""
    seq, _, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / hd)
    ang = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, q_block: int):
    """Causal attention, q (L, H, hd), k/v (L, KV, hd), in blocks of
    queries so that the scores of a long sequence fit."""
    seq, heads, hd = q.shape
    rep = heads // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    nb = seq // q_block
    qb = q.reshape(nb, q_block, heads, hd)
    kpos = jnp.arange(seq)

    def block(args):
        i, qi = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        qpos = i * q_block + jnp.arange(q_block)
        s = jnp.where(qpos[None, :, None] >= kpos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    return jax.lax.map(block, (jnp.arange(nb), qb)).reshape(seq, heads, hd)


_SHAPE_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
               "intermediate_size", "vocab_size", "num_hidden_layers", "rms_norm_eps",
               "rope_theta", "torch_dtype", "qk_norm", "tie_word_embeddings")


@functools.lru_cache(maxsize=8)
def _pieces(cfg_items: Tuple, mode: str, q_block: int) -> "_Pieces":
    return _Pieces(dict(cfg_items), mode, q_block)


class Reference:
    """The float32 forward pass of one configuration, over the weights of
    one seed, in blocks that fit one chip.  The jitted pieces are shared by
    every seed of a configuration."""

    def __init__(self, cfg: Mapping, seed: int, *, mode: str = "f32", q_block: int = 512):
        if mode not in ("f32", "fp8"):
            raise ValueError(f"unknown reference mode {mode!r}")
        self.key = seed_key(seed)
        self.pieces = _pieces(tuple((k, cfg[k]) for k in _SHAPE_KEYS), mode, q_block)

    def hidden(self, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
               length: int) -> List[jax.Array]:
        """The final hidden state at ``rows[i]`` of sequence ``i``, each
        sequence padded at its end to ``length`` (a multiple of the query
        block; padding after a position cannot reach it under the causal
        mask)."""
        p = self.pieces
        xs = []
        for s in seqs:
            toks = np.zeros(length, np.int32)
            toks[:len(s)] = s
            xs.append(p.embed(self.key, jnp.asarray(toks)))
        for layer in range(p.n_layers):
            w = p.layer_weights(self.key, jnp.int32(layer))
            xs = [p.layer(w, x) for x in xs]
            del w
        return [p.final(self.key, x, jnp.asarray(r, jnp.int32)) for x, r in zip(xs, rows)]

    def head(self, hidden: jax.Array) -> jax.Array:
        """Logits over the vocabulary of rows of :meth:`hidden`."""
        return self.pieces.head(self.key, hidden)

    def logits(self, seqs: Sequence[np.ndarray], rows: Sequence[np.ndarray],
               length: int) -> List[jax.Array]:
        """Logits at ``rows[i]`` of sequence ``i`` (see :meth:`hidden`)."""
        return [self.head(h) for h in self.hidden(seqs, rows, length)]


class _Pieces:
    """The jitted pieces of the pass for one configuration and mode."""

    def __init__(self, cfg: Mapping, mode: str, q_block: int):
        self.mode = mode
        self.q_block = q_block
        self.dtype = jnp.dtype(cfg["torch_dtype"])
        self.eps = float(cfg["rms_norm_eps"])
        self.theta = float(cfg["rope_theta"])
        self.shapes = shapes(cfg)
        self.layer_leaves = layer_leaves(cfg)
        self.qk_norm = bool(cfg["qk_norm"])
        self.n_layers = int(cfg["num_hidden_layers"])
        self.heads = int(cfg["num_attention_heads"])
        self.kv_heads = int(cfg["num_key_value_heads"])
        self.head_dim = int(cfg["head_dim"])
        self.layer_weights = jax.jit(self._layer_weights)
        self.layer = jax.jit(self._layer_fn)
        self.embed = jax.jit(self._embed_fn)
        self.final = jax.jit(self._final_fn)
        self.head = jax.jit(self._head_fn)

    # -- weights, as served, in float32 --------------------------------
    def _leaf(self, key, name, layer):
        shape, fan_in = self.shapes[name]
        w = _served(draw(key, name, layer, shape, fan_in, self.dtype), self.dtype)
        if self.mode == "fp8" and name not in NORMS:
            w = _fp8(w)
        return w

    def _layer_weights(self, key, layer):
        return {name: self._leaf(key, name, layer) for name in self.layer_leaves}

    # -- the pieces ------------------------------------------------------
    def _embed_fn(self, key, tokens):
        return self._leaf(key, "embed", 0)[tokens]

    def _mm(self, x, w):
        """A matrix product; the control rounds its activations to float8
        too, so that the whole product is computed in float8."""
        if self.mode == "fp8":
            x = _fp8_rows(x)
        return x @ w

    def _layer_fn(self, w, x):
        with jax.default_matmul_precision("highest"):
            seq = x.shape[0]
            h = _rms(x, w["ln1"], self.eps)
            q = self._mm(h, w["wq"]).reshape(seq, self.heads, self.head_dim)
            k = self._mm(h, w["wk"]).reshape(seq, self.kv_heads, self.head_dim)
            v = self._mm(h, w["wv"]).reshape(seq, self.kv_heads, self.head_dim)
            if self.qk_norm:
                q = _rms(q, w["gamma_q"], self.eps)
                k = _rms(k, w["gamma_k"], self.eps)
            q, k = _rope(q, self.theta), _rope(k, self.theta)
            a = _attention(q, k, v, min(self.q_block, seq))
            x = x + self._mm(a.reshape(seq, -1), w["wo"])
            h = _rms(x, w["ln2"], self.eps)
            g = jax.nn.silu(self._mm(h, w["wg"])) * self._mm(h, w["wu"])
            return x + self._mm(g, w["wd"])

    def _final_fn(self, key, x, rows):
        return _rms(x[rows], self._leaf(key, "final_norm", 0), self.eps)

    def _head_fn(self, key, h):
        with jax.default_matmul_precision("highest"):
            if "unembed" in self.shapes:
                return self._mm(h, self._leaf(key, "unembed", 0))
            return self._mm(h, self._leaf(key, "embed", 0).T)


def positions(prompt_len: int, n_served: int, rows: int) -> np.ndarray:
    """The positions whose logits chose served tokens 0..n_served-1, padded
    with the last one to a fixed count ``rows``."""
    pos = np.arange(prompt_len - 1, prompt_len - 1 + n_served)
    return np.concatenate([pos, np.full(rows - n_served, pos[-1])]).astype(np.int32)


@jax.jit
def _gaps(ref_logits, tokens):
    best = jnp.max(ref_logits, axis=-1)
    return best - jnp.take_along_axis(ref_logits, tokens[:, None], axis=-1)[:, 0]


def gaps(ref_logits: jax.Array, tokens: np.ndarray) -> np.ndarray:
    """By how much each token's reference logit lies below the reference's
    best at its position (rows past ``len(tokens)`` are padding)."""
    n = len(tokens)
    padded = np.zeros(ref_logits.shape[0], np.int32)
    padded[:n] = tokens
    return np.asarray(_gaps(ref_logits, jnp.asarray(padded)), np.float64)[:n]


def top_tokens(logits: jax.Array, n: int) -> np.ndarray:
    """The token each row puts first, for the first ``n`` rows."""
    return np.asarray(jnp.argmax(logits, axis=-1), np.int64)[:n]
