"""The least work a step needs, from a configuration's shapes, and the
chip's peaks.

Everything here counts what the mathematics requires, whatever implements
it: a decode step over ``k`` lanes must read every weight once and each
lane's valid KV entries; a prefill of ``L`` tokens must do the matrix
products of ``L`` tokens, causal attention over them, and the head on the
last position.  A roofline share built on these numbers cannot pass 100%
unless the device time leaves part of the work out.

The configuration is the benchmark's own JSON (Hugging Face key names), so
nothing here depends on the program's model code.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Mapping

#: Published peaks of one chip, keyed by ``jax.Device.device_kind``.
#: Source: Google Cloud documentation, "TPU v5e" (system architecture):
#: 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2 at 819 GB/s.
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


class UnknownDevice(KeyError):
    """A device kind with no published peak in :data:`PEAKS`."""


def peaks(device_kind: str) -> Dict[str, float]:
    """The peaks of ``device_kind``; an unknown kind raises, never defaults."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"no published peak for device kind {device_kind!r}; known: "
            f"{sorted(PEAKS)}") from None


_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


@dataclasses.dataclass(frozen=True)
class LMShapes:
    """The shapes of a dense decoder with grouped-query attention."""

    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int
    qk_norm: bool = True
    tied: bool = False

    @classmethod
    def from_config(cls, cfg: Mapping) -> "LMShapes":
        return cls(layers=int(cfg["num_hidden_layers"]),
                   d_model=int(cfg["hidden_size"]),
                   heads=int(cfg["num_attention_heads"]),
                   kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["head_dim"]),
                   d_ff=int(cfg["intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   dtype_bytes=_DTYPE_BYTES[cfg["torch_dtype"]],
                   qk_norm=bool(cfg["qk_norm"]),
                   tied=bool(cfg["tie_word_embeddings"]))

    # -- parameters ----------------------------------------------------
    @property
    def layer_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        attn = d * self.heads * hd + 2 * d * self.kv_heads * hd + self.heads * hd * d
        return attn + 3 * d * self.d_ff

    @property
    def layer_params(self) -> int:
        """Matrices plus the two RMSNorm scales and any q/k-norm scales."""
        qk = 2 * self.head_dim if self.qk_norm else 0
        return self.layer_matmul_params + 2 * self.d_model + qk

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def params(self) -> int:
        """Embedding, layers, final norm and the head unless it is tied."""
        return (self.vocab * self.d_model + self.layers * self.layer_params
                + self.d_model + (0 if self.tied else self.head_params))

    @property
    def kv_bytes_per_token(self) -> int:
        """K and V of one position in every layer."""
        return 2 * self.layers * self.kv_heads * self.head_dim * self.dtype_bytes

    # -- work ----------------------------------------------------------
    def attn_flops(self, q_positions: Iterable[int]) -> float:
        """QK^T and PV for queries that each see ``c`` keys (their own
        position included), over every layer."""
        keys = sum(q_positions)
        return 4.0 * self.heads * self.head_dim * keys * self.layers

    def prefill_flops(self, length: int) -> float:
        """Matrix products of ``length`` tokens, causal attention, and the
        head on the last position."""
        causal_keys = length * (length + 1) // 2
        return (2.0 * self.layer_matmul_params * self.layers * length
                + 4.0 * self.heads * self.head_dim * causal_keys * self.layers
                + 2.0 * self.head_params)

    def _embed_rows(self, n: int) -> int:
        """Embedding values read for ``n`` tokens; none where the head,
        read whole anyway, is the same table."""
        return 0 if self.tied else n * self.d_model

    def prefill_bytes(self, length: int) -> float:
        """Every layer weight and the head read once, ``length`` embedding
        rows, and the K/V of ``length`` positions written."""
        weights = (self.layers * self.layer_params + self.d_model
                   + self.head_params + self._embed_rows(length)) * self.dtype_bytes
        return weights + length * self.kv_bytes_per_token

    def decode_flops(self, contexts: Iterable[int]) -> float:
        """One decode step over lanes whose new token sees ``c`` keys."""
        contexts = list(contexts)
        per_token = 2.0 * (self.layer_matmul_params * self.layers + self.head_params)
        return per_token * len(contexts) + self.attn_flops(contexts)

    def decode_bytes(self, contexts: Iterable[int]) -> float:
        """One decode step over lanes whose new token sees ``c`` keys: every
        layer weight and the head read once, one embedding row per lane,
        each lane's valid K/V read (the new entry is written)."""
        contexts = list(contexts)
        weights = (self.layers * self.layer_params + self.d_model + self.head_params
                   + self._embed_rows(len(contexts))) * self.dtype_bytes
        return weights + sum(contexts) * self.kv_bytes_per_token


def least_seconds(flops: float, nbytes: float, peak: Mapping[str, float]) -> float:
    """The least time the chip could take: the larger of operations over
    peak FLOP/s and bytes over peak bandwidth."""
    return max(flops / peak["bf16_flops_per_s"], nbytes / peak["hbm_bytes_per_s"])


def cholesky_flops(n: int) -> float:
    """FLOPs credited to a Cholesky factorization of order ``n``: n^3/3,
    the leading term of LAPACK's count."""
    return n ** 3 / 3.0


__all__ = ["LMShapes", "PEAKS", "UnknownDevice", "cholesky_flops", "least_seconds",
           "peaks"]
