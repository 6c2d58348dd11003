"""The least work of Qwen3-Next serving, from its configuration's shapes and
the routing counters of a run.

:class:`Qwen3NextShapes` gives the four functions the serving readers use
(``prefill_flops``, ``prefill_bytes``, ``decode_flops``, ``decode_bytes``,
as :class:`bench.work.LMShapes` does) and the same split by layer kind for
the kernel rooflines of the Gated DeltaNet mixer (``gdn``) and the expert
layer (``moe``).  Every count is a lower bound of what the mathematics
asks, whatever implements it:

* decode step over ``k`` lanes (``decode_*``): every non-expert weight
  read once, one embedding row a lane, each lane's recurrent and
  convolution state read and written and its valid K/V read; routed
  experts only as far as the routing counters show them touched;
* prefill of ``L`` tokens: the FLOPs of the projections, of causal
  attention on the full-attention layers, of the chunked Gated DeltaNet
  (its products per chunk and head) and of the routed pairs the counters
  recorded; the bytes of every non-expert weight and of the held experts
  the prefills touched, read once, and of the state and K/V written;
* one lane's decode call (``scope_decode``, for the kernel rooflines,
  which time per call): the layer kind's weights read once, and for the
  expert layer the experts that call touched.

Routing enters as means over the window's calls (:class:`Routing`).  A
decode step over ``k`` lanes touches, in each layer, the union of its
lanes' experts: with ``t`` held experts of ``n`` touched by a call on
average, ``n (1 - (1 - t/n)^k)``.

The configuration is the benchmark's JSON (Hugging Face key names, with
``num_experts`` the experts held here and ``router_experts`` those routed
over), so nothing here depends on the program's model code.
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Mapping, Tuple

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
_STATE_BYTES = 4        # the recurrent state is float32


@dataclasses.dataclass(frozen=True)
class Routing:
    """Means of the routing counters over a run's calls, summed over the
    expert layers: held pairs per prompt token and held experts touched
    per prefill; held pairs and held experts touched per decode call."""

    prefill_pairs_per_token: float = 0.0
    prefill_touched: float = 0.0
    decode_pairs: float = 0.0
    decode_touched: float = 0.0


@dataclasses.dataclass(frozen=True)
class Qwen3NextShapes:
    layers: int
    full_every: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    lin_k_heads: int
    lin_v_heads: int
    lin_dk: int
    lin_dv: int
    conv_width: int
    experts_routed: int
    experts_held: int
    top_k: int
    d_expert: int
    d_shared: int
    vocab: int
    dtype_bytes: int
    tied: bool = False
    chunk: int = 64
    routing: Routing = Routing()

    @classmethod
    def from_config(cls, cfg: Mapping) -> "Qwen3NextShapes":
        return cls(layers=int(cfg["num_hidden_layers"]),
                   full_every=int(cfg["full_attention_interval"]),
                   d_model=int(cfg["hidden_size"]),
                   heads=int(cfg["num_attention_heads"]),
                   kv_heads=int(cfg["num_key_value_heads"]),
                   head_dim=int(cfg["head_dim"]),
                   lin_k_heads=int(cfg["linear_num_key_heads"]),
                   lin_v_heads=int(cfg["linear_num_value_heads"]),
                   lin_dk=int(cfg["linear_key_head_dim"]),
                   lin_dv=int(cfg["linear_value_head_dim"]),
                   conv_width=int(cfg["linear_conv_kernel_dim"]),
                   experts_routed=int(cfg["router_experts"]),
                   experts_held=int(cfg["num_experts"]),
                   top_k=int(cfg["num_experts_per_tok"]),
                   d_expert=int(cfg["moe_intermediate_size"]),
                   d_shared=int(cfg["shared_expert_intermediate_size"]),
                   vocab=int(cfg["vocab_size"]),
                   dtype_bytes=_DTYPE_BYTES[cfg["torch_dtype"]],
                   tied=bool(cfg["tie_word_embeddings"]))

    def with_routing(self, routing: Routing) -> "Qwen3NextShapes":
        return dataclasses.replace(self, routing=routing)

    # -- layer counts and parameters -------------------------------------
    @property
    def full_layers(self) -> int:
        return self.layers // self.full_every

    @property
    def lin_layers(self) -> int:
        return self.layers - self.full_layers

    @property
    def conv_dim(self) -> int:
        return 2 * self.lin_k_heads * self.lin_dk + self.lin_v_heads * self.lin_dv

    @property
    def attn_matmul_params(self) -> int:
        d, hd = self.d_model, self.head_dim
        return (d * 2 * self.heads * hd + 2 * d * self.kv_heads * hd
                + self.heads * hd * d)

    @property
    def attn_params(self) -> int:
        return self.attn_matmul_params + 2 * self.head_dim

    @property
    def gdn_matmul_params(self) -> int:
        d, hk, hv = self.d_model, self.lin_k_heads, self.lin_v_heads
        return (d * (2 * hk * self.lin_dk + 2 * hv * self.lin_dv) + d * 2 * hv
                + hv * self.lin_dv * d)

    @property
    def gdn_params(self) -> int:
        """Projections, the convolution, A_log, dt_bias and the gated norm."""
        return (self.gdn_matmul_params + self.conv_width * self.conv_dim
                + 2 * self.lin_v_heads + self.lin_dv)

    @property
    def expert_params(self) -> int:
        return 3 * self.d_model * self.d_expert

    @property
    def moe_fixed_params(self) -> int:
        """What every expert layer reads whatever the routing: the router,
        the shared expert and its gate."""
        d = self.d_model
        return d * self.experts_routed + 3 * d * self.d_shared + d

    @property
    def head_params(self) -> int:
        return self.d_model * self.vocab

    @property
    def params(self) -> int:
        """Parameters held on the chip: embedding, layers with the held
        experts, final norm, head."""
        per_layer = self.moe_fixed_params + self.experts_held * self.expert_params \
            + 2 * self.d_model
        return (self.vocab * self.d_model + self.full_layers * self.attn_params
                + self.lin_layers * self.gdn_params + self.layers * per_layer
                + self.d_model + (0 if self.tied else self.head_params))

    @property
    def kv_bytes_per_token(self) -> int:
        return 2 * self.full_layers * self.kv_heads * self.head_dim * self.dtype_bytes

    @property
    def state_bytes(self) -> int:
        """One lane's recurrent and convolution state over all layers."""
        rec = self.lin_v_heads * self.lin_dk * self.lin_dv * _STATE_BYTES
        conv = (self.conv_width - 1) * self.conv_dim * self.dtype_bytes
        return self.lin_layers * (rec + conv)

    # -- the work of each layer kind ------------------------------------
    def _chunked_flops(self, length: int) -> float:
        """Products of the chunked Gated DeltaNet over ``length`` tokens
        (whole chunks), every head of one layer: within a chunk C x C
        products against keys and values and the triangular solve; across
        chunks the three products with the state."""
        c, dk, dv = self.chunk, self.lin_dk, self.lin_dv
        chunks = -(-length // c)
        per = 2.0 * c * c * (3 * dk + 2 * dv) + 6.0 * c * dk * dv + c ** 3 / 3.0
        return chunks * self.lin_v_heads * per

    def _recurrent_flops(self) -> float:
        """One token's state products, every head of one layer."""
        return 6.0 * self.lin_v_heads * self.lin_dk * self.lin_dv

    def _union_touched(self, lanes: int) -> float:
        """Held experts a step over ``lanes`` lanes touches, summed over the
        layers."""
        n = self.experts_held
        per_layer = self.routing.decode_touched / self.layers
        if n <= 0 or per_layer <= 0:
            return 0.0
        return self.layers * n * (1.0 - (1.0 - min(per_layer, n) / n) ** lanes)

    def scope_prefill(self, scope: str, length: int) -> Tuple[float, float]:
        """(FLOPs, bytes) of one prefill of ``length`` tokens in ``scope``
        (``gdn`` or ``moe``)."""
        b = self.dtype_bytes
        r = self.routing
        if scope == "gdn":
            flops = self.lin_layers * (2.0 * self.gdn_matmul_params * length
                                       + self._chunked_flops(length))
            return flops, self.lin_layers * self.gdn_params * b + self.state_bytes
        if scope == "moe":
            flops = (2.0 * self.layers * self.moe_fixed_params * length
                     + 2.0 * r.prefill_pairs_per_token * length * self.expert_params)
            nbytes = (self.layers * self.moe_fixed_params
                      + r.prefill_touched * self.expert_params) * b
            return flops, nbytes
        raise KeyError(scope)

    def scope_decode(self, scope: str) -> Tuple[float, float]:
        """(FLOPs, bytes) of one lane's decode call in ``scope``."""
        b = self.dtype_bytes
        r = self.routing
        if scope == "gdn":
            flops = self.lin_layers * (2.0 * self.gdn_matmul_params + self._recurrent_flops())
            return flops, self.lin_layers * self.gdn_params * b + 2.0 * self.state_bytes
        if scope == "moe":
            flops = (2.0 * self.layers * self.moe_fixed_params
                     + 2.0 * r.decode_pairs * self.expert_params)
            nbytes = (self.layers * self.moe_fixed_params
                      + r.decode_touched * self.expert_params) * b
            return flops, nbytes
        raise KeyError(scope)

    # -- the four functions of the serving readers ----------------------
    def _attn_flops(self, keys: float) -> float:
        return 4.0 * self.heads * self.head_dim * keys * self.full_layers

    def _fixed_weights(self) -> int:
        """Every weight but the routed experts and the embedding."""
        return (self.full_layers * self.attn_params + self.lin_layers * self.gdn_params
                + self.layers * (self.moe_fixed_params + 2 * self.d_model)
                + self.d_model + self.head_params)

    def prefill_flops(self, length: int) -> float:
        attn_proj = 2.0 * self.full_layers * self.attn_matmul_params * length
        causal = self._attn_flops(length * (length + 1) / 2)
        return (attn_proj + causal + self.scope_prefill("gdn", length)[0]
                + self.scope_prefill("moe", length)[0] + 2.0 * self.head_params)

    def prefill_bytes(self, length: int) -> float:
        embed = 0 if self.tied else length * self.d_model
        weights = (self._fixed_weights() + embed
                   + self.routing.prefill_touched * self.expert_params) * self.dtype_bytes
        return weights + self.state_bytes + length * self.kv_bytes_per_token

    def decode_flops(self, contexts: Iterable[int]) -> float:
        contexts = list(contexts)
        per_token = (2.0 * self.full_layers * self.attn_matmul_params
                     + self.scope_decode("gdn")[0] + self.scope_decode("moe")[0]
                     + 2.0 * self.head_params)
        return per_token * len(contexts) + self._attn_flops(sum(contexts))

    def decode_bytes(self, contexts: Iterable[int]) -> float:
        contexts = list(contexts)
        k = len(contexts)
        embed = 0 if self.tied else k * self.d_model
        weights = (self._fixed_weights() + embed
                   + self._union_touched(k) * self.expert_params) * self.dtype_bytes
        return weights + 2.0 * k * self.state_bytes + sum(contexts) * self.kv_bytes_per_token


__all__ = ["Qwen3NextShapes", "Routing"]
