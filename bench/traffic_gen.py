"""One generator for every serving traffic mix; each mix is a JSON file of
parameters in ``bench/traffic/``.

The generator copies the arithmetic of the program's seeded
``PoissonWorkload`` (exponential gaps at a rate, token ids uniform over
the vocabulary, one ``numpy`` generator per seed) and extends it with what
the cells need:

* prompt lengths drawn from buckets with weights;
* output lengths log-uniform over ``[min, max]``;
* ``"arrivals": "backlog"`` (every request due at once: an offline batch)
  or ``"poisson"`` at ``rate_per_s``;
* the same work for every seed.  Requests come in blocks of ``block``.
  Each block holds one fixed set of prompt lengths, output lengths and
  gaps: the weights' share of each bucket, the output distribution's
  quantiles and the exponential distribution's quantiles, scaled so that a
  block spans ``block / rate`` seconds.  The seed only orders each set
  within its block and draws the token ids.  So two seeds differ in the
  order of the work, not in how much there is.

Requests are made lazily, in order, so a backlog can be far longer than a
run drains.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, List, Mapping

import numpy as np


@dataclasses.dataclass(frozen=True)
class GenRequest:
    index: int
    arrival_s: float           # due time, from the start of the traffic
    prompt: np.ndarray         # int32 (1, prompt_len)
    max_new_tokens: int        # every emitted token, the prefill's included

    @property
    def prompt_len(self) -> int:
        return int(self.prompt.shape[1])


def _bucket_counts(weights: List[float], block: int) -> List[int]:
    """Largest-remainder split of ``block`` requests over the buckets."""
    total = float(sum(weights))
    raw = [w / total * block for w in weights]
    counts = [int(math.floor(r)) for r in raw]
    order = sorted(range(len(raw)), key=lambda i: raw[i] - counts[i], reverse=True)
    for i in order[:block - sum(counts)]:
        counts[i] += 1
    return counts


def _midpoints(block: int) -> np.ndarray:
    return (np.arange(block) + 0.5) / block


class LMTraffic:
    """The request stream of one serving mix under one seed."""

    def __init__(self, spec: Mapping[str, Any], seed: int, vocab_size: int):
        self.vocab_size = int(vocab_size)
        self.block = int(spec.get("block", 64))
        self.buckets = [int(b) for b in spec["prompt_buckets"]]
        self.weights = [float(w) for w in spec["prompt_weights"]]
        if len(self.buckets) != len(self.weights):
            raise ValueError("prompt_buckets and prompt_weights differ in length")
        out = spec["output_tokens"]
        if out["dist"] != "log_uniform":
            raise ValueError(f"unknown output distribution {out['dist']!r}")
        self.out_min, self.out_max = int(out["min"]), int(out["max"])
        if not 1 <= self.out_min <= self.out_max:
            raise ValueError(f"output span {self.out_min}..{self.out_max}")
        self.arrivals = spec["arrivals"]
        if self.arrivals not in ("backlog", "poisson"):
            raise ValueError(f"unknown arrival process {self.arrivals!r}")
        self.rate = float(spec["rate_per_s"]) if self.arrivals == "poisson" else None
        self._rng = np.random.default_rng(np.random.SeedSequence(int(seed) % 2**64))
        self._made: List[GenRequest] = []
        self._t = 0.0

        counts = _bucket_counts(self.weights, self.block)
        self._lens = np.repeat(np.asarray(self.buckets), counts)
        lo, hi = math.log(self.out_min), math.log(self.out_max)
        outs = np.exp(lo + (hi - lo) * _midpoints(self.block))
        self._outs = np.clip(np.rint(outs), self.out_min, self.out_max).astype(int)
        if self.rate is not None:
            gaps = -np.log1p(-_midpoints(self.block))
            self._gaps = gaps * (self.block / self.rate) / gaps.sum()

    def _next_block(self) -> None:
        rng = self._rng
        lens = rng.permutation(self._lens)
        outs = rng.permutation(self._outs)
        gaps = rng.permutation(self._gaps) if self.rate is not None else None
        for j in range(self.block):
            if gaps is not None:
                self._t += float(gaps[j])
            prompt = rng.integers(0, self.vocab_size, (1, int(lens[j])), dtype=np.int32)
            self._made.append(GenRequest(len(self._made), self._t, prompt, int(outs[j])))

    def __getitem__(self, i: int) -> GenRequest:
        while i >= len(self._made):
            self._next_block()
        return self._made[i]
