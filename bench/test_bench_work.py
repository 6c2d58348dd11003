"""The least-work counts, the peak table and the traffic generator."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import testing, work
from bench.traffic_gen import LMTraffic

CFG = json.loads((testing.REPO / "bench/configs/qwen3-14b-l8.json").read_text())


def test_qwen3_14b_l8_counts_match_a_hand_count():
    s = work.LMShapes.from_config(CFG)
    d, f, v = 5120, 17408, 151936
    per_layer = d * 5120 + 2 * d * 1024 + 5120 * d + 3 * d * f   # q, k, v, o, mlp
    assert s.layer_matmul_params == per_layer == 330_301_440
    params = 2 * v * d + 8 * (per_layer + 2 * d + 2 * 128) + d
    assert s.params == params and abs(s.params / 1e9 - 4.2) < 0.01
    # a one-lane decode step at context 1 reads all layers and the head
    # once and one embedding row: ~6.84 GB of bfloat16
    weights = (8 * (per_layer + 2 * d + 256) + d + d * v + d) * 2
    assert s.decode_bytes([1]) == weights + s.kv_bytes_per_token
    assert s.decode_bytes([1]) / 1e9 == pytest.approx(6.84, abs=0.01)
    assert s.kv_bytes_per_token == 2 * 8 * 8 * 128 * 2
    # prefill: 2 x matmul params per token, causal attention, head once
    L = 1024
    attn = 4 * 40 * 128 * (L * (L + 1) // 2) * 8
    assert s.prefill_flops(L) == 2 * per_layer * 8 * L + attn + 2 * d * v
    # a decode step over 16 lanes costs 16 tokens' FLOPs but one weight read
    assert s.decode_flops([100] * 16) == pytest.approx(16 * s.decode_flops([100]))
    assert s.decode_bytes([100] * 16) < 1.1 * s.decode_bytes([100])


def test_least_time_takes_the_larger_bound():
    peak = work.peaks("TPU v5 lite")
    assert work.least_seconds(197e12, 0, peak) == pytest.approx(1.0)
    assert work.least_seconds(0, 819e9, peak) == pytest.approx(1.0)


def test_an_unknown_device_kind_raises():
    with pytest.raises(work.UnknownDevice):
        work.peaks("TPU v4")
    with pytest.raises(KeyError):
        work.peaks("cpu")


def test_cholesky_flops():
    assert work.cholesky_flops(7680) == pytest.approx(7680 ** 3 / 3)


SPEC = json.loads((testing.REPO / "bench/traffic/code-poisson.json").read_text())


@pytest.mark.parametrize("name", ["code-poisson", "conv-batch"])
def test_every_seed_gets_the_same_work_in_another_order(name):
    spec = json.loads((testing.REPO / f"bench/traffic/{name}.json").read_text())
    a, b = LMTraffic(spec, 1, 1000), LMTraffic(spec, 2**35 + 1, 1000)
    block = spec["block"]
    for g in (a, b):
        g[2 * block - 1]
    for k in range(2):
        sl = slice(k * block, (k + 1) * block)
        lens = [sorted(r.prompt_len for r in g._made[sl]) for g in (a, b)]
        outs = [sorted(r.max_new_tokens for r in g._made[sl]) for g in (a, b)]
        assert lens[0] == lens[1] and outs[0] == outs[1]
    assert [r.prompt_len for r in a._made] != [r.prompt_len for r in b._made]
    if spec["arrivals"] == "poisson":
        span = a[block - 1].arrival_s
        assert span == pytest.approx(block / spec["rate_per_s"])
        assert b[block - 1].arrival_s == pytest.approx(span)
    else:
        assert all(r.arrival_s == 0.0 for r in a._made)
    again = LMTraffic(spec, 1, 1000)
    assert np.array_equal(again[5].prompt, a[5].prompt)
    out = spec["output_tokens"]
    assert all(out["min"] <= r.max_new_tokens <= out["max"] for r in a._made)
    assert all(r.prompt_len in spec["prompt_buckets"] for r in a._made)


@pytest.mark.parametrize("name", ["code-poisson", "conv-batch"])
def test_lengths_keep_the_medians_of_the_cited_trace(name):
    """A block's median output is the cited median to 10%, and its median
    prompt lies within the powers of two beside the cited one."""
    spec = json.loads((testing.REPO / f"bench/traffic/{name}.json").read_text())
    g = LMTraffic(spec, 3, 1000)
    block = [g[i] for i in range(spec["block"])]
    med = spec["source_medians"]
    assert np.median([r.max_new_tokens for r in block]) == pytest.approx(med["output"],
                                                                         rel=0.1)
    prompt = np.median([r.prompt_len for r in block])
    assert med["prompt"] / 2 < prompt < med["prompt"] * 2
