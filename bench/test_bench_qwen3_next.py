"""The Qwen3-Next serving cell and the large-tile Cholesky cell: they
resolve by name, a toy Qwen3-Next cell runs end to end on the CPU, the
op-scope reader attributes hand-built spans, the least-work counts match a
hand count at the published shapes, and the float8 control is told apart
from the served model."""

from __future__ import annotations

import json

import numpy as np
import pytest

from bench import op_scopes, registry, testing, work
from bench.trace_reduce import Span, TraceData
from bench.traffic_gen import LMTraffic
from bench.work_qwen3_next import Qwen3NextShapes, Routing

REPO = testing.REPO
CFG = json.loads((REPO / "bench/configs/qwen3-next-80b-a3b-l16.json").read_text())
NEW_CELLS = {"qwen3-next.longdoc-batch": "mean_logit_gap",
             "cholesky.n7680-b1536": "factor_rel_error"}

#: the toy's widths (HF keys); bfloat16 as served, 8 of 16 experts held
TOY = dict(name="toy-qwen3-next", hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=32, vocab_size=300, num_hidden_layers=4,
           linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
           linear_value_head_dim=16, num_experts=8, router_experts=16, expert_offset=4,
           num_experts_per_tok=4, moe_intermediate_size=32,
           shared_expert_intermediate_size=32)
#: between the toy's sound mean gaps (under 0.002) and its float8 control's
#: (over 0.01) on the seeds of test_fp8_control_is_told_apart
TOY_LIMIT = {"mean_logit_gap": {"limit": 0.005}}


@pytest.mark.parametrize("cell", sorted(NEW_CELLS))
def test_new_cells_resolve_by_name(cell):
    c = registry.resolve(REPO, cell)
    assert callable(registry.driver(c).run)
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"} and len(c.end_to_end) == 2
    limits = testing.limits(REPO, cell)
    assert NEW_CELLS[cell] in limits and all(float(v["limit"]) > 0 for v in limits.values())
    names = {m["name"] for m in c.per_layer}
    for m in c.per_layer:
        assert callable(registry.metric_reader(REPO, m["name"]))
    if cell.startswith("qwen3-next"):
        assert {"gdn_roofline", "moe_roofline", "decode_roofline", "serve_mfu",
                "device_idle_frac.batch"} == names
        assert c.kind == "lm_serve_qwen3_next"
    else:
        assert {"sched_gap_us", "device_idle_frac.chol"} == names


def test_configuration_keeps_the_published_keys():
    """Every number of the published config.json is in the file unchanged,
    except the keys ``reduced`` names."""
    published = dict(decoder_sparse_step=1, full_attention_interval=4, head_dim=256,
                     hidden_size=2048, intermediate_size=5120, linear_conv_kernel_dim=4,
                     linear_key_head_dim=128, linear_num_key_heads=16,
                     linear_num_value_heads=32, linear_value_head_dim=128,
                     max_position_embeddings=262144, moe_intermediate_size=512,
                     num_attention_heads=16, num_experts=512, num_experts_per_tok=10,
                     num_hidden_layers=48, num_key_value_heads=2, partial_rotary_factor=0.25,
                     rms_norm_eps=1e-6, rope_theta=10000000,
                     shared_expert_intermediate_size=512, vocab_size=151936)
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == CFG["name"])
    changed = {k for k, v in published.items() if CFG[k] != v}
    assert changed == set(entry["reduced"]) == set(CFG["reduced"])
    assert CFG["num_hidden_layers"] % CFG["full_attention_interval"] == 0
    assert CFG["router_experts"] == published["num_experts"]


def test_published_shapes_match_a_hand_count():
    s = Qwen3NextShapes.from_config(CFG)
    d, v = 2048, 151936
    attn = d * 2 * 16 * 256 + 2 * d * 2 * 256 + 16 * 256 * d + 2 * 256
    gdn = d * (2 * 16 * 128 + 2 * 32 * 128) + d * 64 + 32 * 128 * d + 4 * 8192 + 64 + 128
    assert (s.attn_params, s.gdn_params) == (attn, gdn)
    expert = 3 * d * 512
    fixed = d * 512 + 3 * d * 512 + d
    params = 2 * v * d + 4 * attn + 12 * gdn + 16 * (fixed + 64 * expert + 2 * d) + d
    assert s.params == params and abs(s.params / 1e9 - 4.42) < 0.01
    # a lane's state: float32 recurrent state and the convolution's last 3 inputs
    assert s.state_bytes == 12 * (32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert s.kv_bytes_per_token == 2 * 4 * 2 * 256 * 2
    # a one-lane decode step that touched no expert reads the fixed weights,
    # one embedding row, and its state twice
    fixed_w = (4 * attn + 12 * gdn + 16 * (fixed + 2 * d) + d + d * v + d) * 2
    assert s.decode_bytes([1]) == fixed_w + 2 * s.state_bytes + s.kv_bytes_per_token
    # routing: 20 experts touched per call over 16 layers, 32 lanes share them
    r = s.with_routing(Routing(decode_pairs=20, decode_touched=20))
    union = 16 * 64 * (1 - (1 - 20 / 16 / 64) ** 32)
    assert r.decode_bytes([1] * 32) == pytest.approx(
        fixed_w + 31 * d * 2 + union * expert * 2 + 64 * s.state_bytes
        + 32 * s.kv_bytes_per_token)
    assert r.scope_decode("moe") == (2.0 * 16 * fixed + 2.0 * 20 * expert,
                                     (16 * fixed + 20 * expert) * 2)
    # prefill: projections, causal attention on 4 layers, chunked DeltaNet,
    # routed pairs, the head once
    L = 4096
    rp = s.with_routing(Routing(prefill_pairs_per_token=20.0, prefill_touched=1024))
    chunk = 64 * 64 * 2 * (3 * 128 + 2 * 128) + 6 * 64 * 128 * 128 + 64 ** 3 / 3
    gdn_flops = 12 * (2 * (gdn - 4 * 8192 - 64 - 128) * L + (L // 64) * 32 * chunk)
    moe_flops = 2 * 16 * fixed * L + 2 * 20 * L * expert
    attn_flops = 2 * 4 * (attn - 512) * L + 4 * 16 * 256 * (L * (L + 1) / 2) * 4
    assert rp.prefill_flops(L) == pytest.approx(gdn_flops + moe_flops + attn_flops + 2 * d * v)


def test_traffic_keeps_the_cited_medians():
    spec = json.loads((REPO / "bench/traffic/longdoc-batch.json").read_text())
    a, b = LMTraffic(spec, 1, 1000), LMTraffic(spec, 2**35 + 3, 1000)
    block = [a[i] for i in range(spec["block"])]
    other = [b[i] for i in range(spec["block"])]
    med = spec["source_medians"]
    assert np.median([r.prompt_len for r in block]) == med["prompt"]
    assert np.median([r.max_new_tokens for r in block]) == pytest.approx(med["output"], rel=0.1)
    assert sorted(r.prompt_len for r in block) == sorted(r.prompt_len for r in other)
    assert spec["block"] == spec["max_batch"] and spec["max_batch"] % 8 == 0


def _hlo(names):
    return "\n".join(f'  %{n} = f32[] fusion(), metadata={{op_name="jit(bench_decode)/while/body/{s}/dot"}}'
                     for n, s in names.items())


def test_op_scopes_attribute_hand_built_ops():
    table = op_scopes.scope_table(_hlo({"fusion.1": "gdn", "fusion.2": "moe/moe.experts",
                                        "fusion.3": "gated_attn"}) + "\n  %copy.4 = f32[] copy()")
    assert table == {"fusion.1": ("gdn",), "fusion.2": ("moe", "moe.experts"),
                     "fusion.3": ("gated_attn",)}
    dev = "/device:TPU:0"
    td = TraceData(
        ops={dev: [Span("%while.9", 100, 900),          # a loop, under no scope
                   Span("%fusion.1 = f32[] fusion()", 100, 300),
                   Span("%fusion.1", 250, 400),        # overlaps: counted once
                   Span("%fusion.2", 400, 700), Span("%copy.4", 700, 800),
                   Span("%fusion.1", 1100, 1200)]},    # in a run outside the steps
        modules={dev: [Span("jit_bench_decode(1)", 100, 900),
                       Span("jit_bench_decode(1)", 1100, 1300)]},
        host=[])
    got = op_scopes.scope_seconds(td, {"bench_decode": table}, [Span("bench.step", 50, 950)])
    assert got == pytest.approx({"gdn": 300e-9, "moe": 300e-9, "moe.experts": 300e-9})


class _Run:
    """What scope_roofline reads, by hand: one traced step of two lanes and a
    prefill of 16 tokens."""

    def __init__(self, shapes, scope_s):
        from bench.drivers.lm_serve import Step

        self.shapes, self.scope_s = shapes, scope_s
        self.step = Step(0.0, 1.0, contexts=[20, 30], prefills=[16], traced=True)
        self.peak = work.peaks("TPU v5 lite")

    def traced_steps(self):
        return [(self.step, Span("bench.step", 0, 1))]

    def least_seconds(self, flops, nbytes):
        return work.least_seconds(flops, nbytes, self.peak)


@pytest.mark.parametrize("scope", ["gdn", "moe"])
def test_scope_roofline_is_at_most_100_on_exact_work(scope):
    s = Qwen3NextShapes.from_config(CFG).with_routing(
        Routing(prefill_pairs_per_token=20, prefill_touched=900, decode_pairs=20,
                decode_touched=20))
    run = _Run(s, {})
    exact = (run.least_seconds(*s.scope_prefill(scope, 16))
             + 2 * run.least_seconds(*s.scope_decode(scope)))
    run.scope_s = {scope: exact}
    assert op_scopes.scope_roofline(run, scope) == pytest.approx(100.0)
    run.scope_s = {scope: 4 * exact}
    assert op_scopes.scope_roofline(run, scope) == pytest.approx(25.0)
    run.scope_s = {}
    assert op_scopes.scope_roofline(run, scope) is None


def _toy_root(tmp_path):
    """``testing.toy_root`` with a toy Qwen3-Next cell added as files only."""
    root = testing.toy_root(tmp_path)
    b = root / "bench"
    cfg = dict(CFG, **TOY)
    (b / "configs/toy-qwen3-next.json").write_text(json.dumps(cfg))
    traffic = json.loads((b / "traffic/longdoc-batch.json").read_text())
    traffic.update(prompt_buckets=[16, 32], prompt_weights=[0.5, 0.5],
                   output_tokens={"dist": "log_uniform", "min": 4, "max": 24},
                   block=4, max_batch=4, admission_capacity=8, check_requests=4,
                   trace_seconds=0.5)
    (b / "traffic/toy-longdoc.json").write_text(json.dumps(traffic))
    (b / "limits/toy.next.json").write_text(json.dumps(TOY_LIMIT))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toy-qwen3-next", "source": "toy",
                             "file": "bench/configs/toy-qwen3-next.json", "reduced": [],
                             "why": "toy"})
    bench["workloads"].append({"name": "toy.next", "config": "toy-qwen3-next",
                               "traffic": "toy-longdoc", "chips": 1, "why": "toy"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "qwen3-next.longdoc-batch" in m.get("workloads", []):
            m["workloads"].append("toy.next")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_toy_qwen3_next_cell_runs_end_to_end(tmp_path, capsys, monkeypatch):
    monkeypatch.setitem(work.PEAKS, "cpu", dict(work.PEAKS["TPU v5 lite"]))
    root = _toy_root(tmp_path)
    c = registry.resolve(root, "toy.next")
    line = testing.toy_run(root, "toy.next", 2**31 + 13, 0, capsys)
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"tok_s", "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0
    traced = testing.toy_run(root, "toy.next", 5, 1, capsys)
    assert traced["correct"] is True
    assert set(traced["metrics"]) <= {m["name"] for m in c.per_layer}
    assert "serve_mfu" in traced["metrics"]


def test_fp8_control_is_told_apart_from_the_served_model():
    """At the toy size in bfloat16, the program's mean gap stays under the
    toy limit and the float8 control's goes over it, on three seeds."""
    import jax
    import jax.numpy as jnp

    from bench.drivers.lm_serve import Rec
    from bench.drivers.lm_serve_qwen3_next import check, model_config, program_tree
    from bench.harness import compare, verdict
    from bench.reference import qwen3_next as ref
    from repro.models import lm

    cfg = dict(CFG, **TOY)
    mcfg = model_config(cfg)
    readings = []
    for seed in (2, 3, 4):
        params = jax.jit(lambda k: program_tree(ref.draw_all(k, cfg, jnp.bfloat16),
                                                lm.abstract_params(mcfg)))(
            ref.dense_lm.seed_key(seed))
        prompt = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (1, 16),
                                                      dtype=np.int32)
        cache, logits = jax.jit(lambda p, t: lm.prefill(p, mcfg, {"tokens": t}, None,
                                                        max_len=57))(params, prompt)
        decode = jax.jit(lambda p, c, t: lm.decode_step(p, mcfg, c, t, None))
        toks = []
        for _ in range(40):
            tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
            toks.append(int(tok[0, 0]))
            cache, logits = decode(params, cache, tok)
        rec = Rec(0, 0.0, prompt, len(toks), tokens=toks)
        got, lo = check(cfg, seed, [rec], 16, 40, control=True)
        assert verdict(compare(got, TOY_LIMIT)) is True, (got, lo)
        assert verdict(compare(lo, TOY_LIMIT)) is False, (got, lo)
        readings.append((got["mean_logit_gap"], lo["mean_logit_gap"]))
    assert min(c for _, c in readings) >= 3 * max(p for p, _ in readings), readings
