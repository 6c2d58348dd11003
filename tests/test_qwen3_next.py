"""Qwen3-Next (the ``gdn`` family) against the plain float32 reference in
``bench/reference/qwen3_next.py``, at a small size on the CPU, over the
reference's seeded weights.

Both sides compute in float32 with matrix products at ``highest``, so
agreement is to float32 rounding: the program's chunked delta rule and
grouped expert products reassociate the reference's sums, and nothing
else differs.  Tolerances are relative to the largest magnitude compared.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.drivers.lm_serve_qwen3_next import model_config, program_tree
from bench.reference import qwen3_next as ref
from repro.models import gdn as G
from repro.models import layers as L
from repro.models import lm

#: Hugging Face keys at a toy size: one period of 3 DeltaNet layers and a
#: gated full-attention layer, 8 of 16 experts held (experts 4..11)
TOY = dict(name="toy-qwen3-next", hidden_size=64, num_attention_heads=4,
           num_key_value_heads=2, head_dim=32, vocab_size=300, num_hidden_layers=4,
           rms_norm_eps=1e-6, rope_theta=1e7, partial_rotary_factor=0.25,
           full_attention_interval=4, linear_num_key_heads=2, linear_num_value_heads=4,
           linear_key_head_dim=16, linear_value_head_dim=16, linear_conv_kernel_dim=4,
           num_experts=8, router_experts=16, expert_offset=4, num_experts_per_tok=4,
           moe_intermediate_size=32, shared_expert_intermediate_size=32,
           norm_topk_prob=True, torch_dtype="float32", tie_word_embeddings=False)
#: float32 rounding of sums over tens of terms, relative to the largest value
RTOL = 1e-4


def _program(cfg, seed):
    mcfg = model_config(cfg)
    params = jax.jit(lambda k: program_tree(ref.draw_all(k, cfg, jnp.float32),
                                            lm.abstract_params(mcfg)))(ref.dense_lm.seed_key(seed))
    return mcfg, params


def _close(got, want, rtol=RTOL):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert got.shape == want.shape
    err = np.abs(got - want).max()
    assert err <= rtol * np.abs(want).max(), (err, np.abs(want).max())


def test_prefill_then_decode_matches_the_reference():
    """Prefill logits, then 8 greedy decode steps through the cache (conv
    and recurrent state, K/V), against the reference's full forward pass
    over the same tokens."""
    mcfg, params = _program(TOY, 3)
    prompt = np.random.default_rng(0).integers(0, TOY["vocab_size"], (1, 70), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        prefill = jax.jit(lambda p, t: lm.prefill(p, mcfg, {"tokens": t}, None, max_len=80))
        decode = jax.jit(lambda p, c, t: lm.decode_step(p, mcfg, c, t, None))
        cache, logits = prefill(params, jnp.asarray(prompt))
        rows, fed = [logits[0, -1]], []
        for _ in range(8):
            tok = jnp.argmax(rows[-1]).astype(jnp.int32).reshape(1, 1)
            fed.append(int(tok[0, 0]))
            cache, logits = decode(params, cache, tok)
            rows.append(logits[0, -1])
    assert int(cache["index"]) == 78
    seq = np.concatenate([prompt[0], fed])
    want = ref.Reference(TOY, 3, q_block=32).logits([seq], [ref.positions(70, 9, 16)], 96)[0]
    _close(jnp.stack(rows), np.asarray(want)[:9])


@pytest.mark.parametrize("length", [37, 100, 128])
def test_chunked_form_matches_the_token_recurrence(length):
    """The program's chunked (WY) DeltaNet prefill, in chunks of 64, at
    every position of a prompt (under one chunk, a whole number of chunks
    or not), against the reference's token-serial recurrence."""
    mcfg, params = _program(TOY, 5)
    toks = np.random.default_rng(length).integers(0, TOY["vocab_size"], (1, length),
                                                  dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p, t: lm.logits_from_hidden(
            p, mcfg, lm.forward(p, mcfg, {"tokens": t}, None, remat=False)))(
                params, jnp.asarray(toks))[0]
    padded = -(-length // 32) * 32
    want = ref.Reference(TOY, 5, q_block=32).logits(
        [toks[0]], [np.arange(length, dtype=np.int32)], padded)[0]
    _close(got, want)


def test_chunked_delta_rule_carries_the_state_of_the_recurrence():
    """Outputs and final state of the chunked rule, from a non-zero state,
    equal the one-token step applied token by token."""
    B, S, H, d = 2, 45, 3, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, S, H, d))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, d))
    g = -jax.random.uniform(ks[3], (B, S, H), minval=0.0, maxval=0.5)
    beta = jax.random.uniform(ks[4], (B, S, H))
    s0 = jax.random.normal(ks[5], (B, H, d, d))
    with jax.default_matmul_precision("highest"):
        o, s = G.chunked_delta_rule(q, k, v, g, beta, s0, 16)
        state, outs = s0, []
        for t in range(S):
            ot, state = G.recurrent_step(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], state)
            outs.append(ot)
    _close(o, jnp.stack(outs, axis=1))
    _close(s, state)


def _layer0_moe(params):
    """Layer 0's expert layer: its own router and shared expert, and the
    expert stacks of every DeltaNet layer (``moe_dropless`` picks layer 0)."""
    moe = params["blocks"]["lin"]["moe"]
    return {name: w if name in ("wg", "wu", "wd") else jax.tree.map(lambda a: a[0], w)
            for name, w in moe.items()}


def _layer0_moe_input(cfg, seed, tokens):
    """Rows to feed layer 0's expert layer: the embeddings of ``tokens``."""
    r = ref.Reference(cfg, seed)
    x = r.pieces.embed(r.key, jnp.asarray(tokens))
    return r, x


@pytest.mark.parametrize("n_tokens", [1, 12])
def test_dropless_routing_under_skew(n_tokens):
    """Every token the same, so every token routes to the same experts:
    the held expert among them takes every token (a capacity of 1.25x the
    mean load would drop most of them) and the layer still equals the
    reference's.  One token takes the per-pair loop, twelve the grouped
    products."""
    mcfg, params = _program(TOY, 7)
    r, x = _layer0_moe_input(TOY, 7, np.full(n_tokens, 11, np.int32))
    with jax.default_matmul_precision("highest"):
        y, counters = jax.jit(lambda p, v: L.moe_dropless(p, mcfg, v, 0))(
            _layer0_moe(params), x[None])
    routed = np.asarray(r.pieces.moe_fn(r.pieces.layer_weights[False](r.key, jnp.int32(0)), x))
    _close(y[0], routed)
    held_pairs, max_load, touched = (int(c) for c in counters)
    assert held_pairs == touched * n_tokens and touched >= 1
    assert max_load == n_tokens


def test_expert_shares_add_up_to_the_uncut_layer():
    """Two chips holding experts 0-7 and 8-15 of 16: their parts, with the
    shared expert (computed by every chip alike) counted once, add up to
    the reference's layer with all 16 experts held."""
    whole = dict(TOY, expert_offset=0, num_experts=16)
    toks = np.random.default_rng(1).integers(0, TOY["vocab_size"], 24, dtype=np.int32)
    r, x = _layer0_moe_input(whole, 9, toks)
    want = r.pieces.moe_fn(r.pieces.layer_weights[False](r.key, jnp.int32(0)), x)
    parts, shared = [], None
    for offset in (0, 8):
        cfg = dict(TOY, expert_offset=offset, num_experts=8)
        mcfg, params = _program(cfg, 9)
        p = _layer0_moe(params)
        with jax.default_matmul_precision("highest"):
            y, _ = jax.jit(lambda q, v: L.moe_dropless(q, mcfg, v, 0))(p, x[None])
            shared = jax.nn.sigmoid(x @ p["shared_gate"]) * L.mlp(p["shared"], x)
        parts.append(y[0])
    _close(parts[0] + parts[1] - shared, want)


def test_served_through_the_engine_drops_no_token():
    """The registry's configuration, cut to a smoke size, serves through
    ``ContinuousBatchingEngine`` -> ``Session`` -> ``models/lm.py``: each
    request's tokens are those of serving it alone, and every expert layer
    computed all ``top_k`` pairs of every token (all experts held here)."""
    from repro import Session
    from repro.configs import get_config
    from repro.models import greedy_sample
    from repro.serving import ContinuousBatchingEngine, Request

    cfg = get_config("qwen3-next-80b-a3b").reduced()
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    counted = []
    prefill = jax.jit(lambda p, t: lm.prefill(p, cfg, {"tokens": t}, None, max_len=40,
                                              routing=True))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t, None, routing=True))

    def prefill_fn(prompt):
        cache, logits, c = prefill(params, prompt)
        counted.append((prompt.shape[1], c))
        return cache, logits

    def decode_fn(cache, tok):
        cache, logits, c = decode(params, cache, tok)
        counted.append((1, c))
        return cache, logits

    rng = np.random.default_rng(2)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size, (1, n), dtype=np.int32), 5)
            for i, n in enumerate((16, 24, 16))]
    with Session(2) as session:
        engine = ContinuousBatchingEngine(session, decode_fn, prefill_fn, max_batch=2)
        report = engine.run(reqs)
    served = report.tokens_by_rid()
    for req in reqs:
        cache, logits = prefill(params, req.prompt)[:2]
        alone = []
        for _ in range(req.max_new_tokens):
            tok = greedy_sample(logits)
            alone.append(int(np.asarray(tok).reshape(())))
            cache, logits = decode(params, cache, tok)[:2]
        assert served[req.rid] == alone
    for n_tok, c in counted:
        assert np.all(np.asarray(c)[:, 0] == n_tok * cfg.top_k)


#: sha256 of the HLO text of Qwen3-14B's prefill and decode (reduced,
#: bfloat16), as the tree before the gdn family lowered them
DENSE_HLO = ("7be1913812b0afff9fc74680c276f9cc8659f5b1f9a856d21b2d2b965280b08c",
             "8b990da069c63edbde332ff8e4abe7be621060c67477174eb713a3974a6d881f")


def test_dense_programs_lower_to_the_same_hlo():
    """The gate, partial rotary, zero-centred norm and routing branches are
    static: Qwen3-14B's prefill and decode lower to the text they lowered
    to before those branches existed."""
    from repro.configs import get_config

    cfg = get_config("qwen3-14b").reduced(dtype="bfloat16")
    params = lm.abstract_params(cfg)
    tok = jax.ShapeDtypeStruct((1, 16), jnp.int32)

    def bench_prefill_16(p, t):
        return lm.prefill(p, cfg, {"tokens": t}, None, max_len=24)

    def bench_decode(p, c, t):
        return lm.decode_step(p, cfg, c, t, None)

    # the program runs with float64 off; another test module may have
    # turned it on for this process, which changes the lowered text
    was = jax.config.jax_enable_x64
    jax.config.update("jax_enable_x64", False)
    try:
        pre = jax.jit(bench_prefill_16).lower(params, tok).as_text()
        cache, _ = jax.eval_shape(bench_prefill_16, params, tok)
        dec = jax.jit(bench_decode).lower(params, cache,
                                          jax.ShapeDtypeStruct((1, 1), jnp.int32)).as_text()
    finally:
        jax.config.update("jax_enable_x64", was)
    assert (hashlib.sha256(pre.encode()).hexdigest(),
            hashlib.sha256(dec.encode()).hexdigest()) == DENSE_HLO
