"""The plain references agree with the program where both are right, and
their controls (the next precision down) are told apart from it."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import testing
from bench.reference import cholesky as chol_ref
from bench.reference import dense_lm as ref

CFG = json.loads((testing.REPO / "bench/configs/qwen3-14b-l8.json").read_text())
CFG.update(testing.TOY_LM)


def _program(cfg, seed):
    """The program's prefill and decode over the benchmark's weights."""
    from bench.drivers.lm_serve import _to_program_tree, model_config
    from repro.models import lm

    mcfg = model_config(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    params = jax.jit(lambda k: _to_program_tree(ref.draw_all(k, cfg, dtype),
                                                lm.abstract_params(mcfg)))(ref.seed_key(seed))
    return lm, mcfg, params


def test_stacked_weights_are_the_per_layer_draws():
    key = ref.seed_key(2**33 + 5)
    stacked = jax.jit(lambda k: ref.draw_all(k, CFG, jnp.bfloat16))(key)
    for name in ("wq", "wd", "gamma_k"):
        shape, fan_in = ref.shapes(CFG)[name]
        for layer in range(CFG["num_hidden_layers"]):
            one = ref.draw(key, name, layer, shape, fan_in, jnp.bfloat16)
            assert np.array_equal(np.asarray(stacked[name][layer], np.float32),
                                  np.asarray(one, np.float32))


def test_reference_agrees_with_program_prefill_then_decode():
    """In float32 the program's prefill-then-decode logits and the
    reference's full forward pass agree to float32 rounding."""
    cfg = dict(CFG, torch_dtype="float32")
    lm, mcfg, params = _program(cfg, 3)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg["vocab_size"], (1, 12), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        cache, logits = lm.prefill(params, mcfg, {"tokens": jnp.asarray(prompt)}, None,
                                   max_len=24)
        rows = [logits[0, -1]]
        fed = []
        for _ in range(5):
            tok = jnp.argmax(rows[-1]).astype(jnp.int32).reshape(1, 1)
            fed.append(int(tok[0, 0]))
            cache, logits = lm.decode_step(params, mcfg, cache, tok, None)
            rows.append(logits[0, -1])
    program = np.asarray(jnp.stack(rows), np.float64)
    seq = np.concatenate([prompt[0], fed])
    got = ref.Reference(cfg, 3, q_block=8).logits(
        [seq], [ref.positions(12, 6, 8)], 24)[0]
    want = np.asarray(got, np.float64)[:6]
    scale = np.abs(want).max()
    assert np.abs(program - want).max() <= 1e-4 * scale


def _served(cfg, seed, n_tokens):
    """Greedy tokens of the program in its served precision (bfloat16)."""
    lm, mcfg, params = _program(cfg, seed)
    prompt = np.random.default_rng(seed).integers(0, cfg["vocab_size"], (1, 16),
                                                  dtype=np.int32)
    prefill = jax.jit(lambda p, t: lm.prefill(p, mcfg, {"tokens": t}, None,
                                              max_len=16 + n_tokens + 1))
    decode = jax.jit(lambda p, c, t: lm.decode_step(p, mcfg, c, t, None))
    cache, logits = prefill(params, jnp.asarray(prompt))
    out = []
    for _ in range(n_tokens):
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        out.append(int(tok[0, 0]))
        cache, logits = decode(params, cache, tok)
    return prompt, np.asarray(out)


def test_fp8_control_is_told_apart_from_the_served_model():
    """The control's mean gap reads several times the program's, and the
    harness's own comparison, under the toy cell's limits, finds the
    program correct and the control not."""
    from bench.drivers.lm_serve import Rec, check
    from bench.harness import compare, verdict

    limits = testing.TOY_LIMITS["toy.batch"]
    readings = []
    for seed in (2, 3, 4):
        prompt, toks = _served(CFG, seed, 40)
        rec = Rec(0, 0.0, prompt, len(toks), tokens=list(toks))
        got, lo = check(CFG, seed, [rec], 16, 40, control=True)
        assert verdict(compare(got, limits)) is True, (got, lo)
        assert verdict(compare(lo, limits)) is False, (got, lo)
        readings.append((got["mean_logit_gap"], lo["mean_logit_gap"]))
    program = max(p for p, _ in readings)
    control = min(c for _, c in readings)
    assert control >= 3 * program, readings


def test_reference_without_qk_norm_and_with_a_tied_head():
    """A Llama-like configuration (no q/k norm, head tied to the embedding)
    needs only keys of its file: the program over the benchmark's weights
    agrees with the reference in float32."""
    cfg = dict(CFG, torch_dtype="float32", qk_norm=False, tie_word_embeddings=True)
    assert "gamma_q" not in ref.shapes(cfg) and "unembed" not in ref.shapes(cfg)
    lm, mcfg, params = _program(cfg, 6)
    assert "unembed" not in params and "gamma_q" not in params["blocks"]["attn"]
    prompt = np.random.default_rng(1).integers(0, cfg["vocab_size"], (1, 8), dtype=np.int32)
    with jax.default_matmul_precision("highest"):
        _, logits = lm.prefill(params, mcfg, {"tokens": jnp.asarray(prompt)}, None,
                               max_len=9)
    want = np.asarray(ref.Reference(cfg, 6, q_block=8).logits(
        [prompt[0]], [ref.positions(8, 1, 1)], 8)[0], np.float64)[0]
    got = np.asarray(logits[0, -1], np.float64)
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()


def test_cholesky_reference_and_control():
    """The float32 factor at ``highest`` lies near float64 LAPACK; the
    control's products in three bfloat16 passes lie at least three times
    further, and beyond the toy limit."""
    n, b = 64, 16
    limit = testing.TOY_LIMITS["toy.chol"]["factor_rel_error"]["limit"]
    for seed in (1, 2, 3):
        a = chol_ref.make_spd(ref.seed_key(seed), n)
        l_ref = chol_ref.reference_factor(np.asarray(a))
        sound = chol_ref.relative_error(
            np.asarray(chol_ref.tiled_cholesky(a, b, "highest")), l_ref)
        control = chol_ref.relative_error(
            np.asarray(chol_ref.tiled_cholesky(a, b, "high")), l_ref)
        assert sound < limit < control and control >= 3 * sound, (sound, control)


def test_bf16x3_product_is_between_one_pass_and_float32():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    exact = np.asarray(a, np.float64) @ np.asarray(a, np.float64).T
    err3 = np.abs(np.asarray(chol_ref.dot_bf16x3(a, a.T)) - exact).max()
    one = jnp.matmul(a.astype(jnp.bfloat16), a.T.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32)
    err1 = np.abs(np.asarray(one) - exact).max()
    assert err3 < err1 / 20


@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2**40])
def test_seed_key_takes_large_seeds(seed):
    k = ref.seed_key(seed)
    assert jax.random.key_data(k).shape == (2,)
    assert not np.array_equal(jax.random.key_data(k), jax.random.key_data(ref.seed_key(seed + 1)))
