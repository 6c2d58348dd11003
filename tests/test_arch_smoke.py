"""Per-architecture smoke tests: reduced same-family configs, one forward /
loss(+grad) step and one prefill+decode step on CPU, asserting shapes and
no NaNs.  The FULL configs are exercised only via the dry-run."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models import (
    decode_step,
    forward,
    greedy_sample,
    init_params,
    loss_fn,
    prefill,
)
from repro.models.lm import logits_from_hidden, padded_vocab

B, S = 2, 64


def make_batch(cfg, key):
    kt, kl, kp = jax.random.split(key, 3)
    batch = {
        "tokens": jax.random.randint(kt, (B, S), 0, cfg.vocab_size),
        "labels": jax.random.randint(kl, (B, S), 0, cfg.vocab_size),
    }
    if cfg.family == "encdec":
        batch["enc_input"] = jax.random.normal(kp, (B, 32, cfg.d_model),
                                               cfg.jdtype)
    if cfg.family == "vlm":
        batch["patches"] = jax.random.normal(kp, (B, cfg.n_patches, cfg.d_model),
                                             cfg.jdtype)
    return batch


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_is_valid(arch):
    cfg = get_config(arch)
    assert cfg.param_count() > 1e8  # these are real multi-B-param configs
    assert padded_vocab(cfg) % 256 == 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_forward_and_loss(arch):
    cfg = get_config(arch).reduced()
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    batch = make_batch(cfg, jax.random.PRNGKey(1))

    h = jax.jit(lambda p, b: forward(p, cfg, b, None))(params, batch)
    assert h.shape == (B, S, cfg.d_model)
    assert not bool(jnp.any(jnp.isnan(h)))

    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: loss_fn(p, cfg, batch, None)))(params)
    assert np.isfinite(float(loss))
    # a full-vocab CE on random labels should sit near log(V)
    assert 0.2 * np.log(cfg.vocab_size) < float(loss) < 3.0 * np.log(cfg.vocab_size)
    gnorm = jax.tree_util.tree_reduce(
        lambda a, l: a + float(jnp.sum(jnp.abs(l))), grads, 0.0)
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_decode(arch):
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    max_len = S + 4

    cache, logits = jax.jit(
        lambda p, b: prefill(p, cfg, b, None, max_len=max_len))(params, batch)
    assert logits.shape[0] == B and logits.shape[1] == 1
    assert not bool(jnp.any(jnp.isnan(logits)))
    assert int(cache["index"]) == S

    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None].astype(jnp.int32)
    cache2, logits2 = jax.jit(
        lambda p, c, t: decode_step(p, cfg, c, t, None))(params, cache, tok)
    assert logits2.shape[1] == 1
    assert not bool(jnp.any(jnp.isnan(logits2)))
    assert int(cache2["index"]) == S + 1


@pytest.mark.parametrize("arch", ["deepseek-67b", "mamba2-2.7b", "zamba2-7b",
                                  "gemma3-12b", "qwen3-next-80b-a3b"])
def test_decode_matches_forward(arch):
    """Teacher-forced decode must reproduce the forward pass logits: run
    prefill on s tokens, then decode the next token and compare with the
    full-sequence forward."""
    cfg = get_config(arch).reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    full = make_batch(cfg, jax.random.PRNGKey(1))
    s0 = S - 1
    pre_batch = dict(full, tokens=full["tokens"][:, :s0])

    cache, logits_pre = jax.jit(
        lambda p, b: prefill(p, cfg, b, None, max_len=S + 1))(params, pre_batch)
    cache, logits_dec = jax.jit(
        lambda p, c, t: decode_step(p, cfg, c, t, None))(
            params, cache, full["tokens"][:, s0:s0 + 1])

    from repro.models.lm import logits_from_hidden
    h = jax.jit(lambda p, b: forward(p, cfg, b, None))(params, full)
    logits_full = logits_from_hidden(params, cfg, h)

    np.testing.assert_allclose(
        np.asarray(logits_dec[:, 0], dtype=np.float32),
        np.asarray(logits_full[:, s0], dtype=np.float32),
        rtol=2e-2, atol=2e-2)


def test_padded_vocab_is_never_sampled():
    """The vocabulary is padded to a multiple of 256 with columns that name
    no token; logits stop at the real vocabulary, so greedy sampling can
    only return real ids."""
    cfg = get_config("qwen3-14b").reduced(vocab_size=1000)
    assert padded_vocab(cfg) > cfg.vocab_size
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    logits = logits_from_hidden(params, cfg,
                                jax.jit(lambda p, b: forward(p, cfg, b, None))(
                                    params, batch))
    assert logits.shape == (B, S, cfg.vocab_size)
    ids = greedy_sample(logits.reshape(B * S, 1, cfg.vocab_size))
    assert int(ids.max()) < cfg.vocab_size
    _, last = jax.jit(lambda p, b: prefill(p, cfg, b, None, max_len=S + 1))(
        params, batch)
    assert int(greedy_sample(last).max()) < cfg.vocab_size
