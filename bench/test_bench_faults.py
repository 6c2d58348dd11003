"""A run with the timed path broken underneath comes out not correct: the
harness's look for a chip is skipped and the rest of a run is driven on a
toy cell, once for each fault the cell can have."""

from __future__ import annotations

import jax.numpy as jnp
import pytest

from bench import testing


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return testing.toy_root(tmp_path_factory.mktemp("toy"))


def _state_unchanged(real):
    """A decode step that returns the cache it was given."""
    def decode_step(params, cfg, cache, tokens, ctx=None):
        _, logits = real(params, cfg, cache, tokens, ctx)
        return cache, logits
    return decode_step


def _token_altered(real):
    def greedy_sample(logits):
        return (real(logits) + 1) % logits.shape[-1]
    return greedy_sample


def test_serving_decode_that_keeps_its_state_is_caught(root, capsys, monkeypatch):
    from repro.models import lm

    monkeypatch.setattr(lm, "decode_step", _state_unchanged(lm.decode_step))
    line = testing.toy_run(root, "toy.batch", 5, 0, capsys)
    assert line["correct"] is False, line["checks"]


def test_serving_token_altered_where_sampled_is_caught(root, capsys, monkeypatch):
    import repro.models

    monkeypatch.setattr(repro.models, "greedy_sample",
                        _token_altered(repro.models.greedy_sample))
    line = testing.toy_run(root, "toy.poisson", 6, 0, capsys)
    assert line["correct"] is False, line["checks"]


def test_cholesky_update_that_keeps_its_tile_is_caught(root, capsys, monkeypatch):
    from repro.linalg import cholesky

    monkeypatch.setattr(cholesky, "tile_gemm_sub", lambda c, a, b: c)
    line = testing.toy_run(root, "toy.chol", 7, 0, capsys)
    assert line["correct"] is False, line["checks"]


def test_cholesky_answer_altered_where_produced_is_caught(root, capsys, monkeypatch):
    from repro.linalg import cholesky

    real = cholesky.tile_potrf
    monkeypatch.setattr(cholesky, "tile_potrf",
                        lambda a: real(a) * jnp.float32(1.0 + 1e-3))
    line = testing.toy_run(root, "toy.chol", 8, 0, capsys)
    assert line["correct"] is False, line["checks"]
