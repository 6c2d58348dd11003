"""``chip_smoke.py``: its phases at toy size on the CPU, and its refusal to
run anywhere but on a TPU from a checkout of the repository."""

import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

from repro.configs import get_config

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_serving_phase_at_toy_size(chip_smoke):
    # bf16 as on the chip, and a vocabulary that the model pads
    cfg = get_config("qwen3-14b").reduced(n_layers=2, vocab_size=1000,
                                          dtype="bfloat16")
    out = chip_smoke.serve(cfg, prompt_len=32, budget=(3, 5), n_requests=3,
                           rate=100.0, max_batch=2, workers=2)
    assert out["max_rel_err"] <= chip_smoke.LOGIT_TOL


def test_cholesky_phase_at_toy_size(chip_smoke):
    out = chip_smoke.factor(4, 32, workers=2)
    assert out["residual"] <= chip_smoke.RESIDUAL_BOUND


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, str(script)], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def _printed_a_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    if not lines:
        return False
    try:
        return "ok" in json.loads(lines[-1])
    except ValueError:
        return False


def test_refuses_to_run_without_a_tpu():
    r = _run(ROOT, SCRIPT)
    assert r.returncode != 0
    assert "no TPU present" in r.stderr
    assert not _printed_a_result(r.stdout)


def test_refuses_to_run_outside_a_checkout(tmp_path):
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    r = _run(tmp_path, tmp_path / "chip_smoke.py")
    assert r.returncode != 0
    assert not _printed_a_result(r.stdout)
