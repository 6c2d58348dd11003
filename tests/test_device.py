"""The device module: naming the device, refusing the CPU where a chip is
required, refusing child processes on a TPU, and placing the compile
cache."""

import multiprocessing
import os
import subprocess
import sys

import jax
import pytest

import repro
from repro.device import (CHECKOUT_ROOT, NoAcceleratorError, describe_device,
                          refuse_child_processes)
from repro.mp import ProcessPool
from repro.serving import ContinuousBatchingEngine

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_describe_device_names_what_jax_reports():
    dev = describe_device()
    assert dev.platform == jax.devices()[0].platform
    assert dev.kind == jax.devices()[0].device_kind
    assert dev.count == len(jax.devices())
    assert dev.as_dict() == {"platform": dev.platform, "kind": dev.kind,
                             "count": dev.count}


def test_require_tpu_refuses_the_cpu():
    with pytest.raises(NoAcceleratorError, match="no TPU present"):
        describe_device(require_tpu=True)


@pytest.fixture
def on_tpu(monkeypatch):
    """Make this process look as if it computed on a TPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_child_processes_refused_on_tpu(on_tpu):
    with pytest.raises(RuntimeError, match="owns the TPU"):
        refuse_child_processes("a pool")


def test_engine_procs_refused_at_once_on_tpu(on_tpu):
    with repro.Session(1) as session:
        with pytest.raises(RuntimeError, match="owns the TPU"):
            ContinuousBatchingEngine(session, lambda c, t: (c, t),
                                     lambda p: (p, p), procs=2,
                                     fns_ref="serve_lm:make_serving_fns")
    assert multiprocessing.active_children() == []


def test_process_pool_refused_before_spawning_on_tpu(on_tpu):
    with pytest.raises(RuntimeError, match="owns the TPU"):
        ProcessPool(2)
    assert multiprocessing.active_children() == []


def _cache_dir_in_fresh_process(env_value):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_value is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_value
    code = ("import jax; from repro.device import enable_compile_cache; "
            "print(enable_compile_cache()); "
            "print(jax.config.jax_compilation_cache_dir)")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, env=env, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    returned, configured = r.stdout.split()[-2:]
    return returned, configured


@pytest.mark.parametrize("env_value", [None, "placed-from-outside"])
def test_compile_cache_placement(env_value, tmp_path):
    want = (str(CHECKOUT_ROOT / ".jax_cache") if env_value is None
            else str(tmp_path / env_value))
    returned, configured = _cache_dir_in_fresh_process(
        None if env_value is None else want)
    assert returned == configured == want
