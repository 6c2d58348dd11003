"""Compiles for a described TPU v5e, run without the chip.

The TPU compiler is installed beside JAX and compiles for a chip that is
described and not attached.  These cases guard the main path at real
widths: the Qwen3-14B decode step and prefill at published widths (depth
cut to 2 layers) must fit one chip's memory, and the Pallas kernels must
lower to a TPU custom call.  Nothing runs, so nothing here says anything
about results or times.

The topology is described inside a module-scoped fixture: one process at a
time may load the TPU library, so describing it while modules are imported
would let one test worker take it from the others.  Keep every such case
in this one file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import ops
from repro.models import lm

HBM_BYTES = 16 * 2**30          # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 - any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a program compiled for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache out of it.
    # The program runs with float64 off; a test module that turns it on
    # for its process would make the kernels' Python float constants
    # float64, which Mosaic does not lower.
    was = {name: getattr(jax.config, name)
           for name in ("jax_enable_compilation_cache", "jax_enable_x64")}
    for name in was:
        jax.config.update(name, False)
    yield SingleDeviceSharding(topo.devices[0])
    for name, value in was.items():
        jax.config.update(name, value)


def _on(sharding, tree):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding), tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.fixture(scope="module")
def qwen3_14b():
    return dataclasses.replace(get_config("qwen3-14b"), n_layers=2).validate()


@pytest.mark.parametrize("step", ["decode_step", "prefill"])
def test_qwen3_14b_step_fits_one_chip(one_chip, qwen3_14b, step):
    cfg = qwen3_14b
    prompt, max_len = 512, 545
    params = _on(one_chip, lm.abstract_params(cfg))
    if step == "prefill":
        fn = jax.jit(lambda p, t: lm.prefill(p, cfg, {"tokens": t}, None,
                                             max_len=max_len))
        args = (params, _on(one_chip, jax.ShapeDtypeStruct((1, prompt), jnp.int32)))
    else:
        fn = jax.jit(lambda p, c, t: lm.decode_step(p, cfg, c, t, None))
        args = (params, _on(one_chip, lm.cache_struct(cfg, 1, max_len)),
                _on(one_chip, jax.ShapeDtypeStruct((1, 1), jnp.int32)))
    compiled = fn.lower(*args).compile()
    used = _device_bytes(compiled)
    assert 0 < used <= HBM_BYTES, f"{step} needs {used / 2**30:.2f} GiB"


def _kernel_cases():
    bf16, f32 = jnp.bfloat16, jnp.float32
    attn = jax.ShapeDtypeStruct((1, 40, 2048, 128), bf16)
    q1 = jax.ShapeDtypeStruct((1, 40, 128), bf16)
    kv = jax.ShapeDtypeStruct((1, 2048, 40, 128), bf16)
    tile = jax.ShapeDtypeStruct((192, 192), f32)
    return {
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, mode="pallas"),
            (attn, attn, attn)),
        "decode_attention": (
            lambda q, k, v, n: ops.decode_attention(q, k, v, n, mode="pallas"),
            (q1, kv, kv, jax.ShapeDtypeStruct((), jnp.int32))),
        "tile_matmul": (
            lambda a, b: ops.tile_matmul(a, b, mode="pallas"), (tile, tile)),
    }


@pytest.mark.parametrize("kernel", ["flash_attention", "decode_attention",
                                    "tile_matmul"])
def test_kernel_lowers_to_tpu_custom_call(one_chip, kernel):
    fn, shapes = _kernel_cases()[kernel]
    compiled = jax.jit(fn).lower(*_on(one_chip, shapes)).compile()
    assert "tpu_custom_call" in compiled.as_text()
