"""Dry-run machinery tested in-process on a small host-device mesh.

The production dry-run needs 512 devices (subprocess, see launch/dryrun.py);
here we validate the same build_cell plumbing end-to-end on an 8-device
debug mesh via a subprocess so the XLA device-count flag doesn't leak into
the rest of the suite.
"""

import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8 " \
    "--xla_disable_hlo_passes=while-loop-invariant-code-motion"
import json
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import lm
from repro.sharding.rules import make_ctx
from repro.launch.shapes import input_specs
from repro.launch import hlo_analysis
from repro.launch.mesh import make_debug_mesh
from repro.train.steps import StepConfig, make_train_step
from repro.optim.adamw import AdamWConfig

mesh = make_debug_mesh(4, 2)
cfg = get_config("deepseek-67b").reduced(n_layers=3, d_model=128, vocab_size=1024,
                                         n_heads=4, n_kv_heads=2, head_dim=32,
                                         d_ff=256, dtype="bfloat16")
ctx = make_ctx(mesh, cfg)
pspecs = lm.param_pspecs(cfg, ctx)
param_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), pspecs,
                        is_leaf=lambda x: isinstance(x, P))
params = lm.abstract_params(cfg)
batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
         "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
batch_sh = {k: NamedSharding(mesh, P(("data",), None)) for k in batch}
opt = {"m": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
       "v": jax.tree.map(lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
       "step": jax.ShapeDtypeStruct((), jnp.int32)}
opt_sh = {"m": param_sh, "v": param_sh, "step": NamedSharding(mesh, P())}
fn = make_train_step(cfg, AdamWConfig(), ctx, StepConfig(microbatches=2),
                     grad_pspecs=param_sh)
jitted = jax.jit(fn, in_shardings=(param_sh, opt_sh, batch_sh),
                 out_shardings=(param_sh, opt_sh, None))
with mesh:
    lowered = jitted.lower(params, opt, batch)
    compiled = lowered.compile()
mem = compiled.memory_analysis()
hlo = compiled.as_text()
out = {
    "temp": int(mem.temp_size_in_bytes),
    "collectives": hlo_analysis.collective_bytes(hlo)["total_bytes"],
    "dot_flops": hlo_analysis.dot_flops(hlo),
}
print("RESULT:" + json.dumps(out))
"""


@pytest.mark.slow
def test_small_mesh_train_cell_compiles():
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], capture_output=True,
                       text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert out["temp"] > 0
    assert out["collectives"] > 0          # grad reductions present
    assert out["dot_flops"] > 0            # trip-count-scaled matmuls counted
