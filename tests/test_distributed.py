"""Multi-device SPMD tests (subprocess with 8 fake CPU devices).

Verifies the sharded train step is numerically equivalent to single-device
execution, and that the sharded W4A16 matmul (shard_map + fused Pallas
kernel) matches the oracle — the TP-composability claim of DESIGN.md.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import configs
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import steps as rsteps
from repro.runtime import sharding as shd

out = {}

# ---- sharded vs single-device train step equivalence --------------------
cfg = configs.get_reduced("h2o-danube-1.8b")
key = jax.random.PRNGKey(0)
params = T.init_params(key, cfg)
opt_cfg = AdamWConfig(lr=1e-3)
opt = adamw_init(params, opt_cfg)
settings = rsteps.TrainSettings(microbatches=2, fsdp=True)
toks = jax.random.randint(key, (8, 16), 0, cfg.vocab_size)
inputs = {"batch": {"tokens": toks, "labels": toks},
          "step": jnp.zeros((), jnp.int32)}

single = jax.jit(rsteps.make_train_step(cfg, opt_cfg, settings))
p1, o1, m1 = single(params, opt, inputs)

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
with jax.set_mesh(mesh):
    fn = rsteps.jit_train_step(
        cfg, mesh, settings,
        jax.eval_shape(lambda: params),
        jax.eval_shape(lambda: inputs), opt_cfg)
    p2, o2, m2 = fn(params, opt, inputs)
out["loss_single"] = float(m1["loss"])
out["loss_sharded"] = float(m2["loss"])
diffs = jax.tree.map(
    lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)))), p1, p2)
out["param_maxdiff"] = max(jax.tree.leaves(diffs))

# ---- shard_map + fused Pallas kernel TP-composability --------------------
from repro.core.quant import quantize
from repro.kernels import ref
from repro.kernels.w4a16_fused import w4a16_fused

K, N, M = 512, 256, 8
w = jax.random.normal(key, (K, N), jnp.float32)
x = jax.random.normal(key, (M, K), jnp.float32)
qt = quantize(w, group_size=64)

def per_shard(x, packed, scales):
    from repro.core.quant import QuantizedTensor
    q = QuantizedTensor(packed, scales, None, 64, jnp.dtype(jnp.float32))
    return w4a16_fused(x, q, interpret=True)

tp = jax.shard_map(
    per_shard, mesh=mesh,
    in_specs=(P(None, None), P(None, "model"), P(None, "model")),
    out_specs=P(None, "model"), check_vma=False)
with jax.set_mesh(mesh):
    y = tp(x, qt.packed, qt.scales)
want = ref.w4a16_ref(x, qt)
out["tp_w4a16_err"] = float(jnp.abs(y - want).max())
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_spmd_equivalence_and_tp_kernel():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env.pop("JAX_PLATFORMS", None)
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert abs(out["loss_single"] - out["loss_sharded"]) < 1e-3, out
    assert out["param_maxdiff"] < 1e-2, out
    assert out["tp_w4a16_err"] < 1e-3, out
