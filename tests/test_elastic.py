"""Elastic rescale: re-lower the same step on a degraded mesh (lost slice).

Runs in a subprocess with 512 fake devices: lowers h2o train on the full
16×16 mesh, then rebuilds a 15×16 mesh via `degraded_mesh` (one data row
lost) and re-lowers — proving the sharding rules hold off the power-of-two
path, which is what elastic restart on survivors requires.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json
import jax
import jax.numpy as jnp

from repro import configs
from repro.configs import SHAPES, input_specs
from repro.launch.mesh import make_production_mesh, degraded_mesh
from repro.launch.presets import settings_for
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import steps as rsteps

arch = "h2o-danube-1.8b"
cfg = configs.get_config(arch)
shape = SHAPES["train_4k"]
settings = settings_for(arch)
params_abs = T.abstract_params(cfg)
opt_cfg = AdamWConfig(state_dtype=settings.opt_dtype)
opt_abs = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)
specs = input_specs(cfg, shape)
inputs_abs = {"batch": specs["batch"],
              "step": jax.ShapeDtypeStruct((), jnp.int32)}

import dataclasses
out = {}
for name, mesh in [("full", make_production_mesh()),
                   ("degraded", degraded_mesh(make_production_mesh(),
                                              drop_data=1))]:
    if name == "degraded":
        # elastic restart keeps per-device batch constant: 256 → 240 on the
        # 15×16 survivor mesh (the data pipeline takes any per-host batch)
        shape2 = dataclasses.replace(shape, global_batch=240)
        specs = input_specs(cfg, shape2)
        inputs_abs = {"batch": specs["batch"],
                      "step": jax.ShapeDtypeStruct((), jnp.int32)}
    with jax.set_mesh(mesh):
        fn = rsteps.jit_train_step(cfg, mesh, settings, params_abs,
                                   inputs_abs, opt_cfg)
        compiled = fn.lower(params_abs, opt_abs, inputs_abs).compile()
    m = compiled.memory_analysis()
    out[name] = {
        "devices": int(mesh.devices.size),
        "peakGB": round((m.argument_size_in_bytes + m.temp_size_in_bytes
                         + m.output_size_in_bytes) / 1e9, 2),
    }
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_degraded_mesh_relowers():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["full"]["devices"] == 256
    assert out["degraded"]["devices"] == 240     # 15 × 16 survivors
    assert out["degraded"]["peakGB"] < 16.0
