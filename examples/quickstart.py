"""Quickstart: the paper's W4A16 GEMM via the plan-based API, then a
quantized layer.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.core.quant import quantize, dequantize
from repro.kernels import planning

key = jax.random.PRNGKey(0)

# 1. Quantize an FP weight matrix to INT4 with group-wise scales (Eq. 1).
K, N = 4096, 1024                         # K >> N: the LLM-decode regime
w = jax.random.normal(key, (K, N), jnp.float32)
qt = quantize(w, group_size=128)
print(f"weight: {w.nbytes/1e6:.1f} MB fp32 -> {qt.nbytes_packed()/1e6:.1f} MB "
      f"packed int4 (+scales)")

# 2. The primary path: describe the problem, plan it, execute the plan.
x = jax.random.normal(key, (4, K), jnp.float32)     # small M, like decoding
problem = planning.MatmulProblem.from_operands(x, qt)
plan = planning.plan_matmul(problem)                # cost-model planner
y = planning.execute(plan, x, qt)
err = float(jnp.abs(y - x @ dequantize(qt)).max())
print(f"planned: {plan.strategy} split_k={plan.split_k} "
      f"out={y.shape} max|err|={err:.2e}")

# 3. Any strategy supporting the tensor's QuantFormat can be forced —
#    same execute, no dispatcher (format-incompatible ones are refused).
for strategy in planning.strategies_for_format(qt.format.name):
    p = planning.plan_matmul(problem, strategy=strategy)
    y = planning.execute(p, x, qt)
    err = float(jnp.abs(y - x @ dequantize(qt)).max())
    print(f"  strategy={strategy:10s} out={y.shape} max|err|={err:.2e}")

# 4. Decisions are memoized process-wide and persist to JSON.
assert planning.plan_matmul(problem) == plan        # cache hit
n = planning.save_plan_cache("/tmp/repro_quickstart_plans.json")
print(f"plan cache: {n} plan(s) persisted "
      f"({planning.PLAN_CACHE.hits} hits / {planning.PLAN_CACHE.misses} "
      f"misses); split_k for (M=4, N={N}, K={K}):",
      planning.choose_split_k(4, N, K))

# 5. A quantized model layer end-to-end (linear() plans internally).
from repro.models import layers

p = layers.init_linear(key, K, N, jnp.float32)
p["kernel"] = quantize(p["kernel"], group_size=128)
y = layers.linear(p, x)
print("quantized Linear:", y.shape, "finite:", bool(jnp.all(jnp.isfinite(y))))

# 6. Quantization formats are first-class and registered: the same plan →
#    execute path runs W8A16 (per-channel int8) and W4A8 (dynamic int8
#    activations, LiquidGEMM-style) — the planner only considers
#    strategies that declare support for the tensor's format.
from repro.core import quant

for fmt_name in quant.available_formats():
    qf = quantize(w, fmt_name)
    prob = planning.MatmulProblem.from_operands(x, qf)
    pf = planning.plan_matmul(prob)
    err = float(jnp.abs(planning.execute(pf, x, qf) - x @ w).max())
    print(f"  format={fmt_name:14s} bits=w{qf.format.weight_bits} "
          f"scales={tuple(qf.scales.shape)} -> {pf.strategy:9s} "
          f"max|err vs fp32|={err:.2e}")
