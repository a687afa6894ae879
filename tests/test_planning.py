"""Plan-based API tests: registry dispatch parity, plan serialization,
plan-cache hit/miss + JSON persistence, the w4a16_matmul compatibility
shim, and the planner's strategy choice / Split-K edge cases."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import ASCEND
from repro.core.quant import quantize
from repro.kernels import ops, planning, ref
from repro.kernels.planning import (
    PLAN_CACHE, KernelPlan, MatmulProblem, PlanCache, choose_split_k,
    execute, plan_matmul, register_strategy, resolve_plan,
)

KEY = jax.random.PRNGKey(0)


def _operands(M=8, K=512, N=256, g=128):
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (K, N), jnp.float32)
    x = jax.random.normal(k2, (M, K), jnp.float32)
    return x, quantize(w, group_size=g)


# ---------------------------------------------------------------------------
# problem / plan objects
# ---------------------------------------------------------------------------

def test_problem_hashable_and_from_operands():
    x, qt = _operands()
    p1 = MatmulProblem.from_operands(x, qt)
    p2 = MatmulProblem.from_operands(x, qt)
    assert p1 == p2 and hash(p1) == hash(p2)
    assert (p1.M, p1.N, p1.K) == (8, 256, 512)
    assert p1.group_size == 128 and not p1.has_zeros
    # leading dims collapse into M
    p3 = MatmulProblem.from_operands(x.reshape(2, 4, 512), qt)
    assert p3 == p1
    assert MatmulProblem.from_dict(p1.to_dict()) == p1


def test_kernel_plan_json_round_trip():
    plan = KernelPlan(strategy="fused", split_k=4, block_m=64, block_n=128,
                      block_k=256, out_dtype="bfloat16")
    assert KernelPlan.from_json(plan.to_json()) == plan
    # defaulted fields survive too
    assert KernelPlan.from_json(KernelPlan(strategy="xla").to_json()) \
        == KernelPlan(strategy="xla")
    # the JSON is plain data (editable / diffable)
    blob = json.loads(plan.to_json())
    assert blob["strategy"] == "fused" and blob["split_k"] == 4


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registered_strategies_agree_with_oracle():
    """Every strategy supporting the tensor's format matches ref.w4a16_ref
    within tolerance (format-incompatible ones are refused — see
    tests/test_formats.py)."""
    x, qt = _operands()
    want = np.asarray(ref.w4a16_ref(x, qt))
    names = planning.strategies_for_format(qt.format.name)
    assert set(names) >= {"fused", "decoupled", "xla", "reference"}
    for name in names:
        plan = plan_matmul(MatmulProblem.from_operands(x, qt), strategy=name)
        got = execute(plan, x, qt, interpret=True)
        np.testing.assert_allclose(np.asarray(got), want,
                                   rtol=1e-4, atol=1e-3, err_msg=name)


def test_decoupled_is_registry_routed():
    """The paper pipeline is reachable via the registry alone — the
    "new strategy needs no dispatcher edits" acceptance check."""
    strat = planning.get_strategy("decoupled")
    x, qt = _operands()
    got = strat.execute(x, qt, KernelPlan(strategy="decoupled", split_k=2),
                        interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.w4a16_ref(x, qt)),
                               rtol=1e-4, atol=1e-3)


def test_register_strategy_plugs_into_planner():
    """A decorator-registered strategy is immediately planable/executable,
    and an irresistible cost model makes the planner pick it."""
    name = "_test_registered"
    try:
        @register_strategy(name, cost=lambda problem, plan: 0.0)
        def _run(x2, qt, plan, *, interpret=None):
            return ref.w4a16_ref(x2, qt)

        x, qt = _operands()
        problem = MatmulProblem.from_operands(x, qt)
        plan = plan_matmul(problem, use_cache=False)
        assert plan.strategy == name
        np.testing.assert_allclose(
            np.asarray(execute(plan, x, qt)),
            np.asarray(ref.w4a16_ref(x, qt)), rtol=1e-5, atol=1e-5)
    finally:
        planning._REGISTRY.pop(name, None)


def test_unknown_strategy_raises():
    with pytest.raises(ValueError, match="unknown strategy"):
        planning.get_strategy("no-such-kernel")


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------

def test_planner_prefers_xla_off_tpu_and_fused_on_tpu():
    base = dict(M=4, N=1024, K=4096, group_size=128,
                act_dtype="bfloat16", out_dtype="bfloat16")
    assert plan_matmul(MatmulProblem(backend="cpu", **base),
                       use_cache=False).strategy == "xla"
    assert plan_matmul(MatmulProblem(backend="tpu", **base),
                       use_cache=False).strategy == "fused"


def test_planner_falls_back_on_unsupported_shapes():
    """K not divisible by the group size: Pallas strategies are ineligible
    but the planner still returns a runnable plan."""
    problem = MatmulProblem(M=4, N=128, K=300, group_size=128, backend="tpu")
    plan = plan_matmul(problem, use_cache=False)
    assert plan.strategy in ("xla", "reference")
    # group-divisible odd K (hymba-style) stays Pallas-eligible
    ok = MatmulProblem(M=4, N=128, K=320, group_size=32, backend="tpu")
    assert plan_matmul(ok, use_cache=False).strategy == "fused"


def test_planner_refine_uses_tile_search():
    from repro.kernels.autotune import autotune_w4a16

    problem = MatmulProblem(M=8, N=1024, K=4096, backend="tpu")
    plan = plan_matmul(problem, strategy="fused", refine=True)
    bm, bn, bk, s = autotune_w4a16(8, 1024, 4096, group=128)
    assert (plan.block_m, plan.block_n, plan.block_k, plan.split_k) \
        == (bm, bn, bk, s)


def test_num_cores_one_per_v5e_chip(monkeypatch):
    """A kernel runs on one chip: however many v5e chips the host holds,
    its grid spreads over one TensorCore; an unknown TPU kind raises."""
    from repro.kernels import common

    class Chip:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    monkeypatch.setattr(jax, "local_devices", lambda: [Chip()] * 4)
    common.target_spec.cache_clear()
    try:
        assert planning.num_cores() == 1
        Chip.device_kind = "TPU v9 imaginary"
        common.target_spec.cache_clear()
        with pytest.raises(ValueError, match="no published peaks"):
            planning.num_cores()
    finally:
        monkeypatch.undo()
        common.target_spec.cache_clear()
    assert planning.num_cores() == 1       # a CPU host plans for a v5e


def test_gspmd_step_on_tpu_plans_no_pallas():
    """JAX cannot lower a compiled Pallas kernel into one program that
    GSPMD partitions over several chips: there the planner picks XLA GEMMs
    and the gather attention path, and refuses a forced fused path. In
    interpret mode (CPU meshes) the kernels stay eligible."""
    gemm = MatmulProblem(M=8, N=6912, K=2560, backend="tpu")
    assert plan_matmul(gemm, use_cache=False).strategy == "fused"
    spmd = dataclasses.replace(gemm, spmd=True)
    assert plan_matmul(spmd, use_cache=False).strategy == "xla"
    mesh = type("Mesh", (), {"shape": {"data": 2, "model": 2},
                             "axis_names": ("data", "model")})
    assert planning.shard_problem(gemm, mesh, "col").spmd
    mesh.shape = {"data": 1, "model": 1}
    assert not planning.shard_problem(gemm, mesh, "col").spmd
    assert not planning.spmd_traced()          # no ambient mesh here

    attn = planning.AttentionProblem(B=8, Hq=32, Hkv=8, D=80,
                                     cache_len=4096, page_size=8,
                                     backend="tpu")
    assert planning.plan_attention(attn).path == "fused"
    attn_spmd = dataclasses.replace(attn, spmd=True)
    assert planning.plan_attention(attn_spmd).path == "gather"
    with pytest.raises(ValueError, match="spmd=True"):
        planning.plan_attention(attn_spmd, path="fused")
    cpu = dataclasses.replace(attn_spmd, backend="cpu")
    assert planning.plan_attention(cpu, path="fused").path == "fused"


def test_choose_split_k_decode_regime_and_non_divisible_k(monkeypatch):
    # one v5e TensorCore: a single output tile already occupies the chip
    assert choose_split_k(1, 128, 16384) == 1
    monkeypatch.setattr(planning, "num_cores", lambda: ASCEND.num_cores)
    assert choose_split_k(1, 128, 16384) > 1             # decode regime
    assert choose_split_k(2048, 8192, 4096) == 1         # plenty of tiles
    # regression: K not divisible by group_size must not split (and must
    # not raise) — the old heuristic assumed divisibility
    assert choose_split_k(1, 128, 16384 + 64, group_size=128) == 1
    assert choose_split_k(1, 128, 100, group_size=128) == 1


# ---------------------------------------------------------------------------
# plan cache
# ---------------------------------------------------------------------------

def test_plan_cache_hit_miss_and_persistence(tmp_path):
    cache = PlanCache()
    x, qt = _operands()
    problem = MatmulProblem.from_operands(x, qt)

    p1 = plan_matmul(problem, cache=cache)
    assert (cache.hits, cache.misses, len(cache)) == (0, 1, 1)
    p2 = plan_matmul(problem, cache=cache)
    assert p2 == p1
    assert (cache.hits, cache.misses) == (1, 1)         # second call hits

    path = tmp_path / "plans.json"
    assert cache.save(str(path)) == 1
    fresh = PlanCache()
    assert fresh.load(str(path)) == 1
    assert fresh.get(problem) == p1                      # survives the disk trip
    assert fresh.hits == 1


def test_plan_cache_save_is_atomic(tmp_path, monkeypatch):
    """A crash mid-save must never truncate the shared plan-cache JSON:
    the write goes to a tmp file and lands via os.replace."""
    path = tmp_path / "plans.json"
    cache = PlanCache()
    cache.put(MatmulProblem(M=1, N=128, K=256), KernelPlan(strategy="xla"))
    cache.save(str(path))
    before = path.read_text()
    assert PlanCache().load(str(path)) == 1

    # serialization blowing up leaves the previous file byte-identical
    cache.put(MatmulProblem(M=2, N=128, K=256), KernelPlan(strategy="xla"))
    monkeypatch.setattr(planning.json, "dumps",
                        lambda *a, **k: (_ for _ in ()).throw(OSError("disk")))
    with pytest.raises(OSError):
        cache.save(str(path))
    monkeypatch.undo()
    assert path.read_text() == before
    # no tmp litter either way
    assert [p.name for p in tmp_path.iterdir()] == ["plans.json"]
    # and a clean save overwrites atomically with the new contents
    assert cache.save(str(path)) == 2
    assert PlanCache().load(str(path)) == 2


def test_refine_bypasses_stale_cache_hit():
    """refine=True must reach the tile search even when a heuristic plan is
    already cached (and the refined plan replaces it)."""
    from repro.kernels.autotune import autotune_w4a16

    cache = PlanCache()
    problem = MatmulProblem(M=8, N=1024, K=4096, backend="tpu")
    heuristic = plan_matmul(problem, cache=cache)
    refined = plan_matmul(problem, refine=True, cache=cache)
    bm, bn, bk, s = autotune_w4a16(8, 1024, 4096, group=128)
    assert (refined.block_m, refined.block_n, refined.block_k) == (bm, bn, bk)
    assert cache.get(problem) == refined            # overwrote the heuristic
    assert heuristic.strategy == refined.strategy == "fused"


def test_tolerant_load_survives_corrupt_and_missing_files(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text('{"version": 1, "plans": [{"nope"')
    with pytest.raises(ValueError):
        PlanCache().load(str(bad))
    assert planning.load_plan_cache(str(bad), tolerant=True) == -1
    assert planning.load_plan_cache(str(tmp_path / "gone.json"),
                                    tolerant=True) == -1
    # structurally-wrong-but-valid JSON raises ValueError, not TypeError
    for blob in ("[]", '{"version": 1, "plans": [{"problem": {"bogus": 1},'
                 ' "plan": {"strategy": "xla"}}]}'):
        bad.write_text(blob)
        with pytest.raises(ValueError):
            PlanCache().load(str(bad))


def test_load_drops_plans_for_unregistered_strategies(tmp_path):
    """A cache written by a build with extra strategies must not smuggle
    un-executable plans past loading (they'd crash at execute time)."""
    path = tmp_path / "future.json"
    cache = PlanCache()
    problem = MatmulProblem(M=1, N=128, K=256)
    cache.put(problem, KernelPlan(strategy="xla"))
    cache.put(dataclasses.replace(problem, M=2),
              KernelPlan(strategy="w4a8_from_the_future"))
    cache.save(str(path))
    fresh = PlanCache()
    assert fresh.load(str(path)) == 1                   # unknown one dropped
    assert fresh.get(problem) == KernelPlan(strategy="xla")


def test_plan_cache_distinguishes_problems():
    cache = PlanCache()
    a = MatmulProblem(M=1, N=1024, K=4096, backend="tpu")
    b = dataclasses.replace(a, M=512)
    plan_matmul(a, cache=cache)
    plan_matmul(b, cache=cache)
    assert len(cache) == 2 and cache.hits == 0


def test_plan_for_params_warm_starts_layer_lookups():
    """Pre-planned entries must be keyed exactly like the layer-time lookup
    (2-D scan slices, batch=1) — regression for the write-only warm-start."""
    from repro.core.quant import QuantizedTensor
    from repro.models import layers as L

    params = {"kernel": jax.random.normal(KEY, (3, 256, 128), jnp.float32)}
    qparams = L.quantize_tree(params, group_size=64, min_size=0)
    plans = planning.plan_for_params(qparams, M=4)
    assert set(plans) == {"256x128"}

    qt3 = qparams["kernel"]
    qt0 = QuantizedTensor(qt3.packed[0], qt3.scales[0], None,
                          qt3.group_size, qt3.out_dtype)   # one scan slice
    x = jnp.zeros((4, 256), jnp.float32)
    hits0 = PLAN_CACHE.hits
    got = plan_matmul(MatmulProblem.from_operands(x, qt0))
    assert PLAN_CACHE.hits == hits0 + 1                    # warm-start hit
    assert got == plans["256x128"]


def test_module_level_cache_round_trip(tmp_path):
    x, qt = _operands(M=3, K=256, N=128, g=64)
    problem = MatmulProblem.from_operands(x, qt)
    plan = plan_matmul(problem)                          # populates PLAN_CACHE
    path = tmp_path / "global.json"
    assert planning.save_plan_cache(str(path)) >= 1
    PLAN_CACHE._plans.pop(problem)
    assert planning.load_plan_cache(str(path)) >= 1
    assert PLAN_CACHE.get(problem) == plan


# ---------------------------------------------------------------------------
# config override resolution
# ---------------------------------------------------------------------------

def test_resolve_plan_honors_config_overrides():
    x, qt = _operands()
    problem = MatmulProblem.from_operands(x, qt)

    class Cfg:
        w4a16_strategy = "auto"
        w4a16_plan = None

    cfg = Cfg()
    assert resolve_plan(problem, cfg) == plan_matmul(problem)

    cfg.w4a16_strategy = "decoupled"
    assert resolve_plan(problem, cfg).strategy == "decoupled"

    pinned = KernelPlan(strategy="reference")
    cfg.w4a16_plan = pinned
    assert resolve_plan(problem, cfg) is pinned

    cfg.w4a16_plan = {problem.layer_key: {"strategy": "xla", "split_k": 1}}
    assert resolve_plan(problem, cfg).strategy == "xla"

    cfg.w4a16_plan = {"9999x9999": pinned}              # wrong layer: fall back
    assert resolve_plan(problem, cfg).strategy == "decoupled"

    cfg.w4a16_plan = KernelPlan(strategy="fused", split_k=2).to_json()
    assert resolve_plan(problem, cfg) == KernelPlan(strategy="fused",
                                                    split_k=2)


# ---------------------------------------------------------------------------
# compatibility shim
# ---------------------------------------------------------------------------

def test_w4a16_matmul_shim_matches_primary_path():
    x, qt = _operands()
    want = np.asarray(ref.w4a16_ref(x, qt))
    # "auto" == plan+execute
    got = ops.w4a16_matmul(x, qt)
    prim = execute(plan_matmul(MatmulProblem.from_operands(x, qt)), x, qt)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(prim))
    # named strategies and kwargs still work unchanged
    for s in ("fused", "decoupled", "xla", "reference"):
        o = ops.w4a16_matmul(x, qt, strategy=s, interpret=True)
        np.testing.assert_allclose(np.asarray(o), want,
                                   rtol=1e-4, atol=1e-3, err_msg=s)
    o = ops.w4a16_matmul(x, qt, strategy="fused", split_k=2, interpret=True)
    np.testing.assert_allclose(np.asarray(o), want, rtol=1e-4, atol=1e-3)
    with pytest.raises(ValueError, match="unknown strategy"):
        ops.w4a16_matmul(x, qt, strategy="bogus")


def test_shim_leading_dims_and_out_dtype():
    x, qt = _operands()
    y = ops.w4a16_matmul(x.reshape(2, 4, 512), qt, out_dtype=jnp.bfloat16)
    assert y.shape == (2, 4, 256) and y.dtype == jnp.bfloat16
