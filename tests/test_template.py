"""Stage-template invariants + the strategy × format × edge-shape parity
matrix (ISSUE 3): every registered strategy, on every format it supports,
at the shapes that historically break tiled kernels — M not a multiple of
SUBLANE, K == group_size (a single scale group), and N == LANE — checked
against the format's reference oracle within analytic quantization bounds
(same quantized operands → only fp32 association differs)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quant import (
    get_format, per_channel_scales, quantize, w4a8_matmul_ref,
)
from repro.kernels import common, planning, ref, template
from repro.kernels.planning import KernelPlan, MatmulProblem

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# parity matrix
# ---------------------------------------------------------------------------

FORMATS = ("w4a16_g128", "w8a16_channel", "w4a8_g128")

EDGE_SHAPES = [
    # M, K, N — g=128 where the format is grouped (channel formats span K)
    (5, 256, 384),                    # M not a multiple of SUBLANE
    (8, 128, 256),                    # K == group_size: a single scale group
    (16, 256, common.LANE),           # N == LANE: one lane-wide block column
    (3, 128, common.LANE),            # all three edges at once
]


def _oracle(fmt_name, x, qt):
    if get_format(fmt_name).quantized_activations:
        return w4a8_matmul_ref(x, qt)           # same activation quant path
    return ref.w4a16_ref(x, qt)                 # float-activation formats


def _cases():
    for fmt in FORMATS:
        for strategy in planning.strategies_for_format(fmt):
            for shape in EDGE_SHAPES:
                yield fmt, strategy, shape


@pytest.mark.parametrize("fmt,strategy,shape", list(_cases()),
                         ids=lambda v: str(v))
def test_parity_matrix(fmt, strategy, shape):
    M, K, N = shape
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (K, N), jnp.float32)
    x = jax.random.normal(k2, (M, K), jnp.float32)
    qt = quantize(w, fmt)
    problem = MatmulProblem.from_operands(x, qt)
    strat = planning.get_strategy(strategy)
    if not strat.supports(problem):
        pytest.skip(f"{strategy} rejects {shape}")
    plan = planning.plan_matmul(problem, strategy=strategy, use_cache=False)
    got = np.asarray(planning.execute(plan, x, qt, interpret=True),
                     np.float32)
    want = np.asarray(_oracle(fmt, x, qt), np.float32)
    # same quantized operands: any difference is fp32 summation order,
    # bounded well below one rounding step of the quantization grid (s/2)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-3,
                               err_msg=f"{fmt}/{strategy}/{shape}")


def test_new_fused_kernels_are_registered_planner_strategies():
    """Acceptance: w8a16_fused / w4a8_fused are planner strategies the cost
    model actually picks on the target backend."""
    names = planning.available_strategies()
    assert "w8a16_fused" in names and "w4a8_fused" in names
    pick8 = planning.plan_matmul(
        MatmulProblem(M=16, N=1024, K=4096, group_size=4096, backend="tpu",
                      format="w8a16_channel"), use_cache=False)
    assert pick8.strategy == "w8a16_fused"
    pick48 = planning.plan_matmul(
        MatmulProblem(M=16, N=1024, K=4096, group_size=128, backend="tpu",
                      format="w4a8_g128"), use_cache=False)
    assert pick48.strategy == "w4a8_fused"
    # off-TPU the interpret penalty keeps the planner on the XLA paths
    cpu48 = planning.plan_matmul(
        MatmulProblem(M=16, N=1024, K=4096, group_size=128, backend="cpu",
                      format="w4a8_g128"), use_cache=False)
    assert cpu48.strategy == "w4a8_xla"


def test_planner_assigns_split_k_to_new_tiled_strategies(monkeypatch):
    """Splittability is a Strategy attribute, not a name list: the planner
    fills split_k for w4a8_fused in the decode regime (M=1, K ≫ N) exactly
    as it does for the w4a16 kernels — on a chip with cores to fill (a
    v5e has one TensorCore, so the count is set here)."""
    monkeypatch.setattr(planning, "num_cores", lambda: 8)
    plan = planning.plan_matmul(
        MatmulProblem(M=1, N=128, K=16384, group_size=128, backend="tpu",
                      format="w4a8_g128"),
        strategy="w4a8_fused", use_cache=False)
    assert plan.split_k > 1
    # XLA paths never get a split
    assert planning.get_strategy("w4a8_xla").splittable is False


def test_forced_split_k_paths_agree():
    """Split-K invariance holds for the new kernels too (paper Alg. 1)."""
    from repro.kernels.w4a8_fused import w4a8_fused
    from repro.kernels.w8a16_fused import w8a16_fused
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (512, 256), jnp.float32)
    x = jax.random.normal(k2, (4, 512), jnp.float32)
    qt8 = quantize(w, "w8a16_channel")
    base = w8a16_fused(x, qt8, split_k=1, interpret=True)
    np.testing.assert_allclose(
        np.asarray(w8a16_fused(x, qt8, split_k=2, interpret=True)),
        np.asarray(base), rtol=1e-5, atol=1e-4)
    qt48 = quantize(w, "w4a8_g128")
    base = w4a8_fused(x, qt48, split_k=1, interpret=True)
    np.testing.assert_allclose(
        np.asarray(w4a8_fused(x, qt48, split_k=2, interpret=True)),
        np.asarray(base), rtol=1e-5, atol=1e-4)


# ---------------------------------------------------------------------------
# block chooser: divisibility + the VMEM budget is enforced at launch time
# ---------------------------------------------------------------------------

def test_choose_blocks_divides_and_group_aligns():
    bc = template.choose_blocks(128, 1024, 4096, group_size=128,
                                weight_elt_bytes=0.5, has_scales=True,
                                dequant_tile=True)
    assert 128 % bc.bm == 0 and 1024 % bc.bn == 0
    assert (4096 // bc.split_k) % bc.bk == 0
    assert bc.bk % 128 == 0 or 128 % bc.bk == 0
    assert bc.nk == (4096 // bc.split_k) // bc.bk


def test_choose_blocks_enforces_vmem_budget():
    """A tiny budget shrinks bk (then bn) until the working set fits —
    the satellite: kernels enforce the budget, not only the autotuner."""
    budget = 2 * 1024 * 1024
    bc = template.choose_blocks(
        128, 1024, 4096, group_size=128, weight_elt_bytes=0.5,
        has_scales=True, dequant_tile=True, vmem_budget=budget)
    assert common.vmem_working_set(
        bc.bm, bc.bn, bc.bk, 128, weight_elt_bytes=0.5) <= budget
    # and the default-budget choice is unchanged from the target blocks
    bc_def = template.choose_blocks(128, 1024, 4096, group_size=128,
                                    weight_elt_bytes=0.5, has_scales=True,
                                    dequant_tile=True)
    assert (bc_def.bm, bc_def.bn, bc_def.bk) == (128, 256, 512)


def test_choose_blocks_refuses_misaligned_splits():
    with pytest.raises(ValueError, match="group-aligned"):
        template.choose_blocks(8, 256, 512, group_size=128, split_k=8)
    with pytest.raises(ValueError, match="divide K"):
        template.choose_blocks(8, 256, 512, split_k=3)


def test_budget_constrained_kernel_still_correct():
    """tiled_matmul under an artificially tiny budget picks smaller blocks
    and still matches the oracle."""
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (512, 256), jnp.float32)
    x = jax.random.normal(k2, (8, 512), jnp.float32)
    qt = quantize(w, group_size=128)
    got = template.tiled_matmul(
        x,
        template.GroupedInt4Dequant(qt.packed, qt.scales, qt.zeros),
        template.FloatContraction(),
        N=qt.N, group_size=qt.group_size,
        vmem_budget=512 * 1024, interpret=True)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.w4a16_ref(x, qt)),
                               rtol=1e-5, atol=1e-4)


def test_gemm_block_chooser_handles_unaligned_m():
    """The dead/duplicated bm computation in the old gemm() is gone: padded
    M routes through the shared chooser and stays correct for any M."""
    from repro.kernels.gemm import gemm
    for M in (1, 5, 8, 33):
        k1, k2 = jax.random.split(KEY)
        x = jax.random.normal(k1, (M, 256), jnp.float32)
        w = jax.random.normal(k2, (256, 128), jnp.float32)
        np.testing.assert_allclose(
            np.asarray(gemm(x, w, interpret=True)),
            np.asarray(ref.gemm_ref(x, w)), rtol=1e-5, atol=1e-4)


def test_per_channel_scales_helper():
    w = jax.random.normal(KEY, (64, 32), jnp.float32)
    qt = quantize(w, "w8a16_channel")
    s, z = per_channel_scales(qt)
    assert s.shape == (1, 32) and z is None
    with pytest.raises(ValueError, match="group-granular"):
        per_channel_scales(quantize(w, group_size=32))


def test_plan_roundtrip_for_new_strategies():
    """Plans for the new strategies JSON round-trip (cache compatibility)."""
    for name in ("w8a16_fused", "w4a8_fused"):
        plan = KernelPlan(strategy=name, split_k=2)
        assert KernelPlan.from_json(plan.to_json()) == plan
