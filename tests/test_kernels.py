"""Per-kernel shape/dtype sweeps: every Pallas kernel vs its ref.py oracle
(interpret=True on CPU; the kernels target TPU BlockSpec tiling)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import ASCEND
from repro.core.quant import quantize
from repro.kernels import ops, planning, ref
from repro.kernels.gemm import gemm
from repro.kernels.w4a16_decoupled import (
    dequant_w4, reduce_partials, splitk_gemm, w4a16_decoupled,
)
from repro.kernels.w4a16_fused import w4a16_fused

KEY = jax.random.PRNGKey(0)


def tol(dt):
    return dict(rtol=2e-2, atol=2e-2) if dt == jnp.bfloat16 \
        else dict(rtol=2e-4, atol=2e-4)


def rel_close(got, want, dt):
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = max(np.abs(want).max(), 1.0)
    np.testing.assert_allclose(got / scale, want / scale, **tol(dt))


SWEEP = [
    # M, K, N, group, symmetric, dtype
    (8, 256, 128, 128, True, jnp.float32),
    (1, 512, 128, 64, True, jnp.bfloat16),      # decode-like: M=1, K>N
    (16, 1024, 256, 128, False, jnp.float32),   # asymmetric (zero-points)
    (33, 384, 256, 128, True, jnp.float32),     # M not sublane-aligned
    (4, 512, 384, 256, True, jnp.bfloat16),     # group > default block
    (2, 320, 128, 32, True, jnp.float32),       # odd K (hymba-style)
]


@pytest.mark.parametrize("M,K,N,g,sym,dt", SWEEP)
def test_w4a16_fused_vs_oracle(M, K, N, g, sym, dt):
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (K, N), jnp.float32)
    x = jax.random.normal(k2, (M, K), jnp.float32).astype(dt)
    qt = quantize(w, group_size=g, symmetric=sym, out_dtype=dt)
    want = ref.w4a16_ref(x, qt)
    got = w4a16_fused(x, qt, interpret=True)
    rel_close(got, want, dt)


@pytest.mark.parametrize("M,K,N,g,sym,dt", SWEEP)
def test_w4a16_decoupled_vs_oracle(M, K, N, g, sym, dt):
    k1, k2 = jax.random.split(KEY)
    w = jax.random.normal(k1, (K, N), jnp.float32)
    x = jax.random.normal(k2, (M, K), jnp.float32).astype(dt)
    qt = quantize(w, group_size=g, symmetric=sym, out_dtype=dt)
    want = ref.w4a16_ref(x, qt)
    sk = 4 if (K % 4 == 0 and (K // 4) % g == 0) else 1
    got = w4a16_decoupled(x, qt, split_k=sk, interpret=True)
    rel_close(got, want, dt)


@pytest.mark.parametrize("M,K,N,dt", [
    (8, 256, 128, jnp.float32), (1, 512, 256, jnp.bfloat16),
    (64, 1024, 512, jnp.bfloat16), (5, 128, 128, jnp.float32),
])
def test_gemm_vs_oracle(M, K, N, dt):
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (M, K), jnp.float32).astype(dt)
    w = jax.random.normal(k2, (K, N), jnp.float32).astype(dt)
    rel_close(gemm(x, w, interpret=True), ref.gemm_ref(x, w), dt)


@pytest.mark.parametrize("K,N,g,sym", [
    (256, 128, 128, True), (512, 256, 64, False), (1024, 128, 256, True),
])
def test_phase1_dequant_kernel(K, N, g, sym):
    w = jax.random.normal(KEY, (K, N), jnp.float32)
    qt = quantize(w, group_size=g, symmetric=sym, out_dtype=jnp.bfloat16)
    want = ref.dequant_ref(qt.packed, qt.scales, qt.zeros, g, jnp.bfloat16)
    got = dequant_w4(qt, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("S", [1, 2, 4, 8])
def test_phase2_splitk_partials(S):
    M, K, N = 8, 1024, 128
    k1, k2 = jax.random.split(KEY)
    x = jax.random.normal(k1, (M, K), jnp.float32)
    w = jax.random.normal(k2, (K, N), jnp.float32)
    got = splitk_gemm(x, w, split_k=S, interpret=True)
    want = ref.splitk_partials_ref(x, w, S)
    assert got.shape == (S, M, N) and got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_phase3_reduce():
    parts = jax.random.normal(KEY, (4, 16, 128), jnp.float32)
    got = reduce_partials(parts, out_dtype=jnp.bfloat16, interpret=True)
    want = ref.reduce_ref(parts, jnp.bfloat16)
    rel_close(got, want, jnp.bfloat16)


@pytest.mark.parametrize("S", [1, 2, 4])
def test_splitk_invariance_fused(S):
    """Paper Alg. 1 invariant: the result is independent of the split factor."""
    M, K, N = 4, 1024, 128
    w = jax.random.normal(KEY, (K, N), jnp.float32)
    x = jax.random.normal(KEY, (M, K), jnp.float32)
    qt = quantize(w, group_size=128)
    base = w4a16_fused(x, qt, split_k=1, interpret=True)
    got = w4a16_fused(x, qt, split_k=S, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(base),
                               rtol=1e-5, atol=1e-4)


def test_strategies_agree():
    """fused ≡ decoupled ≡ xla ≡ reference on the same quantized weight."""
    M, K, N = 8, 512, 256
    w = jax.random.normal(KEY, (K, N), jnp.float32)
    x = jax.random.normal(KEY, (M, K), jnp.float32)
    qt = quantize(w, group_size=128)
    outs = {
        s: ops.w4a16_matmul(x, qt, strategy=s, interpret=True)
        for s in ("fused", "decoupled", "xla", "reference")
    }
    for s, o in outs.items():
        np.testing.assert_allclose(
            np.asarray(o), np.asarray(outs["reference"]),
            rtol=1e-5, atol=1e-4, err_msg=s)


def test_batched_leading_dims():
    """w4a16_matmul contracts the last dim of arbitrary leading shapes."""
    w = jax.random.normal(KEY, (256, 128), jnp.float32)
    x = jax.random.normal(KEY, (2, 3, 256), jnp.float32)
    qt = quantize(w, group_size=64)
    y = ops.w4a16_matmul(x, qt, strategy="fused", interpret=True)
    assert y.shape == (2, 3, 128)
    want = ref.w4a16_ref(x.reshape(-1, 256), qt).reshape(2, 3, 128)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=1e-5, atol=1e-4)


def test_choose_split_k_heuristic(monkeypatch):
    """K≫N with small M (LLM decode) → split on a many-core chip (the
    paper's Ascend); big output tiles → don't."""
    monkeypatch.setattr(planning, "num_cores", lambda: ASCEND.num_cores)
    assert ops.choose_split_k(1, 128, 16384) > 1          # decode regime
    assert ops.choose_split_k(4, 256, 8192) > 1
    assert ops.choose_split_k(2048, 8192, 4096) == 1      # plenty of tiles
    assert ops.choose_split_k(1, 128, 128) == 1           # K too shallow
