"""The paper's quantitative claims, reproduced by the calibrated Ascend model.

These are the EXPERIMENTS.md validation gates: if the model drifts away from
the paper's published numbers, these tests fail.
"""
import numpy as np
import pytest

from repro.configs import PAPER_BATCH_SIZES, PAPER_GEMM_SHAPES
from repro.core import costmodel as cm


def sweep(fn):
    return np.array([[fn(M, N, K) for M in PAPER_BATCH_SIZES]
                     for (N, K) in PAPER_GEMM_SHAPES])


def test_fig2_splitk_speedup_range():
    """Paper §4.1: Split-K over data-parallel = 1.01×–1.74× and never a loss."""
    s = sweep(cm.splitk_speedup_ascend)
    assert s.min() >= 1.0 - 1e-9
    assert 1.5 <= s.max() <= 1.9, s.max()


def test_fig2_splitk_wins_when_k_much_larger_than_n():
    """Paper §4.1: 'when K is significantly larger than N, Split-K
    outperforms data-parallel approaches'."""
    gains_kgn, gains_other = [], []
    for (N, K) in PAPER_GEMM_SHAPES:
        for M in PAPER_BATCH_SIZES:
            g = cm.splitk_speedup_ascend(M, N, K)
            (gains_kgn if K >= 4 * N else gains_other).append(g)
    assert max(gains_kgn) > 1.3
    assert np.mean(gains_kgn) > np.mean(gains_other)


def test_fig3_w4a16_speedup_capped_at_1p48():
    """Paper §4.2 headline: max speedup over FP16 ≈ 1.48×, far below the
    theoretical ~4× — the decoupled-architecture memory bottleneck."""
    s = sweep(cm.w4a16_speedup_ascend)
    assert 1.40 <= s.max() <= 1.55, s.max()
    assert s.max() < 2.0            # nowhere near the naive 4x


def test_bottleneck_is_transfer_not_typecast():
    """Paper §4.2: removing the round-trip (bw_l2 → ∞) recovers most of the
    lost speedup; making the cast slower (cube_flops unchanged, vector time
    is hidden anyway) does not change it. I.e. the bottleneck is the
    transfer, not the dequant computation."""
    import dataclasses
    M, N, K = 16, 2048, 16384
    base = cm.w4a16_speedup_ascend(M, N, K)
    no_roundtrip = dataclasses.replace(cm.ASCEND, bw_l2=1e18)
    assert cm.w4a16_speedup_ascend(M, N, K, no_roundtrip) > base * 1.25


def test_tpu_fused_removes_roundtrip_penalty():
    """DESIGN.md adaptation claim: the fused TPU kernel approaches the 4×
    weight-traffic bound at small M; the decoupled port does not."""
    M, N, K = 1, 2048, 16384
    fp16 = cm.fp16_time_tpu(M, N, K)
    fused = cm.w4a16_time_tpu_fused(M, N, K)
    dec = cm.w4a16_time_tpu_decoupled(M, N, K)
    assert fp16 / fused > 3.0          # near the 4x bandwidth bound
    assert fp16 / dec < 1.0            # HBM round-trip makes it a LOSS on TPU
    assert fused < dec


def test_best_splitk_prefers_deep_k():
    assert cm.best_split_k_ascend(1, 1024, 16384) >= 2
    assert cm.best_split_k_ascend(2048, 8192, 1024) == 1


def test_peak_table_keyed_by_device_kind_raises_on_unknown():
    assert cm.tpu_spec("TPU v5 lite") is cm.TPU_V5E
    assert cm.TPU_V5E.flops == 197e12 and cm.TPU_V5E.hbm_bw == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        cm.tpu_spec("TPU v9 imaginary")
