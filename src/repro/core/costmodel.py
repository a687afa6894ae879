"""Analytic performance models.

Two machines are modeled:

1. **Ascend 910** (the paper's hardware) — a mechanistic three-phase model of
   Alg. 1 used to *reproduce the paper's measured trends* (Fig. 2: Split-K vs
   data-parallel; Fig. 3: W4A16 ≤1.48× over FP16). The decoupled-architecture
   constraint is explicit: dequantized weights round-trip through the
   GM/L2 path between vector and cube cores.

2. **TPU v5e** (our target) — the roofline constants used by
   benchmarks/roofline.py for the dry-run analysis, plus a fused-kernel
   model showing the round-trip term vanishing (the paper's Future-Work
   "direct data path", which the TPU core has).

The Ascend model is *calibrated, not measured*: compute/HBM constants are
public datasheet numbers; (bw_l2, bw_sat_cores, launch_s) are fit by grid
search so the model reproduces the paper's headline numbers — Split-K
speedup range [1.00, 1.78] vs the paper's [1.01, 1.74] and a W4A16-vs-FP16
cap of 1.47x vs the paper's 1.48x (see tests/test_costmodel.py).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional


@dataclasses.dataclass(frozen=True)
class AscendSpec:
    cube_flops: float = 256e12        # FP16 MACs/s aggregate (910)
    bw_gm: float = 1.1e12             # HBM bytes/s
    bw_l2: float = 2.2e12             # on-chip L2 path (vector↔cube round-trip)
    num_cores: int = 32               # AI cores (1 cube + 2 vector each)
    bw_sat_cores: int = 10           # cores needed to saturate GM bandwidth —
                                      # an underfilled grid can't pull peak BW;
                                      # this is WHY Split-K wins at K≫N/small M
    launch_s: float = 3e-6            # kernel-launch + sync overhead
    block_m: int = 128
    block_n: int = 256
    block_k: int = 256


@dataclasses.dataclass(frozen=True)
class TPUSpec:
    flops: float                      # bf16 FLOP/s per chip
    hbm_bw: float                     # bytes/s per chip
    ici_bw: float                     # bytes/s per link
    vmem_bytes: int
    cores_per_chip: int               # TensorCores a "parallel" grid axis
                                      # of one kernel can spread over


ASCEND = AscendSpec()

# Published per-chip peaks, keyed by ``jax.Device.device_kind``. Source:
# Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s of interconnect over 4 links, one TensorCore).
TPU_PEAKS = {
    "TPU v5 lite": TPUSpec(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                           vmem_bytes=128 * 2 ** 20, cores_per_chip=1),
}
TPU_V5E = TPU_PEAKS["TPU v5 lite"]


def tpu_spec(device_kind: str) -> TPUSpec:
    """Peaks of one chip of ``device_kind``; an unlisted kind is an error,
    never a default — a plan or a roofline share against the wrong chip's
    peaks would be silently wrong."""
    try:
        return TPU_PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for TPU device_kind {device_kind!r}; "
            f"known kinds: {sorted(TPU_PEAKS)} (add one to "
            f"core/costmodel.TPU_PEAKS with its source)") from None


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Ascend 910 model (paper Alg. 1)
# ---------------------------------------------------------------------------

def _wave_efficiency(tiles: int, cores: int) -> float:
    """Cube-core utilization with wave quantization: the last wave may be
    partially filled — the effect behind the paper's Fig. 2."""
    if tiles >= cores:
        waves = _ceil_div(tiles, cores)
        return tiles / (waves * cores)
    return tiles / cores


def gemm_time_ascend(M: int, N: int, K: int, *, split_k: int = 1,
                     weight_bytes_per_elt: float = 2.0,
                     weight_bw: Optional[float] = None,
                     spec: AscendSpec = ASCEND) -> float:
    """Time of one tiled GEMM phase (data-parallel if split_k == 1).

    weight_bytes_per_elt / weight_bw let the caller model where B comes
    from: GM fp16 (2.0, bw_gm), GM int4 (0.5, bw_gm) or the L2-resident
    dequant workspace (2.0, bw_l2).
    """
    weight_bw = weight_bw or spec.bw_gm
    m, n = spec.block_m, spec.block_n
    tiles = _ceil_div(M, m) * _ceil_div(N, n) * split_k
    eff = _wave_efficiency(tiles, spec.num_cores)
    t_compute = (2 * M * N * K) / (spec.cube_flops * eff)
    # memory bandwidth scales with active cores until saturation — the
    # decoupled-architecture effect behind the paper's Fig. 2
    bw_frac = min(1.0, min(tiles, spec.num_cores) / spec.bw_sat_cores)
    # A re-read per N-tile wave; B re-read per M-tile (M small → once)
    a_traffic = 2 * M * K * max(1, _ceil_div(N, n * spec.num_cores))
    b_traffic = weight_bytes_per_elt * K * N * _ceil_div(M, m)
    c_traffic = (4 if split_k > 1 else 2) * M * N * split_k
    t_mem = (a_traffic / spec.bw_gm + b_traffic / weight_bw
             + c_traffic / spec.bw_gm) / bw_frac
    return max(t_compute, t_mem) + spec.launch_s


def w4a16_time_ascend(M: int, N: int, K: int, *, split_k: int = 1,
                      spec: AscendSpec = ASCEND) -> float:
    """Full three-phase W4A16 pipeline (paper Alg. 1).

    Phase 1 (AIV): read INT4 from GM, write FP16 workspace (L2 path —
    this is THE decoupled-architecture round-trip the paper measures).
    Phase 2 (AIC): Split-K GEMM, weights from the L2-resident workspace.
    Phase 3 (AIV): reduce S partials + downcast.
    """
    t1 = (0.5 * K * N) / spec.bw_gm + (2 * K * N) / spec.bw_l2 + spec.launch_s
    t2 = gemm_time_ascend(M, N, K, split_k=split_k,
                          weight_bytes_per_elt=2.0, weight_bw=spec.bw_l2,
                          spec=spec)
    t3 = 0.0
    if split_k > 1:
        t3 = (4 * M * N * split_k + 2 * M * N) / spec.bw_gm + spec.launch_s
    return t1 + t2 + t3


def fp16_time_ascend(M: int, N: int, K: int,
                     spec: AscendSpec = ASCEND) -> float:
    """Native FP16×FP16 (the paper's PyTorch baseline): data-parallel,
    FP16 weights straight from GM."""
    return gemm_time_ascend(M, N, K, split_k=1,
                            weight_bytes_per_elt=2.0, weight_bw=spec.bw_gm,
                            spec=spec)


def best_split_k_ascend(M: int, N: int, K: int,
                        spec: AscendSpec = ASCEND) -> int:
    best, best_t = 1, float("inf")
    for s in (1, 2, 4, 8, 16):
        if K % s:
            continue
        t = w4a16_time_ascend(M, N, K, split_k=s, spec=spec)
        if t < best_t:
            best, best_t = s, t
    return best


def splitk_speedup_ascend(M: int, N: int, K: int,
                          spec: AscendSpec = ASCEND) -> float:
    """Paper Fig. 2: best Split-K W4A16 vs data-parallel W4A16."""
    t_dp = w4a16_time_ascend(M, N, K, split_k=1, spec=spec)
    t_sk = w4a16_time_ascend(
        M, N, K, split_k=best_split_k_ascend(M, N, K, spec), spec=spec)
    return t_dp / t_sk


def w4a16_speedup_ascend(M: int, N: int, K: int,
                         spec: AscendSpec = ASCEND) -> float:
    """Paper Fig. 3: best-split W4A16 vs native FP16."""
    s = best_split_k_ascend(M, N, K, spec)
    return fp16_time_ascend(M, N, K, spec) / \
        w4a16_time_ascend(M, N, K, split_k=s, spec=spec)


# ---------------------------------------------------------------------------
# TPU v5e fused-kernel model (the beyond-paper comparison)
# ---------------------------------------------------------------------------

def w4a16_time_tpu_fused(M: int, N: int, K: int,
                         spec: TPUSpec = TPU_V5E) -> float:
    """Fused kernel: INT4 weights cross HBM once; dequant lives in VMEM.
    No round-trip term — the 'direct vector→cube data path'."""
    traffic = 2 * M * K + 0.5 * K * N + 2 * M * N
    return max((2 * M * N * K) / spec.flops, traffic / spec.hbm_bw)


def w4a16_time_tpu_decoupled(M: int, N: int, K: int, *, split_k: int = 1,
                             spec: TPUSpec = TPU_V5E) -> float:
    """Paper-faithful pipeline on TPU: workspace round-trips through HBM
    (TPU has no shared L2 between kernels — the penalty is *worse* than
    Ascend's, which is exactly why the fused kernel is the right port)."""
    t1 = (0.5 * K * N + 2 * K * N) / spec.hbm_bw
    t2 = max((2 * M * N * K) / spec.flops,
             (2 * M * K + 2 * K * N + 4 * M * N * split_k) / spec.hbm_bw)
    t3 = (4 * M * N * split_k + 2 * M * N) / spec.hbm_bw if split_k > 1 else 0
    return t1 + t2 + t3


def fp16_time_tpu(M: int, N: int, K: int,
                  spec: TPUSpec = TPU_V5E) -> float:
    traffic = 2 * M * K + 2 * K * N + 2 * M * N
    return max((2 * M * N * K) / spec.flops, traffic / spec.hbm_bw)


def w8a16_time_tpu_fused(M: int, N: int, K: int,
                         spec: TPUSpec = TPU_V5E) -> float:
    """Fused per-channel INT8 kernel: int8 weight rows cross HBM once
    (K·N bytes, half of fp16) plus one fp32 scale row; dequant in VMEM."""
    traffic = 2 * M * K + 1.0 * K * N + 4 * N + 2 * M * N
    return max((2 * M * N * K) / spec.flops, traffic / spec.hbm_bw)


def w4a8_time_tpu_fused(M: int, N: int, K: int, *, group: int = 128,
                        spec: TPUSpec = TPU_V5E) -> float:
    """Fused W4A8 kernel: int8 activations (M·K bytes, half of fp16),
    packed int4 weights (K·N/2) + fp32 group scales; int8×int8 MXU dots at
    twice the bf16 MAC rate (v5e int8 peak is 2× bf16)."""
    traffic = M * K + 0.5 * K * N + 4.0 * K * N / max(group, 1) + 2 * M * N
    return max((2 * M * N * K) / (2 * spec.flops), traffic / spec.hbm_bw)


# ---------------------------------------------------------------------------
# Decode-attention traffic model (ring vs gather vs fused-paged)
# ---------------------------------------------------------------------------
#
# Decode attention is the same bottleneck the paper measures for W4A16
# GEMM, transposed onto the KV cache: bandwidth-bound, and the naive
# quantized path pays an extra round-trip through global memory (gather +
# dequantize to an HBM staging buffer, then read it back for attention).
# These entries price that round-trip so the planner can charge it.

def kv_bytes_per_token(Hkv: int, D: int, *, quantized: bool,
                       act_bytes: int = 2) -> float:
    """HBM bytes to read one cached token's K+V across all kv-heads:
    payload (int8 or the activation dtype) plus the per-(token, head)
    fp32 scale pair for quantized formats."""
    payload = 1 if quantized else act_bytes
    scales = 2 * 4 * Hkv if quantized else 0
    return 2 * payload * Hkv * D + scales


def paged_attn_bytes(path: str, B: int, Hq: int, Hkv: int, D: int,
                     ctx: int, *, quantized: bool, act_bytes: int = 2,
                     kv_partitions: int = 1, q_len: int = 1) -> float:
    """HBM bytes moved by one attention step of ``q_len`` queries per row
    over a ctx-token window, per path:

    - ``ring``: dense fp16 ring buffer, read once (ring stores no
      quantized payloads).
    - ``gather``: pool read + the dequantized window *written to HBM and
      read back* — the two-pass round-trip the fused kernel deletes. The
      window materialization is charged in full regardless of ``q_len``:
      a prefill chunk or verify step gathers exactly as many bytes as a
      single decode token does.
    - ``fused``: pool read once + O(S·q_len) combine partials.

    For ``q_len > 1`` (chunked prefill / speculative verify) both paged
    paths additionally stage the chunk's own quantize-roundtripped K/V
    segment and read it back — identical work, charged to both.
    """
    q_out = 2 * B * q_len * Hq * D * act_bytes      # q in, out back
    window = B * ctx
    dense_tok = 2 * act_bytes * Hkv * D             # one token's K+V raw
    seg = 2 * B * q_len * dense_tok if q_len > 1 else 0
    if path == "ring":
        return window * dense_tok + q_out
    pool = window * kv_bytes_per_token(Hkv, D, quantized=quantized,
                                       act_bytes=act_bytes)
    if path == "gather":
        staged = window * dense_tok                 # dequantized window
        return pool + 2 * staged + seg + q_out      # write + read back
    if path == "fused":
        partials = kv_partitions * B * q_len * Hq * (D + 2) * 4 * 2
        return pool + seg + q_out + partials
    raise ValueError(f"unknown attention path {path!r} "
                     "(expected ring | gather | fused)")


def attn_decode_time_tpu(path: str, B: int, Hq: int, Hkv: int, D: int,
                         ctx: int, *, quantized: bool, act_bytes: int = 2,
                         kv_partitions: int = 1, q_len: int = 1,
                         spec: TPUSpec = TPU_V5E) -> float:
    """Roofline time of one attention step (``q_len`` queries per row):
    QK^T + PV flops vs the path's HBM traffic. Decode and chunk-sized
    prefill are both firmly bandwidth-bound (arithmetic intensity ~q_len
    flops/byte at serving chunk sizes), so the bytes term decides the
    ranking."""
    flops = 4 * B * q_len * Hq * D * ctx            # QK^T + PV
    bytes_moved = paged_attn_bytes(
        path, B, Hq, Hkv, D, ctx, quantized=quantized,
        act_bytes=act_bytes, kv_partitions=kv_partitions, q_len=q_len)
    return max(flops / spec.flops, bytes_moved / spec.hbm_bw)
