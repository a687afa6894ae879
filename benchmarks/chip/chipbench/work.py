"""Operations and bytes that the algorithm needs, counted from shapes.

Only what the work requires is counted: for a W4A16 GEMM the packed int4
weights, their float32 group scales and the bfloat16 activations in and
out; for attention the keys and values over the context a row attends, and
the queries in and outputs out. Dequantized weights are never counted. A
job's least time is the larger of its operations over peak FLOP/s and its
bytes over peak bandwidth, per call.

Which matrices a layer has and which context each layer attends is the
architecture's: :func:`gemm_call`, :func:`attn_decode` and
:func:`model_flops` ask the configuration's module (:mod:`chipbench.arch`),
which counts with the helpers here.
"""
from __future__ import annotations

from chipbench import arch, weights

ACT = 2          # bytes of a bfloat16 activation
KV = 2           # bytes of a kv_fp16 key or value element
SCALE = 4        # bytes of a float32 group scale


def w4a16_gemm(rows: int, K: int, N: int) -> tuple:
    """(flops, bytes) of one W4A16 GEMM of ``rows`` x (K, N)."""
    return (2 * rows * K * N,
            K * N // 2 + (K // weights.GROUP) * N * SCALE
            + rows * (K + N) * ACT)


def attn_row(heads: int, kv_heads: int, dim: int, ctx: int) -> tuple:
    """(flops, bytes) of one query row's attention over ``ctx`` keys, with
    ``heads`` query and ``kv_heads`` key/value heads of ``dim``."""
    return (4 * heads * dim * ctx,
            2 * ctx * kv_heads * dim * KV + 2 * heads * dim * ACT)


def gemm_call(cfgj: dict, rows: int) -> tuple:
    """(flops, bytes) of one model pass's W4A16 GEMMs over ``rows`` tokens."""
    return arch.of(cfgj).gemm_call(cfgj, rows)


def attn_decode(cfgj: dict, positions) -> tuple:
    """(flops, bytes) of one decode step's attention, rows at ``positions``."""
    return arch.of(cfgj).attn_decode(cfgj, positions)


def model_flops(cfgj: dict, rows: int, positions) -> float:
    """Model FLOPs of ``rows`` tokens at ``positions``."""
    return arch.of(cfgj).model_flops(cfgj, rows, positions)


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
