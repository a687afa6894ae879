"""Operations and bytes that the algorithm needs, counted from shapes.

Only what the work requires is counted: for a W4A16 GEMM the packed int4
weights, their float32 group scales and the bfloat16 activations in and
out; for attention the keys and values over ``min(context, window)``, and
the queries in and outputs out. Dequantized weights are never counted. A
job's least time is the larger of its operations over peak FLOP/s and its
bytes over peak bandwidth, per call.
"""
from __future__ import annotations

from chipbench import weights

ACT = 2          # bytes of a bfloat16 activation
KV = 2           # bytes of a kv_fp16 key or value element
SCALE = 4        # bytes of a float32 group scale


def gemm_call(cfgj: dict, rows: int) -> tuple:
    """(flops, bytes) of one model pass's W4A16 GEMMs over ``rows`` tokens."""
    L = cfgj["num_hidden_layers"]
    flops = nbytes = 0
    for K, N in weights.matrices(cfgj).values():
        flops += 2 * rows * K * N
        nbytes += K * N // 2 + (K // weights.GROUP) * N * SCALE \
            + rows * (K + N) * ACT
    return L * flops, L * nbytes


def _ctx(cfgj: dict, pos: int) -> int:
    w = cfgj["sliding_window"]
    return min(pos + 1, w) if w else pos + 1


def attn_decode(cfgj: dict, positions) -> tuple:
    """(flops, bytes) of one decode step's attention, rows at ``positions``."""
    H, Hkv, D = (cfgj["num_attention_heads"], cfgj["num_key_value_heads"],
                 cfgj["head_dim"])
    L = cfgj["num_hidden_layers"]
    flops = nbytes = 0
    for p in positions:
        c = _ctx(cfgj, p)
        flops += 4 * H * D * c
        nbytes += 2 * c * Hkv * D * KV + 2 * H * D * ACT
    return L * flops, L * nbytes


def least_time(flops: float, nbytes: float, peaks: dict) -> float:
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def model_flops(cfgj: dict, rows: int, positions) -> float:
    """Model FLOPs of ``rows`` tokens at ``positions``: every weight
    matmul (the head included) and attention over each token's context."""
    d, V = cfgj["hidden_size"], weights.padded_vocab(cfgj)
    gemm, _ = gemm_call(cfgj, rows)
    attn, _ = attn_decode(cfgj, positions)
    return gemm + attn + 2 * rows * d * V
