"""Analytic block-size autotuner for the fused W4A16 kernel.

No hardware timing is available in this container, so candidates are ranked
by the TPU v5e cost model under a hard VMEM-budget constraint — the same
"reason from the lowered working set" methodology as EXPERIMENTS.md §Perf:

  * VMEM working set (double-buffered inputs + fp32 accumulator) must fit;
  * MXU dims want 128-alignment (lane width) and big K blocks amortize the
    per-block dequant;
  * grid shape balances against the chip's TensorCores via the wave
    model (``common.target_spec``; one on a v5e).

Returns (block_m, block_n, block_k, split_k) for a given GEMM shape.
"""
from __future__ import annotations

import functools
from typing import Tuple

from repro.kernels import common

# Both re-exported from kernels/common.py — the one budget and working-set
# model, shared with the template's block chooser (template.choose_blocks),
# which enforces the budget at kernel-launch time, not just here.
VMEM_BUDGET = common.VMEM_BUDGET
vmem_working_set = common.vmem_working_set


def _score(M, N, K, bm, bn, bk, split_k):
    """Estimated kernel time: HBM traffic + dequant + wave quantization
    over the target chip's TensorCores."""
    spec = common.target_spec()
    cores = spec.cores_per_chip
    ks = K // split_k
    n_m, n_n, n_k = -(-M // bm), -(-N // bn), ks // bk
    tiles = n_m * n_n * split_k
    waves = -(-tiles // cores)
    eff = tiles / (waves * cores)
    flops = 2 * M * N * K
    t_compute = flops / (spec.flops * eff)
    # x re-read per N tile; packed W re-read per M tile; partials out
    traffic = (2 * M * K * n_n + 0.5 * K * N * n_m
               + (4 * split_k if split_k > 1 else 2) * M * N)
    t_mem = traffic / spec.hbm_bw
    return max(t_compute, t_mem)


@functools.lru_cache(maxsize=4096)
def autotune_w4a16(M: int, N: int, K: int,
                   group: int = 128) -> Tuple[int, int, int, int]:
    """Best (bm, bn, bk, split_k) under the VMEM budget."""
    best = None
    bm = common.largest_divisor(max(M, 8), 128)
    for bn in (128, 256, 512):
        if N % bn:
            continue
        for bk in (256, 512, 1024, 2048):
            if K % bk or not (bk % group == 0 or group % bk == 0):
                continue
            if vmem_working_set(bm, bk=bk, bn=bn, group=group,
                                k=K) > VMEM_BUDGET:
                continue
            for s in (1, 2, 4, 8):
                if K % (s * bk) and (K // s) % bk:
                    continue
                if K % s or (K // s) % bk:
                    continue
                t = _score(M, N, K, bm, bn, bk, s)
                if best is None or t < best[0]:
                    best = (t, bm, bn, bk, s)
    if best is None:                          # odd shapes: conservative
        return (bm, common.pick_block(N, 256), common.pick_block(K, 512), 1)
    return best[1:]
