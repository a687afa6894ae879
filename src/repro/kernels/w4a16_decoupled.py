"""Paper-faithful decoupled W4A16 pipeline (Ascend Alg. 1 on TPU).

Reproduces the Ascend 910 data-flow *including the global-memory round-trip*
that the paper identifies as the bottleneck:

  Phase 1 (AIV role)  — dequant kernel: INT4 → float weights written to an
                        HBM workspace (the "global workspace buffer").
  Phase 2 (AIC role)  — Split-K tiled GEMM over the fp16/bf16 workspace,
                        producing S fp32 partials in HBM ("split buffers in
                        global memory").
  Phase 3 (AIV role)  — reduce kernel: elementwise sum over S + fp32→fp16
                        downcast.

Each phase is its own ``pallas_call`` so the dequantized weights and the
partials genuinely travel through HBM — this is the variant whose roofline
reproduces the paper's ≤1.48× cap, and the baseline the fused kernel beats.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quant import QuantizedTensor
from repro.kernels import common, template


# ---------------------------------------------------------------------------
# Phase 1: dequant (vector-core role)
# ---------------------------------------------------------------------------

def _make_dequant_kernel(bk: int, g: int, has_zeros: bool):
    spb = max(1, bk // g)

    def kernel(p_ref, s_ref, *rest):
        if has_zeros:
            z_ref, o_ref = rest
        else:
            z_ref = None
            (o_ref,) = rest
        # the scale block spans all K/g rows; take this k block's groups
        row0 = (pl.program_id(0) * bk) // g
        o_ref[...] = common.dequant_block(
            p_ref, common.scale_rows(s_ref, row0, spb),
            None if z_ref is None else common.scale_rows(z_ref, row0, spb),
            min(bk, g), o_ref.dtype)

    return kernel


@functools.partial(
    jax.jit,
    static_argnames=("block_k", "block_n", "out_dtype", "interpret"),
)
def dequant_w4(
    qt: QuantizedTensor,
    *,
    block_k: int = 512,
    block_n: int = 512,
    out_dtype=None,
    interpret=None,
) -> jax.Array:
    """Phase-1 kernel: materialize Dequant(W) -> (K, N) in HBM."""
    out_dtype = out_dtype or qt.out_dtype
    interpret = common.resolve_interpret(interpret)
    K, N = qt.K, qt.N
    g = qt.group_size
    bn = common.pick_block(N, block_n)
    bk = common.pick_block(K, block_k)
    while bk > 1 and not (bk % g == 0 or g % bk == 0):
        bk = common.largest_divisor(K, bk - 1)
    has_zeros = qt.zeros is not None

    in_specs = [
        pl.BlockSpec((bk // 2, bn), lambda k, n: (k, n)),
        pl.BlockSpec((K // g, bn), lambda k, n: (0, n)),
    ]
    operands = [qt.packed, qt.scales]
    if has_zeros:
        in_specs.append(pl.BlockSpec((K // g, bn), in_specs[1].index_map))
        operands.append(qt.zeros)

    return pl.pallas_call(
        _make_dequant_kernel(bk, g, has_zeros),
        grid=(K // bk, N // bn),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((bk, bn), lambda k, n: (k, n)),
        out_shape=jax.ShapeDtypeStruct((K, N), out_dtype),
        compiler_params=common.compiler_params(("parallel", "parallel")),
        interpret=interpret,
    )(*operands)


# ---------------------------------------------------------------------------
# Phase 2: Split-K GEMM over the HBM workspace (cube-core role).
# A template composition: identity weight stage + float contraction, raw
# (S, M, N) partials — phase 3 reduces them through HBM, per the paper.
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("split_k", "block_m", "block_n", "block_k", "interpret"),
)
def splitk_gemm(
    x: jax.Array,
    w: jax.Array,
    *,
    split_k: int = 4,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    interpret=None,
) -> jax.Array:
    """Phase-2 kernel: S fp32 partial products C_i = A · B_i in HBM."""
    K2, N = w.shape
    assert x.shape[1] == K2 and K2 % split_k == 0
    return template.tiled_matmul(
        x,
        template.DenseWeight(w),
        template.FloatContraction(),
        N=N,
        split_k=split_k,
        block_m=block_m, block_n=block_n, block_k=block_k,
        reduce_splits=False,
        interpret=interpret,
    )


# ---------------------------------------------------------------------------
# Phase 3: reduction (vector-core role)
# ---------------------------------------------------------------------------

def _reduce_kernel(p_ref, o_ref):
    o_ref[...] = jnp.sum(p_ref[...], axis=0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_m", "block_n", "out_dtype", "interpret")
)
def reduce_partials(
    partials: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 512,
    out_dtype=jnp.bfloat16,
    interpret=None,
) -> jax.Array:
    """Phase-3 kernel: C = sum_i C_i, fp32 → out_dtype."""
    interpret = common.resolve_interpret(interpret)
    S, M, N = partials.shape
    partials = common.pad_dim(partials, 1, common.SUBLANE)
    Mp = partials.shape[1]
    bm = common.largest_divisor(Mp, block_m)
    bn = common.pick_block(N, block_n)

    out = pl.pallas_call(
        _reduce_kernel,
        grid=(Mp // bm, N // bn),
        in_specs=[pl.BlockSpec((S, bm, bn), lambda m, n: (0, m, n))],
        out_specs=pl.BlockSpec((bm, bn), lambda m, n: (m, n)),
        out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
        compiler_params=common.compiler_params(("parallel", "parallel")),
        interpret=interpret,
    )(partials)
    return out[:M]


# ---------------------------------------------------------------------------
# The full 3-phase pipeline (paper Alg. 1)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=(
        "split_k", "block_m", "block_n", "block_k", "out_dtype", "interpret",
    ),
)
def w4a16_decoupled(
    x: jax.Array,
    qt: QuantizedTensor,
    *,
    split_k: int = 4,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
    interpret=None,
) -> jax.Array:
    """C = A · Dequant(W) via the Ascend 3-phase GM-workspace pipeline."""
    out_dtype = out_dtype or x.dtype
    w = dequant_w4(qt, out_dtype=x.dtype, interpret=interpret)     # Phase 1
    partials = splitk_gemm(
        x, w,
        split_k=split_k, block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=interpret,
    )                                                              # Phase 2
    return reduce_partials(partials, out_dtype=out_dtype, interpret=interpret)
