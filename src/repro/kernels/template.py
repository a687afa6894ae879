"""Stage-based tiled-GEMM template — paper Alg. 1 as composable stages.

Every quantized-GEMM kernel in this package is the same three-stage loop,
and the stages map one-to-one onto the paper's Alg. 1 phases:

  weight stage  (AIV role)  — produce the (bk, bn) weight tile in VMEM:
                              identity load (:class:`DenseWeight`), grouped
                              INT4 dequant (:class:`GroupedInt4Dequant`),
                              per-channel INT8 dequant
                              (:class:`ChannelInt8Dequant`), or a raw INT4→
                              INT8 unpack feeding an integer MXU dot
                              (:class:`GroupedInt4Raw`);
  contraction   (AIC role)  — accumulate x_tile · w_tile into the fp32 VMEM
                              accumulator: a float MXU dot
                              (:class:`FloatContraction`) or an int8×int8
                              ``preferred_element_type=int32`` dot with
                              per-group rescale at the group boundary
                              (:class:`Int8GroupContraction`);
  epilogue      (AIV role)  — in-kernel flush (downcast on the last k step,
                              or a partial write per Split-K slice) plus a
                              host-side finalize (Split-K reduce, per-token
                              rescale, M-crop).

:func:`tiled_matmul` composes the stages over a shared grid/BlockSpec
builder. Block selection (:func:`choose_blocks`) is the one place the
``[m, n, k]`` block parameter of Alg. 1 is decided: divisor-aligned blocks
near the requested targets, group-compatible ``bk``, shrunk until the
working set fits ``common.VMEM_BUDGET`` via the same
``common.vmem_working_set`` model the autotuner ranks candidates with.

Both launch shapes of the paper are provided:

  split_k == 1 : grid ``(M/bm, N/bn, K/bk)``, direct output
                 (the data-parallel strategy);
  split_k == S : grid ``(S, M/bm, N/bn, K/S/bk)`` writing S fp32 partials,
                 reduced outside the kernel (the Split-K strategy; the S
                 axis is marked "parallel" so megacore/futures overlap it).

Adding a new quantization format is a weight stage (+ contraction stage if
the arithmetic changes) and a ~20-line wrapper — see docs/kernels.md.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import common

__all__ = [
    "BlockConfig", "choose_blocks", "tiled_matmul",
    "DenseWeight", "GroupedInt4Dequant", "ChannelInt8Dequant",
    "GroupedInt4Raw", "FloatContraction", "Int8GroupContraction",
    "DensePages", "Int8ChannelPages",
]


# ---------------------------------------------------------------------------
# Shared block selection (Alg. 1's [m, n, k] under the VMEM budget)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockConfig:
    """One launch decision: block shapes + Split-K split of the K loop."""

    bm: int
    bn: int
    bk: int
    split_k: int
    nk: int                 # k grid steps per K slice ((K // split_k) // bk)
    group_size: int = 0     # K rows per scale row; 0 = ungrouped/dense


def choose_blocks(
    M: int, N: int, K: int, *,
    block_m: int = 128, block_n: int = 256, block_k: int = 512,
    split_k: int = 1, group_size: int = 0,
    act_bytes: int = 2, weight_elt_bytes: float = 2.0,
    has_scales: bool = False, dequant_tile: bool = False,
    vmem_budget: int = common.VMEM_BUDGET,
) -> BlockConfig:
    """Pick (bm, bn, bk) near the targets, then enforce the VMEM budget.

    ``bm`` divides M (callers pad M to SUBLANE first), ``bn``/``bk`` prefer
    LANE-aligned divisors, ``bk`` additionally divides the K slice and stays
    group-compatible (``bk % g == 0 or g % bk == 0``). If the working set
    (``common.vmem_working_set`` with the weight stage's byte layout)
    exceeds the budget, ``bk`` shrinks first (the dequant tile dominates),
    then ``bn``.
    """
    if K % split_k:
        raise ValueError(f"split_k={split_k} must divide K={K}")
    ks = K // split_k
    if group_size > 0 and ks % group_size:
        raise ValueError(
            f"K={K} split_k={split_k} must keep K-slices group-aligned "
            f"(group_size={group_size})")
    bm = common.largest_divisor(M, block_m)
    bn = common.pick_block(N, block_n)
    bk = common.pick_block(ks, block_k)

    def group_ok(b: int) -> bool:
        return group_size <= 0 or b % group_size == 0 or group_size % b == 0

    def shrink(b: int) -> int:
        """Largest group-compatible divisor of the K slice below ``b``."""
        b = common.largest_divisor(ks, b - 1)
        while b > 1 and not group_ok(b):
            b = common.largest_divisor(ks, b - 1)
        return b

    if not group_ok(bk):
        bk = shrink(bk + 1)

    def working_set(bn_: int, bk_: int) -> int:
        return common.vmem_working_set(
            bm, bn_, bk_, group_size or K, act_bytes=act_bytes,
            weight_elt_bytes=weight_elt_bytes, has_scales=has_scales,
            dequant_tile=dequant_tile, k=K)

    while working_set(bn, bk) > vmem_budget and bk > 1:
        bk = shrink(bk)
    while working_set(bn, bk) > vmem_budget and bn > 1:
        bn = common.largest_divisor(N, bn - 1)
    return BlockConfig(bm=bm, bn=bn, bk=bk, split_k=split_k,
                       nk=ks // bk, group_size=group_size)


# ---------------------------------------------------------------------------
# Weight stages (the AIV dequant role). Each declares how its operands are
# blocked along (K, N) — a row function mapping the global k block index to
# the operand's row block — and how the in-VMEM tile is produced.
# ---------------------------------------------------------------------------

RowFn = Callable[[jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class DenseWeight:
    """Identity stage: a dense (K, N) weight already in a float dtype."""

    w: jax.Array

    @property
    def vmem(self):
        return dict(weight_elt_bytes=jnp.dtype(self.w.dtype).itemsize,
                    has_scales=False, dequant_tile=False)

    def operands(self) -> List[jax.Array]:
        return [self.w]

    def layout(self, bc: BlockConfig) -> List[Tuple[Tuple[int, int], RowFn]]:
        return [((bc.bk, bc.bn), lambda kk: kk)]

    def produce(self, refs: Sequence, bc: BlockConfig, compute_dtype, kk):
        (w_ref,) = refs
        return w_ref[...]


def _group_layout(bc: BlockConfig) -> Tuple[int, int]:
    """(repeat, scale rows per k block) for grouped scales."""
    g = bc.group_size
    return min(bc.bk, g), max(1, bc.bk // g)


@dataclasses.dataclass(frozen=True)
class GroupedInt4Dequant:
    """Grouped INT4 → float dequant in VMEM (the fused-W4A16 weight stage)."""

    packed: jax.Array                 # (K//2, N) int8, two nibbles per byte
    scales: jax.Array                 # (K//g, N)
    zeros: Optional[jax.Array]        # same shape as scales, or None

    vmem = dict(weight_elt_bytes=0.5, has_scales=True, dequant_tile=True)

    def operands(self) -> List[jax.Array]:
        ops = [self.packed, self.scales]
        if self.zeros is not None:
            ops.append(self.zeros)
        return ops

    def layout(self, bc: BlockConfig) -> List[Tuple[Tuple[int, int], RowFn]]:
        # the scale block spans the whole K/g axis (a few KiB): a (bk/g, bn)
        # block would break the TPU's 8-row tiling whenever bk/g < 8, so
        # the k block's rows are sliced in-kernel (_group_rows) instead
        rows = self.scales.shape[0]
        specs = [((bc.bk // 2, bc.bn), lambda kk: kk),
                 ((rows, bc.bn), lambda kk: 0)]
        if self.zeros is not None:
            specs.append(((rows, bc.bn), lambda kk: 0))
        return specs

    def produce(self, refs: Sequence, bc: BlockConfig, compute_dtype, kk):
        p_ref, s_ref, *z = refs
        repeat, _ = _group_layout(bc)
        return common.dequant_block(
            p_ref, _group_rows(s_ref, bc, kk),
            _group_rows(z[0], bc, kk) if z else None, repeat, compute_dtype)


def _group_rows(ref, bc: BlockConfig, kk):
    """The scale rows of global k block ``kk`` from a whole-K scale block."""
    _, spb = _group_layout(bc)
    return common.scale_rows(ref, (kk * bc.bk) // bc.group_size, spb)


@dataclasses.dataclass(frozen=True)
class ChannelInt8Dequant:
    """Per-channel INT8 → float dequant in VMEM (the w8a16 weight stage)."""

    rows: jax.Array                   # (K, N) int8
    scales: jax.Array                 # (1, N)
    zeros: Optional[jax.Array]        # (1, N) or None

    vmem = dict(weight_elt_bytes=1.0, has_scales=True, dequant_tile=True)

    def operands(self) -> List[jax.Array]:
        ops = [self.rows, self.scales]
        if self.zeros is not None:
            ops.append(self.zeros)
        return ops

    def layout(self, bc: BlockConfig) -> List[Tuple[Tuple[int, int], RowFn]]:
        specs = [((bc.bk, bc.bn), lambda kk: kk),
                 ((1, bc.bn), lambda kk: 0)]
        if self.zeros is not None:
            specs.append(((1, bc.bn), lambda kk: 0))
        return specs

    def produce(self, refs: Sequence, bc: BlockConfig, compute_dtype, kk):
        r_ref, s_ref, *z = refs
        return common.dequant_channel_block(
            r_ref, s_ref, z[0] if z else None, compute_dtype)


@dataclasses.dataclass(frozen=True)
class GroupedInt4Raw:
    """INT4 → INT8 unpack only — scales stay symbolic for an integer dot.

    ``produce`` returns ``(wq int8 (bk, bn), scales (spb, bn), zeros|None)``
    for :class:`Int8GroupContraction`, which applies the group scales at
    the group boundary after the int32 accumulation (LiquidGEMM-style).
    """

    packed: jax.Array
    scales: jax.Array
    zeros: Optional[jax.Array]

    # int8 tile instead of a float tile; budget-wise dequant_tile=True is a
    # safe overestimate
    vmem = dict(weight_elt_bytes=0.5, has_scales=True, dequant_tile=True)

    operands = GroupedInt4Dequant.operands
    layout = GroupedInt4Dequant.layout

    def produce(self, refs: Sequence, bc: BlockConfig, compute_dtype, kk):
        p_ref, s_ref, *z = refs
        return (common.unpack_int4_block(p_ref).astype(jnp.int8),
                _group_rows(s_ref, bc, kk),
                _group_rows(z[0], bc, kk) if z else None)


# ---------------------------------------------------------------------------
# KV stages (the stage vocabulary extended from GEMM to attention).
#
# A KVStage is the attention analogue of a WeightStage: it names the paged
# pools the fused attention kernel copies page by page (runtime/kvcache.py
# block pools, ``(num_blocks, Hkv, page_size, D)``, so one page of every
# head is one contiguous DMA), the per-(token, head) values that ride
# beside them, and where those values enter the online softmax: nowhere
# for ``kv_fp16`` pages; as the per-(token, head) INT8 scales for
# ``kv8_channel`` (the same AIV dequant role the GEMM weight stages play,
# fused into the consumer instead of round-tripping through HBM).
#
# Per-token values reach the kernel as ``(Hkv, ppb·page_size)`` blocks: one
# lane row per head, one lane per token of the block. The emitter
# (kernels/paged_attention.py) gathers them through the block tables with
# XLA, because a page's ``(Hkv, page_size)`` values cannot be copied into
# a slice of a lane row. A stage applies them to the fp32 scores and
# probabilities of head ``h``, so payload tiles reach the MXU as stored.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class DensePages:
    """Identity KV stage: pool pages already hold the cache dtype
    (``kv_fp16`` — no scales, no dequant)."""

    k_pool: jax.Array                 # (num_blocks, Hkv, ps, D)
    v_pool: jax.Array

    def token_values(self) -> List[jax.Array]:
        return []

    def scores(self, s, refs: Sequence, h: int):
        return s

    def probs(self, p, refs: Sequence, h: int):
        return p


@dataclasses.dataclass(frozen=True)
class Int8ChannelPages:
    """Per-(token, head) INT8 KV dequant in VMEM (``kv8_channel``).

    ``core/quant.kv_dequantize`` scales each payload row by its token's
    fp32 scale; here the K scale multiplies the fp32 score column and the
    V scale the fp32 probability column instead, which is the same sum of
    the same products (exact up to fp32 rounding). The dequantized page
    never exists, in HBM or in VMEM.
    """

    k_pool: jax.Array                 # (num_blocks, Hkv, ps, D) int8
    v_pool: jax.Array
    k_scale: jax.Array                # (num_blocks, Hkv, ps) fp32
    v_scale: jax.Array

    def token_values(self) -> List[jax.Array]:
        return [self.k_scale, self.v_scale]

    def scores(self, s, refs: Sequence, h: int):
        return s * refs[0][0, 0, h:h + 1]          # (QG, L) × (1, L)

    def probs(self, p, refs: Sequence, h: int):
        return p * refs[1][0, 0, h:h + 1]


# ---------------------------------------------------------------------------
# Contraction stages (the AIC MXU role)
# ---------------------------------------------------------------------------

class FloatContraction:
    """acc += x · w on the MXU with fp32 accumulation."""

    def step(self, x_tile, w_tile, acc_ref, bc: BlockConfig) -> None:
        acc_ref[...] += jnp.dot(
            x_tile, w_tile, preferred_element_type=jnp.float32)


class Int8GroupContraction:
    """int8×int8 MXU dot, int32 accumulate, group rescale into fp32.

    The weight stage hands over ``(wq int8, scales, zeros|None)``; each
    scale group inside the block gets its own exact int32 dot, rescaled at
    the group boundary — the W4A8 arithmetic of ``w4a8_matmul_ref`` moved
    into the k loop. The asymmetric correction uses the per-token nibble
    sum (``z · Σ x_q``), matching the oracle.
    """

    def step(self, x_tile, w_prod, acc_ref, bc: BlockConfig) -> None:
        wq, s_ref, z_ref = w_prod
        repeat, spb = _group_layout(bc)
        for i in range(spb):                      # static unroll over groups
            xs = x_tile[:, i * repeat:(i + 1) * repeat]
            ws = wq[i * repeat:(i + 1) * repeat, :]
            part = jnp.dot(
                xs, ws, preferred_element_type=jnp.int32
            ).astype(jnp.float32)
            if z_ref is not None:
                tok = jnp.sum(xs.astype(jnp.int32), axis=1)
                part = part - (z_ref[i, :].astype(jnp.float32)[None, :]
                               * tok.astype(jnp.float32)[:, None])
            acc_ref[...] += part * s_ref[i, :].astype(jnp.float32)[None, :]


# ---------------------------------------------------------------------------
# The emitter
# ---------------------------------------------------------------------------

def _make_kernel(weight_stage, contraction, bc: BlockConfig, *,
                 n_weight_refs: int, partial_out: bool, k_axis: int,
                 compute_dtype):
    def kernel(x_ref, *rest):
        w_refs = rest[:n_weight_refs]
        o_ref, acc_ref = rest[n_weight_refs:]
        k = pl.program_id(k_axis)
        # global k block: Split-K slice s covers blocks s*nk .. s*nk+nk-1
        kk = pl.program_id(0) * bc.nk + k if partial_out else k

        @pl.when(k == 0)
        def _init():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        w_tile = weight_stage.produce(w_refs, bc, compute_dtype, kk)
        contraction.step(x_ref[...], w_tile, acc_ref, bc)

        @pl.when(k == pl.num_programs(k_axis) - 1)
        def _flush():
            if partial_out:
                o_ref[0] = acc_ref[...].astype(o_ref.dtype)
            else:
                o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    return kernel


def tiled_matmul(
    x: jax.Array,
    weight_stage,
    contraction,
    *,
    N: int,
    group_size: int = 0,
    split_k: int = 1,
    block_m: int = 128,
    block_n: int = 256,
    block_k: int = 512,
    out_dtype=None,
    interpret=None,
    reduce_splits: bool = True,
    finalize: Optional[Callable[[jax.Array], jax.Array]] = None,
    vmem_budget: int = common.VMEM_BUDGET,
) -> jax.Array:
    """Emit one tiled GEMM from a (weight stage, contraction) pair.

    x : (M, K); M is padded to SUBLANE internally and cropped on return.
    With ``split_k == 1`` the kernel writes the output directly; with
    ``split_k == S`` it writes S fp32 partials which are summed outside
    (set ``reduce_splits=False`` to get the raw ``(S, M, N)`` partials —
    the decoupled pipeline reduces them in its own phase-3 kernel).
    ``finalize`` runs host-side on the fp32 result before the out_dtype
    cast (per-token rescale lives here).
    """
    out_dtype = out_dtype or x.dtype
    interpret = common.resolve_interpret(interpret)
    M, K = x.shape
    x = common.pad_dim(x, 0, common.SUBLANE)
    Mp = x.shape[0]

    bc = choose_blocks(
        Mp, N, K, block_m=block_m, block_n=block_n, block_k=block_k,
        split_k=split_k, group_size=group_size,
        act_bytes=max(1, jnp.dtype(x.dtype).itemsize),
        vmem_budget=vmem_budget, **weight_stage.vmem)
    layout = weight_stage.layout(bc)
    operands = [x] + weight_stage.operands()

    # kernel output dtype: direct out unless a host-side pass still needs
    # the fp32 accumulator (Split-K reduce and/or finalize)
    direct = split_k == 1 and finalize is None
    kernel_dtype = jnp.dtype(out_dtype) if direct else jnp.float32

    # raw-partials callers (the decoupled pipeline's phase 2) get the
    # (S, M, N) launch shape even at S == 1
    if split_k == 1 and reduce_splits:
        in_specs = [pl.BlockSpec((bc.bm, bc.bk), lambda m, n, k: (m, k))]
        for shape, row_fn in layout:
            in_specs.append(pl.BlockSpec(
                shape, lambda m, n, k, rf=row_fn: (rf(k), n)))
        out = pl.pallas_call(
            _make_kernel(weight_stage, contraction, bc,
                         n_weight_refs=len(layout), partial_out=False,
                         k_axis=2, compute_dtype=x.dtype),
            grid=(Mp // bc.bm, N // bc.bn, bc.nk),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bc.bm, bc.bn), lambda m, n, k: (m, n)),
            out_shape=jax.ShapeDtypeStruct((Mp, N), kernel_dtype),
            scratch_shapes=[pltpu.VMEM((bc.bm, bc.bn), jnp.float32)],
            compiler_params=common.compiler_params(
                ("parallel", "parallel", "arbitrary")),
            interpret=interpret,
        )(*operands)
        out = out[:M]
        if finalize is not None:
            out = finalize(out)
        return out.astype(out_dtype)

    nk = bc.nk
    in_specs = [pl.BlockSpec((bc.bm, bc.bk),
                             lambda s, m, n, k: (m, s * nk + k))]
    for shape, row_fn in layout:
        in_specs.append(pl.BlockSpec(
            shape, lambda s, m, n, k, rf=row_fn: (rf(s * nk + k), n)))
    partials = pl.pallas_call(
        _make_kernel(weight_stage, contraction, bc,
                     n_weight_refs=len(layout), partial_out=True,
                     k_axis=3, compute_dtype=x.dtype),
        grid=(split_k, Mp // bc.bm, N // bc.bn, nk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bc.bm, bc.bn),
                               lambda s, m, n, k: (s, m, n)),
        out_shape=jax.ShapeDtypeStruct((split_k, Mp, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bc.bm, bc.bn), jnp.float32)],
        compiler_params=common.compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*operands)
    partials = partials[:, :M]
    if not reduce_splits:
        return partials
    out = jnp.sum(partials, axis=0)
    if finalize is not None:
        out = finalize(out)
    return out.astype(out_dtype)
