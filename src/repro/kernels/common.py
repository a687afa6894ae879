"""Shared helpers for the Pallas TPU kernels.

Block-size selection is the TPU analogue of the paper's ``[m, n, k]`` block
parameter (Alg. 1): blocks must fit VMEM (the L1/L0 analogue) and keep the
MXU dimensions 128-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import costmodel

LANE = 128          # TPU lane width — minor dim of every block
SUBLANE = 8         # fp32 sublane; bf16 is 16 but 8 keeps blocks legal

# The one VMEM budget: Mosaic's default scoped-VMEM limit on TPU v5e. A
# kernel whose blocks and temporaries exceed it is refused at compile time
# ("Ran out of memory in memory space vmem"). Both the autotuner's
# candidate ranking and the template's block chooser
# (template.choose_blocks) enforce it through vmem_working_set below.
VMEM_BUDGET = 16 * 1024 * 1024


def vmem_working_set(bm: int, bn: int, bk: int, group: int,
                     act_bytes: int = 2, weight_elt_bytes: float = 0.5,
                     has_scales: bool = True,
                     dequant_tile: bool = True, k: int = 0) -> int:
    """Bytes resident per grid step (double-buffered ins + fp32 acc).

    Defaults describe the fused W4A16 kernel (packed int4 weights at 0.5
    bytes/element, fp32 group scales, a dequantized tile feeding the MXU).
    Other weight stages override: dense GEMM has ``weight_elt_bytes=
    act_bytes`` and neither scales nor a dequant tile; per-channel INT8 has
    ``weight_elt_bytes=1``. ``k`` (the full K) sizes the scale block the
    template actually holds, which spans every K group; 0 counts only the
    groups of one k block. A dequant tile also costs its int32 unpack and
    fp32 dequant temporaries (8 bytes per element), which is what bounds
    the tile on a v5e: compiling for one refuses (bk, bn) = (2560, 768)
    and accepts (2560, 512).
    """
    x_blk = bm * bk * act_bytes
    w_blk = int(bk * bn * weight_elt_bytes)
    s_rows = max(1, (k or bk) // max(group, 1))
    s_blk = s_rows * bn * 4 if has_scales else 0
    deq = bk * bn * (act_bytes + 8) if dequant_tile else 0
    acc = bm * bn * 4
    return 2 * (x_blk + w_blk + s_blk) + deq + acc


def is_cpu() -> bool:
    return jax.default_backend() == "cpu"


def resolve_interpret(interpret) -> bool:
    """interpret=None → auto (interpret on CPU, compiled on TPU)."""
    if interpret is None:
        return is_cpu()
    return bool(interpret)


def largest_divisor(dim: int, target: int, multiple_of: int = 1) -> int:
    """Largest d ≤ target with dim % d == 0 and d % multiple_of == 0."""
    target = min(target, dim)
    for d in range(target, 0, -1):
        if dim % d == 0 and d % multiple_of == 0:
            return d
    return multiple_of if dim % multiple_of == 0 else 1


def pick_block(dim: int, target: int, align: int = LANE) -> int:
    """Prefer a LANE-aligned divisor of ``dim`` near ``target``."""
    if dim % align == 0:
        d = largest_divisor(dim, target, align)
        if d >= align:
            return d
    return largest_divisor(dim, target)


def pad_dim(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    """Zero-pad ``axis`` of x up to the next multiple."""
    size = x.shape[axis]
    rem = (-size) % multiple
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, rem)
    return jnp.pad(x, pads)


def compiler_params(dimension_semantics):
    """Mosaic compiler params: the grid axes' dimension semantics."""
    return pltpu.CompilerParams(dimension_semantics=dimension_semantics)


@functools.lru_cache(maxsize=1)
def target_spec() -> costmodel.TPUSpec:
    """Peaks of the chip kernels are planned for: the local TPU's, looked
    up by ``device_kind`` (an unknown kind raises), or a v5e's on a CPU
    host, which plans for that chip rather than for itself."""
    dev = jax.local_devices()[0]
    if dev.platform == "cpu":
        return costmodel.TPU_V5E
    return costmodel.tpu_spec(dev.device_kind)


def unpack_int4_block(packed) -> jax.Array:
    """In-VMEM INT4 unpack of one packed weight block (no scaling).

    packed : (bk//2, bn) int8 ref/array — two nibbles per byte along K
    returns: (bk, bn) int32 in [-8, 7]

    Shift-based sign extension lowers to cheap VPU ops. The shifts run on
    int32 because the TPU vector unit has no 8-bit shifts. The raw tile
    either feeds a float dequant (:func:`dequant_block`) or, cast to int8,
    an int8×int8 MXU dot (the W4A8 contraction stage).
    """
    b = packed[...].astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(b, 28), 28)  # sign-extend low nibble
    hi = jnp.right_shift(jnp.left_shift(b, 24), 28)  # ... and the high one
    k2, bn = b.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * k2, bn)


def scale_rows(ref, row0, n: int) -> jax.Array:
    """Rows ``[row0, row0 + n)`` of a VMEM scale block as an (n, bn) array.

    Mosaic loads a dynamic run of rows only from an 8-aligned start, and a
    k block's first group row is any multiple of ``bk // group``; one-row
    loads are legal at every offset.
    """
    rows = [ref[pl.ds(row0 + i, 1), :] for i in range(n)]
    return rows[0] if n == 1 else jnp.concatenate(rows, axis=0)


def dequant_block(packed, scales, zeros, repeat: int, compute_dtype):
    """In-VMEM INT4→float dequant of one weight block (the AIV role, fused).

    packed : (bk//2, bn) int8 — two nibbles per byte along K
    scales : (bk//repeat, bn) float — group scales covering this block
    zeros  : same shape as scales, or None (symmetric)
    returns: (bk, bn) compute_dtype
    """
    q = unpack_int4_block(packed).astype(jnp.float32)
    s = jnp.repeat(scales[...].astype(jnp.float32), repeat, axis=0)
    if zeros is not None:
        q = q - jnp.repeat(zeros[...].astype(jnp.float32), repeat, axis=0)
    return (q * s).astype(compute_dtype)


def dequant_channel_block(rows, scales, zeros, compute_dtype):
    """In-VMEM per-channel INT8→float dequant of one weight block.

    rows   : (bk, bn) int8 ref/array — weight rows stored directly
    scales : (1, bn) float — one scale per output channel
    zeros  : same shape as scales, or None (symmetric)
    returns: (bk, bn) compute_dtype
    """
    q = rows[...].astype(jnp.float32)
    if zeros is not None:
        q = q - zeros[...].astype(jnp.float32)
    return (q * scales[...].astype(jnp.float32)).astype(compute_dtype)
