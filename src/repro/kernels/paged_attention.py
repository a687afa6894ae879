"""Fused paged-attention kernel: block-table walk + KV dequant + online
softmax in ONE pass over the KV working set — for decode (q_len=1),
chunked prefill (q_len=C) and speculative verify (q_len=k+1).

The paper's profiling says bandwidth-bound decode loses to *extra
global-memory traffic*, not compute — and the XLA gather path is exactly
that: ``kvcache.gather_window`` materializes each slot's whole (dequantized)
KV window to HBM, then attention reads it back. PR 9 made chunked prefill
the single prefill path, so every admit and every speculative verify paid
that round-trip too. This kernel walks the per-slot block tables *inside*
the kernel instead, for any query length:

  grid ``(B·Hkv, Q_tiles, S, P)`` — one (slot, kv-head) pair per row of the
  first axis; ``Q_tiles`` tiles the chunk's queries so each kernel instance
  holds ``Tq·G ≤ 128`` query rows (``planning.choose_q_block`` — decode's
  q_len=1 degenerates to the old flash-decoding grid); the slot's
  ``T = S·P`` table entries are split into ``S`` Split-K style partitions
  of ``P`` physical pages each (``planning.choose_kv_partitions``, now
  occupancy-aware of the Q-tile axis — the paper's K ≫ N fix applied to
  the KV axis).

  block tables ride scalar prefetch (``pltpu.PrefetchScalarGridSpec``), so
  the K/V BlockSpec index maps resolve ``tables[slot, s·P + p]`` to a
  *physical page* and the pages stream through VMEM double-buffering — the
  gathered window never exists in HBM. Per-query positions and the chunk
  ``start`` arrive as small expanded int32 operands so the kernel reads
  only its own block.

  a :class:`~repro.kernels.template.DensePages` /
  :class:`~repro.kernels.template.Int8ChannelPages` KV stage produces the
  in-VMEM (page_size, D) tiles (identity load or per-(token, head) INT8
  dequant matching ``kv_dequantize`` exactly), and the flash online softmax
  runs per partition with ``(m, l, acc)`` scratch over all Tq·G rows.

  each partition flushes unnormalized ``(acc, m, l)`` partials; a small
  host-side combine epilogue merges partitions (``exp(m_s - m_max)``
  rescale) and normalizes — the Split-K phase-3 reduce of Alg. 1, at
  O(B·q_len·Hq·S·D) fp32 bytes instead of a second trip over the window.

Masking is purely positional via the pool's ``page_pos`` tags (``-1`` =
empty — the null block a ``-1`` table entry resolves to is all ``-1``
tags) plus the per-row causal / sliding-window / chunk-start clauses, so
ring-wrap SWA, vision-prefix, shared-prefix and stale-rejected-draft
semantics carry over from the gather path verbatim: pool entries at
positions ≥ the chunk start (a sharing peer's copy of this chunk, or a
rejected draft's leftover tags) are masked in-kernel, the single-counting
rule the gather path applied by rewriting ``win.pos``. The chunk's own
K/V — which the caller scatters only *after* attention, preserving the
gather-before-scatter SWA-wrap ordering — contributes one extra
"partition" computed as a tiny C×C host einsum and merged in the same
combine epilogue. Token parity with gather + ``prefix_chunk_attention``
is asserted by tests/test_paged_attention.py.

``interpret=None`` auto-selects interpret mode on CPU hosts
(``common.resolve_interpret``) so the parity suite runs on CPU CI, same as
the GEMM template kernels; the planner (``planning.plan_attention``) never
*auto*-chooses this path off-TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant import KVFormat
from repro.kernels import common, template

NEG_INF = -1e30
LANES = 128

__all__ = ["fused_paged_attention", "fused_chunk_attention", "kv_stage_for"]


def kv_stage_for(pool, fmt: KVFormat):
    """Build the KV stage for a pool/format pair (the attention analogue of
    picking a WeightStage per QuantFormat)."""
    if not fmt.quantized:
        return template.DensePages(k_pool=pool.k_pool, v_pool=pool.v_pool)
    if pool.k_scale is None or pool.v_scale is None:
        raise ValueError(
            f"KV format {fmt.name!r} stores per-(token, head) scales, but "
            f"the pool carries none — was it built with init_pool(..., "
            f"kv_format={fmt.name!r})?")
    return template.Int8ChannelPages(
        k_pool=pool.k_pool, v_pool=pool.v_pool,
        k_scale=pool.k_scale, v_scale=pool.v_scale)


def _make_kernel(stage, *, P: int, window: int, n_stage: int,
                 compute_dtype):
    def kernel(tbl_ref, q_ref, qpos_ref, spos_ref, *rest):
        # tbl_ref (B, S*P) is the scalar-prefetch operand driving the
        # BlockSpec index maps below; qpos/spos are per-row int32 blocks.
        stage_refs = rest[:n_stage]
        pp_ref, o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = rest[n_stage:]
        p = pl.program_id(3)

        @pl.when(p == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        q = q_ref[0, 0, 0]                                # (QG, D)
        k, v = stage.produce(stage_refs, compute_dtype)   # (ps, D) each
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # (QG, ps)

        # pos-tag masking — identical to prefix_chunk_attention's
        # ``kpos >= 0 & kpos <= qpos`` (+ window), plus ``kpos < start``:
        # the pool copy of anything at/after the chunk start (a peer's
        # duplicate, a rejected draft's stale tags) is masked so only the
        # in-flight segment supplies those positions. The null block's
        # tags are all -1, so unmapped table entries mask themselves out.
        kpos = pp_ref[0]                                  # (1, ps)
        qe = qpos_ref[0, 0]                               # (QG, 1)
        se = spos_ref[0, 0]
        valid = (kpos >= 0) & (kpos <= qe) & (kpos < se)
        if window:
            valid &= kpos > qe - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[:, :1]                             # (QG, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        pexp = jnp.exp(s - m_new)                         # (QG, ps)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = jnp.broadcast_to(
            l_ref[:, :1] * corr + jnp.sum(pexp, axis=-1, keepdims=True),
            l_ref.shape)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            pexp.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

        @pl.when(p == P - 1)
        def _flush():
            o_ref[0, 0, 0, 0] = acc_ref[...]              # unnormalized
            mo_ref[0, 0, 0, 0] = m_ref[...]
            lo_ref[0, 0, 0, 0] = l_ref[...]

    return kernel


def _pooled_partials(qg, positions, start, pool, tables, *, window: int,
                     fmt: KVFormat, kv_partitions, interpret):
    """Kernel pass over the pooled pages; per-query unnormalized partials.

    qg: (B, C, Hkv, G, D) pre-scaled queries in the compute dtype;
    positions: (B, C) int32 (-1 = padded row); start: (B,) first chunk
    position per slot (pool entries at ``kpos >= start`` are masked).
    Returns (acc (B,Hkv,C,S,G,D), m (B,Hkv,C,S,G), l (B,Hkv,C,S,G)) with
    ``S`` the Split-K partition count over the page axis.
    """
    B, C, Hkv, G, D = qg.shape
    ps = pool.page_size
    T = tables.shape[1]
    from repro.kernels import planning  # lazy: keep module load light

    Tq = planning.choose_q_block(C, G)
    QT = C // Tq
    QG = Tq * G
    if kv_partitions is None:
        kv_partitions = planning.choose_kv_partitions(B, Hkv, T, q_tiles=QT)
    S = max(1, min(int(kv_partitions), T))
    if T % S:
        raise ValueError(
            f"kv_partitions={S} must divide the table length T={T} "
            f"(choose_kv_partitions only returns divisors)")
    P = T // S

    # host-side prep: q rows laid out (qt, tq, g); per-row positions and
    # chunk starts expanded on the host into (QG, 1) columns, so each
    # kernel instance reads nothing but its own block
    qk = qg.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, QT, QG, D)
    qpos = jnp.broadcast_to(
        positions.reshape(B, QT, Tq, 1).astype(jnp.int32),
        (B, QT, Tq, G)).reshape(B, QT, QG, 1)
    spos = jnp.broadcast_to(
        start.reshape(B, 1, 1, 1).astype(jnp.int32), (B, QT, QG, 1))
    bt = jnp.where(tables < 0, 0, tables).astype(jnp.int32)   # NULL_BLOCK=0

    stage = kv_stage_for(pool, fmt)
    operands = stage.operands()
    n_stage = len(operands)

    def slot(bh):
        return bh // Hkv

    def head(bh):
        return bh % Hkv

    def page(bh, s, p, tbl):
        return tbl[slot(bh), s * P + p]

    def row_block():
        return pl.BlockSpec((1, 1, QG, 1),
                            lambda bh, qt, s, p, tbl: (slot(bh), qt, 0, 0))

    in_specs = [
        pl.BlockSpec((1, 1, 1, QG, D),
                     lambda bh, qt, s, p, tbl:
                     (slot(bh), head(bh), qt, 0, 0)),
        row_block(),
        row_block(),
    ]
    # stage operands are (nb, Hkv, ps, ·) pools, one (page, head) tile each
    for shape in stage.block_shapes(ps, D):
        in_specs.append(pl.BlockSpec(
            shape, lambda bh, qt, s, p, tbl:
            (page(bh, s, p, tbl), head(bh), 0, 0)))
    in_specs.append(pl.BlockSpec(            # page_pos tags as (nb, 1, ps)
        (1, 1, ps), lambda bh, qt, s, p, tbl: (page(bh, s, p, tbl), 0, 0)))

    def part_spec(last):
        return pl.BlockSpec((1, 1, 1, 1, QG, last),
                            lambda bh, qt, s, p, tbl:
                            (slot(bh), head(bh), qt, s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B * Hkv, QT, S, P),
        in_specs=in_specs,
        out_specs=[part_spec(D), part_spec(LANES), part_spec(LANES)],
        scratch_shapes=[
            pltpu.VMEM((QG, LANES), jnp.float32),     # running max
            pltpu.VMEM((QG, LANES), jnp.float32),     # running denom
            pltpu.VMEM((QG, D), jnp.float32),         # unnormalized acc
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        _make_kernel(stage, P=P, window=window, n_stage=n_stage,
                     compute_dtype=qk.dtype),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, Hkv, QT, S, QG, D), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, QT, S, QG, LANES), jnp.float32),
            jax.ShapeDtypeStruct((B, Hkv, QT, S, QG, LANES), jnp.float32),
        ],
        compiler_params=common.compiler_params(
            ("parallel", "parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="paged_attention",
    )(bt, qk, qpos, spos, *operands, pool.page_pos[:, None, :])

    def per_query(x):
        # (B, Hkv, QT, S, QG, ·) → (B, Hkv, C, S, G, ·): split QG = Tq·G
        # and move the query axis out of the partition axis's way
        y = x.reshape(B, Hkv, QT, S, Tq, G, *x.shape[5:])
        y = jnp.moveaxis(y, 4, 3)
        return y.reshape(B, Hkv, C, S, G, *x.shape[5:])

    return (per_query(o_part), per_query(m_part[..., 0]),
            per_query(l_part[..., 0]))


def _combine(acc, m, l):
    """Merge partition partials over axis 3 and normalize — the Split-K
    phase-3 reduce of Alg. 1. Fully-masked partitions carry m = NEG_INF
    and cancel via exp(NEG_INF - m_max) = 0; fully-masked rows (padded
    queries) come out finite garbage that callers discard."""
    m_max = jnp.max(m, axis=3)                         # (B, Hkv, C, G)
    alpha = jnp.exp(m - m_max[:, :, :, None])          # (B, Hkv, C, S, G)
    l_tot = jnp.sum(l * alpha, axis=3)
    out = jnp.sum(acc * alpha[..., None], axis=3)      # (B, Hkv, C, G, D)
    return out / jnp.maximum(l_tot, 1e-30)[..., None]


def fused_paged_attention(
    q: jax.Array,                 # (B, Hq, D) — one new token per slot
    pool,                         # kvcache.PagedKVCache (one layer)
    tables: jax.Array,            # (B, T) int32 block tables, -1 = unmapped
    pos: jax.Array,               # (B,) int32 absolute positions
    *,
    window: int = 0,
    fmt: KVFormat,
    out_dtype,
    kv_partitions: Optional[int] = None,
    interpret=None,
) -> jax.Array:
    """One-pass paged decode attention; drop-in for ``gather_window`` +
    ``decode_attention`` (same masking, same dtype policy, same output).

    The q_len=1 regime of the multi-query kernel: decode inserts the new
    token BEFORE attending, so its position is already in the pool and
    ``start = pos + 1`` makes the chunk-start clause ``kpos < start``
    collapse onto the decode mask ``kpos <= pos`` exactly.

    ``kv_partitions`` is the Split-K degree over the page axis (None →
    ``planning.choose_kv_partitions``); ``interpret=None`` auto-selects
    interpret mode on CPU.
    """
    interpret = common.resolve_interpret(interpret)
    B, Hq, D = q.shape
    Hkv = pool.k_pool.shape[1]
    G = Hq // Hkv
    # host-side prep, mirroring the gather path's dtype policy exactly:
    # q pre-scaled in fp32 then cast to the cache compute dtype
    compute_dtype = jnp.dtype(out_dtype)
    qg = (q.reshape(B, 1, Hkv, G, D).astype(jnp.float32)
          * (D ** -0.5)).astype(compute_dtype)
    pos = pos.astype(jnp.int32)
    acc, m, l = _pooled_partials(
        qg, pos[:, None], pos + 1, pool, tables, window=window, fmt=fmt,
        kv_partitions=kv_partitions, interpret=interpret)
    out = _combine(acc, m, l)                          # (B, Hkv, 1, G, D)
    return out[:, :, 0].reshape(B, Hq, D).astype(q.dtype)


def fused_chunk_attention(
    q: jax.Array,                 # (B, C, Hq, D) rope'd chunk queries
    kseg: jax.Array,              # (B, C, Hkv, D) chunk K after the
    vseg: jax.Array,              # (B, C, Hkv, D) quantize round-trip
    pool,                         # kvcache.PagedKVCache (one layer)
    tables: jax.Array,            # (B, T) int32 block tables, -1 = unmapped
    positions: jax.Array,         # (B, C) int32 absolute, -1 = padding
    *,
    window: int = 0,
    fmt: KVFormat,
    out_dtype,
    kv_partitions: Optional[int] = None,
    interpret=None,
) -> jax.Array:
    """One-pass paged attention for a (B, C) chunk — chunked prefill
    (C = prefill chunk) and speculative verify (C = k+1): drop-in for
    ``gather_window`` + segment concat + ``prefix_chunk_attention``.

    The pooled window is one kernel pass (entries at positions ≥ the
    chunk start are masked in-kernel — the single-counting rule the
    gather path applied via ``wpos``); the C×C intra-chunk attention over
    ``kseg``/``vseg`` — the chunk's own K/V after the same
    quantize→dequantize round-trip its stored copy takes — is a tiny host
    einsum merged into the combine epilogue as one extra partition.
    Callers scatter the chunk into the pool only AFTER this returns,
    preserving the gather-before-scatter ordering that keeps SWA ring
    wrap correct. Rows with ``positions < 0`` produce garbage the callers
    discard, exactly like the gather path.
    """
    interpret = common.resolve_interpret(interpret)
    B, C, Hq, D = q.shape
    Hkv = kseg.shape[2]
    G = Hq // Hkv
    compute_dtype = jnp.dtype(out_dtype)
    qg = (q.reshape(B, C, Hkv, G, D).astype(jnp.float32)
          * (D ** -0.5)).astype(compute_dtype)
    positions = positions.astype(jnp.int32)
    acc, m, l = _pooled_partials(
        qg, positions, positions[:, 0], pool, tables, window=window,
        fmt=fmt, kv_partitions=kv_partitions, interpret=interpret)

    # intra-chunk partial: prefix_chunk_attention's mask and dtype policy
    # over the segment alone (fp32 scores, p cast to the V dtype)
    ks = kseg.astype(compute_dtype)
    vs = vseg.astype(compute_dtype)
    s = jnp.einsum("bchgd,bwhd->bhcgw", qg, ks,
                   preferred_element_type=jnp.float32)  # (B,Hkv,C,G,C)
    kpos = positions[:, None, None, None, :]
    qpos = positions[:, None, :, None, None]
    valid = (kpos >= 0) & (kpos <= qpos)
    if window:
        valid = valid & (kpos > qpos - window)
    s = jnp.where(valid, s, NEG_INF)
    m_seg = jnp.max(s, axis=-1)                         # (B, Hkv, C, G)
    pexp = jnp.exp(s - m_seg[..., None])
    l_seg = jnp.sum(pexp, axis=-1)
    acc_seg = jnp.einsum("bhcgw,bwhd->bhcgd", pexp.astype(vs.dtype), vs,
                         preferred_element_type=jnp.float32)

    acc = jnp.concatenate([acc, acc_seg[:, :, :, None]], axis=3)
    m = jnp.concatenate([m, m_seg[:, :, :, None]], axis=3)
    l = jnp.concatenate([l, l_seg[:, :, :, None]], axis=3)
    out = _combine(acc, m, l)                           # (B, Hkv, C, G, D)
    out = out.transpose(0, 2, 1, 3, 4).reshape(B, C, Hq, D)
    return out.astype(q.dtype)
