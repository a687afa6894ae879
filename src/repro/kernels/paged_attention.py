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

  grid ``(B, Q_tiles, S, NJ)`` — one slot per row of the first axis, all
  of its KV heads in every step; ``Q_tiles`` tiles the chunk's queries so
  each kernel instance holds ``Tq·G ≤ 128`` query rows
  (``planning.choose_q_block`` — decode's q_len=1 degenerates to one
  tile); the slot's ``T`` table entries are split into ``S`` Split-K
  style partitions (``planning.choose_kv_partitions``, occupancy-aware of
  the Q-tile axis — the paper's K ≫ N fix applied to the KV axis), each
  walked in ``NJ`` blocks of ``ppb`` whole pages
  (``planning.choose_pages_per_block``: a power-of-two divisor of T/S,
  up to 512 tokens, under a VMEM budget). A table whose page count is
  not a whole number of blocks is padded with -1 entries.

  One grid step per (page, head) tile cost about 0.4 us of fixed
  overhead, and at long contexts that overhead, not HBM bytes, bounded
  the kernel; a step now covers ``ppb`` pages of ``Hkv`` heads.

  block tables ride scalar prefetch (``pltpu.PrefetchScalarGridSpec``):
  each of the block's ``ppb`` pages is its own K and its own V operand,
  whose BlockSpec index map resolves ``tables[slot, (s·NJ + j)·ppb + i]``
  to a *physical page*, so the pipeline copies one ``(Hkv, ps, D)`` page
  of every head per DMA and double-buffers the next block behind this
  one — the gathered window never exists in HBM. (Manual DMAs of
  ``pool.at[page]`` would do the same, but Mosaic refuses to slice a pool
  whose ``D`` is not a multiple of 128, such as danube's 80.) Position
  tags, and the ``kv8_channel`` scales, are gathered through the tables
  by XLA into one lane row per block (per head for scales); per-query
  positions and the chunk ``start`` arrive as small expanded int32
  operands, so the kernel reads only its own blocks.

  a :class:`~repro.kernels.template.DensePages` /
  :class:`~repro.kernels.template.Int8ChannelPages` KV stage places the
  per-token scales (none for ``kv_fp16``); per head, the block's pages
  join as ``(ppb·ps, D)`` rows, one MXU dot gives the ``(Tq·G, ppb·ps)``
  scores, and the flash online softmax runs per partition with
  ``(m, l, acc)`` scratch per head over all Tq·G rows.

  each partition flushes unnormalized ``(acc, m, l)`` partials; a small
  host-side combine epilogue merges partitions (``exp(m_s - m_max)``
  rescale) and normalizes — the Split-K phase-3 reduce of Alg. 1, at
  O(B·q_len·Hq·S·D) fp32 bytes instead of a second trip over the window.

Masking is purely positional via the pool's ``page_pos`` tags (``-1`` =
empty — the null block a ``-1`` or padding table entry resolves to is all
``-1`` tags) plus the per-row causal / sliding-window / chunk-start
clauses, so ring-wrap SWA, vision-prefix, shared-prefix and
stale-rejected-draft semantics carry over from the gather path verbatim:
pool entries at positions ≥ the chunk start (a sharing peer's copy of
this chunk, or a rejected draft's leftover tags) are masked in-kernel,
the single-counting rule the gather path applied by rewriting
``win.pos``. The chunk's own K/V — which the caller scatters only *after*
attention, preserving the gather-before-scatter SWA-wrap ordering —
contributes one extra "partition" computed as a tiny C×C host einsum and
merged in the same combine epilogue. Token parity with gather + ``prefix_chunk_attention``
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


def _make_kernel(stage, *, Hkv: int, ppb: int, window: int, n_tok: int,
                 compute_dtype):
    def kernel(tbl_ref, q_ref, qpos_ref, spos_ref, *rest):
        # tbl_ref (B, S·NJ·ppb) is the scalar-prefetched table the page
        # operands' index maps read; the ppb K and ppb V refs each hold
        # one (1, Hkv, ps, D) page; tag_ref and the stage's token refs
        # are lane rows of the block's ppb·ps tokens
        k_refs, v_refs = rest[:ppb], rest[ppb:2 * ppb]
        tag_ref = rest[2 * ppb]
        tok_refs = rest[2 * ppb + 1:2 * ppb + 1 + n_tok]
        o_ref, mo_ref, lo_ref, m_ref, l_ref, acc_ref = \
            rest[2 * ppb + 1 + n_tok:]
        j = pl.program_id(3)

        @pl.when(j == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        # pos-tag masking — identical to prefix_chunk_attention's
        # ``kpos >= 0 & kpos <= qpos`` (+ window), plus ``kpos < start``:
        # the pool copy of anything at/after the chunk start (a peer's
        # duplicate, a rejected draft's stale tags) is masked so only the
        # in-flight segment supplies those positions. The null block's
        # tags are all -1, so unmapped and padded table entries mask
        # themselves out.
        kpos = tag_ref[0, 0]                              # (1, L)
        qe = qpos_ref[0, 0]                               # (QG, 1)
        se = spos_ref[0, 0]
        valid = (kpos >= 0) & (kpos <= qe) & (kpos < se)
        if window:
            valid &= kpos > qe - window

        for h in range(Hkv):
            k = _rows(k_refs, h, compute_dtype)           # (L, D)
            v = _rows(v_refs, h, compute_dtype)
            sc = jax.lax.dot_general(
                q_ref[0, h, 0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)       # (QG, L)
            sc = jnp.where(valid, stage.scores(sc, tok_refs, h), NEG_INF)

            m_prev = m_ref[h, :, :1]                      # (QG, 1)
            m_new = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
            pexp = jnp.exp(sc - m_new)                    # (QG, L)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = jnp.broadcast_to(
                l_ref[h, :, :1] * corr
                + jnp.sum(pexp, axis=-1, keepdims=True), l_ref.shape[1:])
            pv = stage.probs(pexp, tok_refs, h).astype(compute_dtype)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                pv, v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])

        @pl.when(j == pl.num_programs(3) - 1)
        def _flush():
            o_ref[0, :, 0, 0] = acc_ref[...]              # unnormalized
            mo_ref[0, :, 0, 0] = m_ref[...]
            lo_ref[0, :, 0, 0] = l_ref[...]

    return kernel


def _rows(page_refs, h, compute_dtype):
    """Head ``h`` of the block's pages as (ppb·ps, D) rows for the MXU."""
    tiles = [r[0, h] for r in page_refs]
    rows = tiles[0] if len(tiles) == 1 else jnp.concatenate(tiles, axis=0)
    return rows.astype(compute_dtype)


def _pooled_partials(qg, positions, start, pool, tables, *, window: int,
                     fmt: KVFormat, kv_partitions, pages_per_block,
                     interpret):
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
    if pages_per_block is None:
        pages_per_block = planning.choose_pages_per_block(
            T, ps, Hkv, D, pool.k_pool.dtype, QG, S)
    ppb = max(1, int(pages_per_block))
    n_blocks = -(-T // ppb)
    if n_blocks % S:
        raise ValueError(
            f"kv_partitions={S} must divide the table's {n_blocks} blocks "
            f"of {ppb} pages (T={T}; choose_pages_per_block only returns "
            f"divisors of T // S)")
    NJ = n_blocks // S
    L = ppb * ps

    # host-side prep: q rows laid out (qt, tq, g); per-row positions and
    # chunk starts expanded on the host into (QG, 1) columns, so each
    # kernel instance reads nothing but its own block
    qk = qg.transpose(0, 2, 1, 3, 4).reshape(B, Hkv, QT, QG, D)
    qpos = jnp.broadcast_to(
        positions.reshape(B, QT, Tq, 1).astype(jnp.int32),
        (B, QT, Tq, G)).reshape(B, QT, QG, 1)
    spos = jnp.broadcast_to(
        start.reshape(B, 1, 1, 1).astype(jnp.int32), (B, QT, QG, 1))
    # -1 entries, and the padding up to whole blocks, read the null block
    # (NULL_BLOCK=0, all tags -1)
    bt = jnp.where(tables < 0, 0, tables).astype(jnp.int32)
    bt = jnp.pad(bt, ((0, 0), (0, n_blocks * ppb - T)))

    # per-token values gathered through the tables into one lane row per
    # block (tags) or per (block, head) (the stage's scales)
    tags = pool.page_pos[bt].reshape(B, n_blocks, 1, L)

    def head_rows(x):                                 # (nb, Hkv, ps)
        y = x[bt].reshape(B, n_blocks, ppb, Hkv, ps)
        return y.transpose(0, 1, 3, 2, 4).reshape(B, n_blocks, Hkv, L)

    stage = kv_stage_for(pool, fmt)
    tok = [head_rows(x) for x in stage.token_values()]

    def blk(b, qt, s, j, tbl):
        return (b, s * NJ + j, 0, 0)

    def page_spec(i):
        # page i of block j: one (1, Hkv, ps, D) DMA of every head
        def index(b, qt, s, j, tbl):
            return (tbl[b, (s * NJ + j) * ppb + i], 0, 0, 0)
        return pl.BlockSpec((1,) + pool.k_pool.shape[1:], index)

    def row_block():
        return pl.BlockSpec((1, 1, QG, 1),
                            lambda b, qt, s, j, tbl: (b, qt, 0, 0))

    in_specs = [
        pl.BlockSpec((1, Hkv, 1, QG, D),
                     lambda b, qt, s, j, tbl: (b, 0, qt, 0, 0)),
        row_block(),
        row_block(),
        *[page_spec(i) for i in range(ppb)],          # K pages
        *[page_spec(i) for i in range(ppb)],          # V pages
        pl.BlockSpec((1, 1, 1, L), blk),
        *[pl.BlockSpec((1, 1, Hkv, L), blk) for _ in tok],
    ]

    def part_spec(last):
        return pl.BlockSpec((1, Hkv, 1, 1, QG, last),
                            lambda b, qt, s, j, tbl: (b, 0, qt, s, 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, QT, S, NJ),
        in_specs=in_specs,
        out_specs=[part_spec(D), part_spec(LANES), part_spec(LANES)],
        scratch_shapes=[
            pltpu.VMEM((Hkv, QG, LANES), jnp.float32),    # running max
            pltpu.VMEM((Hkv, QG, LANES), jnp.float32),    # running denom
            pltpu.VMEM((Hkv, QG, D), jnp.float32),        # unnormalized acc
        ],
    )
    o_part, m_part, l_part = pl.pallas_call(
        _make_kernel(stage, Hkv=Hkv, ppb=ppb, window=window,
                     n_tok=len(tok), compute_dtype=qk.dtype),
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
    )(bt, qk, qpos, spos, *[stage.k_pool] * ppb, *[stage.v_pool] * ppb,
      tags, *tok)

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
    pages_per_block: Optional[int] = None,
    interpret=None,
) -> jax.Array:
    """One-pass paged decode attention; drop-in for ``gather_window`` +
    ``decode_attention`` (same masking, same dtype policy, same output).

    The q_len=1 regime of the multi-query kernel: decode inserts the new
    token BEFORE attending, so its position is already in the pool and
    ``start = pos + 1`` makes the chunk-start clause ``kpos < start``
    collapse onto the decode mask ``kpos <= pos`` exactly.

    ``kv_partitions`` is the Split-K degree over the page axis (None →
    ``planning.choose_kv_partitions``), ``pages_per_block`` the table
    pages one grid step covers (None → ``planning.choose_pages_per_block``);
    ``interpret=None`` auto-selects interpret mode on CPU.
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
        kv_partitions=kv_partitions, pages_per_block=pages_per_block,
        interpret=interpret)
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
    pages_per_block: Optional[int] = None,
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
        fmt=fmt, kv_partitions=kv_partitions,
        pages_per_block=pages_per_block, interpret=interpret)

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
