"""Fused paged-attention decode kernel tests: op-level parity with the
XLA gather path (both KV formats, windowed and full attention, every
Split-K partition degree), the gather_window fp16 fast path, the
attention-path planner, and engine-level token parity across SWA-wrap /
vision-prefix / shared-prefix-CoW archs — single-device and TP×DP on 8
fake devices (subprocess)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import quant
from repro.kernels import common, planning
from repro.kernels.paged_attention import (
    fused_chunk_attention, fused_paged_attention, kv_stage_for)
from repro.kernels import template
from repro.models import attention, transformer as T
from repro.runtime import kvcache as kvc
from repro.runtime import metrics as rmetrics
from repro.runtime.engine import Request, ServingEngine

ROOT = os.path.join(os.path.dirname(__file__), "..")
KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# op-level parity: fused kernel ≡ gather + decode_attention
# ---------------------------------------------------------------------------

def _filled_pool(fmt_name, *, B=2, Hkv=2, D=32, ps=4, T_pages=4, fill=14,
                 wrap_from=0):
    """A pool with ``fill`` tokens scattered per slot through the public
    insert path. ``wrap_from > 0`` writes positions [wrap_from, wrap_from +
    fill) into a T_pages·ps ring — the SWA wrap layout where logical
    offsets alias ``pos % cache_len``."""
    fmt = quant.get_kv_format(fmt_name)
    nb = 1 + B * T_pages
    cache_len = T_pages * ps
    pool = kvc.init_pool(nb, ps, Hkv, D, jnp.float32, fmt_name)
    tables = jnp.asarray(
        (1 + np.arange(B * T_pages, dtype=np.int32)).reshape(B, T_pages))
    for p in range(wrap_from, wrap_from + fill):
        k = jax.random.normal(jax.random.fold_in(KEY, 2 * p),
                              (B, Hkv, D), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(KEY, 2 * p + 1),
                              (B, Hkv, D), jnp.float32)
        pool = kvc.paged_insert(pool, tables, k, v,
                                jnp.full((B,), p, jnp.int32),
                                cache_len=cache_len, fmt=fmt)
    pos = jnp.full((B,), wrap_from + fill - 1, jnp.int32)
    q = jax.random.normal(jax.random.fold_in(KEY, 999),
                          (B, 2 * Hkv, D), jnp.float32)
    return q, pool, tables, pos, fmt


# page blocks: (kv_partitions, pages_per_block, table pages) — one page
# per step, a block inside the partition, the whole partition, S=2 with
# blocks, and ppb=3 over 4 pages (the table padded with -1 up to 6)
BLOCKS = [
    pytest.param((1, 1, 8), id="S1-ppb1"),
    pytest.param((1, 4, 8), id="S1-ppb4"),
    pytest.param((1, 8, 8), id="S1-whole"),
    pytest.param((2, 2, 8), id="S2-ppb2"),
    pytest.param((2, 4, 8), id="S2-whole"),
    pytest.param((1, 3, 4), id="S1-ppb3-padded"),
]


@pytest.mark.parametrize("fmt_name", ["kv_fp16", "kv8_channel"])
@pytest.mark.parametrize("window", [0, 8])
@pytest.mark.parametrize("parts", [1, 2, 4] + BLOCKS)
def test_fused_matches_gather(fmt_name, window, parts):
    parts, ppb, T_pages = parts if isinstance(parts, tuple) \
        else (parts, None, 4)
    q, pool, tables, pos, fmt = _filled_pool(fmt_name, T_pages=T_pages,
                                             fill=3 * T_pages + 2)
    ref = kvc.paged_decode_attention(q, pool, tables, pos, window=window,
                                     fmt=fmt, out_dtype=jnp.float32)
    out = fused_paged_attention(q, pool, tables, pos, window=window,
                                fmt=fmt, out_dtype=jnp.float32,
                                kv_partitions=parts, pages_per_block=ppb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fused_matches_gather_wrapped_ring():
    """SWA wrap: positions past cache_len alias earlier ring offsets, so
    pages hold out-of-order position tags — masking must follow the tags,
    not the page order."""
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16", wrap_from=9)
    for window in (0, 8):
        ref = kvc.paged_decode_attention(q, pool, tables, pos,
                                         window=window, fmt=fmt,
                                         out_dtype=jnp.float32)
        for ppb in (None, 1, 2, 4):         # blocks of the wrapped ring
            out = fused_paged_attention(q, pool, tables, pos, window=window,
                                        fmt=fmt, out_dtype=jnp.float32,
                                        pages_per_block=ppb)
            np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                       rtol=2e-5, atol=2e-6)


def test_fused_unmapped_tables_mask_to_null_block():
    """-1 table entries resolve to the null block (all -1 tags): parity
    holds when slots hold windows of different lengths."""
    q, pool, tables, pos, fmt = _filled_pool("kv8_channel", fill=6)
    tables = tables.at[1, 2:].set(-1)      # slot 1: half the table unmapped
    ref = kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                     out_dtype=jnp.float32)
    out = fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                                out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fused_null_only_block():
    """A block made only of -1 entries (slot 1's second block of two) is
    all null-block tags: it contributes nothing, the slot's first block
    carries its whole window."""
    q, pool, tables, pos, fmt = _filled_pool("kv8_channel", T_pages=8,
                                             fill=14)
    tables = tables.at[1, 4:].set(-1)
    ref = kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                     out_dtype=jnp.float32)
    for parts, ppb in ((1, 4), (2, 4), (2, 2)):
        out = fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                                    out_dtype=jnp.float32,
                                    kv_partitions=parts, pages_per_block=ppb)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)


def test_fused_partition_count_validation():
    """S must divide the table's block count T / ppb (T padded up to
    whole blocks): 4 pages in blocks of 1 do not split 3 ways, nor 2
    blocks of 2 four ways; 2 blocks of 3 (padded) split 2 ways."""
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16")   # T=4 pages
    with pytest.raises(ValueError, match="must divide"):
        fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                              out_dtype=jnp.float32, kv_partitions=3)
    with pytest.raises(ValueError, match="must divide"):
        fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                              out_dtype=jnp.float32, kv_partitions=4,
                              pages_per_block=2)
    ref = kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                     out_dtype=jnp.float32)
    out = fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                                out_dtype=jnp.float32, kv_partitions=2,
                                pages_per_block=3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fused_interpret_toggle():
    """The CPU-CI fallback: interpret=None resolves per-backend (True on
    CPU), and forcing interpret=True gives the same tokens — the toggle
    the parity suite rides."""
    assert common.resolve_interpret(None) is common.is_cpu()
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16")
    auto = fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                                 out_dtype=jnp.float32)
    forced = fused_paged_attention(q, pool, tables, pos, fmt=fmt,
                                   out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))


def test_kv_stage_selection_and_refusal():
    _, pool, _, _, _ = _filled_pool("kv_fp16")
    assert isinstance(kv_stage_for(pool, quant.get_kv_format("kv_fp16")),
                      template.DensePages)
    _, qpool, _, _, _ = _filled_pool("kv8_channel")
    assert isinstance(kv_stage_for(qpool, quant.get_kv_format("kv8_channel")),
                      template.Int8ChannelPages)
    # a quantized format over a scale-less pool is refused loudly
    with pytest.raises(ValueError, match="scales"):
        kv_stage_for(pool, quant.get_kv_format("kv8_channel"))


# ---------------------------------------------------------------------------
# op-level multi-query parity: fused_chunk_attention ≡ gather + segment
# ---------------------------------------------------------------------------

def _roundtrip(x, fmt):
    return quant.kv_dequantize(*quant.kv_quantize(x, fmt), fmt=fmt,
                               dtype=jnp.float32)


def _chunk_setup(fmt_name, *, B=2, C=3, start=6, Hkv=2, D=32, ps=4,
                 T_pages=4):
    """A pool holding positions [0, start) per slot plus an in-flight
    chunk of C tokens at positions [start, start+C) — the pre-scatter
    state both chunk-attention paths see. Positions past cache_len alias
    earlier ring offsets (the SWA-wrap layout)."""
    fmt = quant.get_kv_format(fmt_name)
    nb = 1 + B * T_pages
    cache_len = T_pages * ps
    pool = kvc.init_pool(nb, ps, Hkv, D, jnp.float32, fmt_name)
    tables = jnp.asarray(
        (1 + np.arange(B * T_pages, dtype=np.int32)).reshape(B, T_pages))
    for p in range(start):
        k = jax.random.normal(jax.random.fold_in(KEY, 2 * p),
                              (B, Hkv, D), jnp.float32)
        v = jax.random.normal(jax.random.fold_in(KEY, 2 * p + 1),
                              (B, Hkv, D), jnp.float32)
        pool = kvc.paged_insert(pool, tables, k, v,
                                jnp.full((B,), p, jnp.int32),
                                cache_len=cache_len, fmt=fmt)
    q = jax.random.normal(jax.random.fold_in(KEY, 777),
                          (B, C, 2 * Hkv, D), jnp.float32)
    # the chunk segment takes the same quantize round-trip the model
    # applies before attending it (a no-op for kv_fp16)
    kseg = _roundtrip(jax.random.normal(jax.random.fold_in(KEY, 778),
                                        (B, C, Hkv, D), jnp.float32), fmt)
    vseg = _roundtrip(jax.random.normal(jax.random.fold_in(KEY, 779),
                                        (B, C, Hkv, D), jnp.float32), fmt)
    positions = jnp.broadcast_to(
        start + jnp.arange(C, dtype=jnp.int32), (B, C))
    return q, kseg, vseg, pool, tables, positions, fmt


def _chunk_reference(q, kseg, vseg, pool, tables, positions, *, window,
                     fmt):
    """The gather path verbatim (transformer._paged_chunk_attn gather
    branch): materialize the window, mask entries at chunk positions,
    concatenate the segment, run prefix_chunk_attention."""
    win = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32)
    start = positions[:, :1]
    wpos = jnp.where(win.pos < start, win.pos, -1)
    seq = attention.KVCache(
        k=jnp.concatenate([win.k, kseg.astype(win.k.dtype)], axis=1),
        v=jnp.concatenate([win.v, vseg.astype(win.v.dtype)], axis=1),
        pos=jnp.concatenate([wpos, positions], axis=1))
    return attention.prefix_chunk_attention(q, seq, positions,
                                            window=window)


@pytest.mark.parametrize("fmt_name", ["kv_fp16", "kv8_channel"])
@pytest.mark.parametrize("C,start", [(1, 6), (3, 6), (6, 5), (32, 6)])
@pytest.mark.parametrize("window", [0, 8])
def test_fused_chunk_matches_gather(fmt_name, C, start, window):
    """The tentpole parity matrix: q_len ∈ {1, 3, page-straddling 6, a
    32-token prefill chunk}, both KV formats, full + sliding-window masks
    — the fused multi-query walk must reproduce the gathered-window
    reference bit-for-policy."""
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup(
        fmt_name, C=C, start=start)
    ref = _chunk_reference(q, ks, vs, pool, tables, positions,
                           window=window, fmt=fmt)
    out = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                window=window, fmt=fmt,
                                out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("parts", [1, 2] + [
    pytest.param((1, 1), id="S1-ppb1"), pytest.param((1, 4), id="S1-whole"),
    pytest.param((2, 2), id="S2-whole"), pytest.param((1, 3), id="padded")])
def test_fused_chunk_split_k(parts):
    parts, ppb = parts if isinstance(parts, tuple) else (parts, None)
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup(
        "kv8_channel", C=3, start=9)
    ref = _chunk_reference(q, ks, vs, pool, tables, positions,
                           window=0, fmt=fmt)
    out = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                window=0, fmt=fmt, out_dtype=jnp.float32,
                                kv_partitions=parts, pages_per_block=ppb)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fused_chunk_swa_wrap():
    """Chunk positions past cache_len: the pool's pos tags are
    out-of-order across pages and stale single-counted entries at chunk
    positions must stay masked — the layout chunked prefill hits on SWA
    archs whose prompt exceeds the logical window."""
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup(
        "kv_fp16", C=3, start=18)   # cache_len=16 → the ring has wrapped:
                                    # page 0 holds tags {16, 17, 2, 3}
    for window in (0, 8):
        ref = _chunk_reference(q, ks, vs, pool, tables, positions,
                               window=window, fmt=fmt)
        out = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                    window=window, fmt=fmt,
                                    out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=2e-5, atol=2e-6)


def test_fused_chunk_null_block_padding():
    """-1 table tails resolve to the null block; padded query rows
    (positions = -1, the verify step's short-draft rows) produce garbage
    both paths discard — parity is asserted on live rows only."""
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup(
        "kv8_channel", C=3, start=5)
    tables = tables.at[1, 2:].set(-1)
    positions = positions.at[1, 1:].set(-1)     # slot 1: one live query
    ref = _chunk_reference(q, ks, vs, pool, tables, positions,
                           window=0, fmt=fmt)
    out = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                window=0, fmt=fmt, out_dtype=jnp.float32)
    live = np.asarray(positions) >= 0
    np.testing.assert_allclose(np.asarray(out)[live], np.asarray(ref)[live],
                               rtol=2e-5, atol=2e-6)


def test_fused_chunk_masks_pool_entries_at_chunk_positions():
    """Single-counting: pool entries tagged >= positions[:, 0] (a sharing
    peer's copy of the same tokens, or stale rejected drafts) must not be
    double-attended alongside the in-flight segment."""
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup(
        "kv_fp16", C=3, start=6)
    # poison the pool at the chunk's own positions with junk copies
    cache_len = 16
    for j in range(3):
        junk = jnp.full((2, 2, 32), 37.0, jnp.float32)
        pool = kvc.paged_insert(pool, tables, junk, junk,
                                jnp.full((2,), 6 + j, jnp.int32),
                                cache_len=cache_len, fmt=fmt)
    ref = _chunk_reference(q, ks, vs, pool, tables, positions,
                          window=0, fmt=fmt)
    out = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                window=0, fmt=fmt, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)


def test_fused_chunk_interpret_toggle():
    q, ks, vs, pool, tables, positions, fmt = _chunk_setup("kv_fp16")
    auto = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                 window=0, fmt=fmt, out_dtype=jnp.float32)
    forced = fused_chunk_attention(q, ks, vs, pool, tables, positions,
                                   window=0, fmt=fmt,
                                   out_dtype=jnp.float32, interpret=True)
    np.testing.assert_array_equal(np.asarray(auto), np.asarray(forced))


# ---------------------------------------------------------------------------
# gather_window fp16 fast path (satellite)
# ---------------------------------------------------------------------------

def test_gather_window_fp16_skips_dequant(monkeypatch):
    """Passthrough pools must not route through kv_dequantize (no dequant
    pass, no scale gathers) — the pre-fix behavior cost an extra pool-sized
    elementwise pass per decode step."""
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16")
    want = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32)

    def boom(*a, **k):
        raise AssertionError("kv_dequantize called for a passthrough format")

    monkeypatch.setattr(kvc, "kv_dequantize", boom)
    got = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32)
    np.testing.assert_array_equal(np.asarray(got.k), np.asarray(want.k))
    np.testing.assert_array_equal(np.asarray(got.pos), np.asarray(want.pos))
    # quantized pools still dequantize
    q2, qpool, t2, p2, qfmt = _filled_pool("kv8_channel")
    with pytest.raises(AssertionError, match="passthrough"):
        kvc.gather_window(qpool, t2, fmt=qfmt, out_dtype=jnp.float32)


def test_gather_window_fp16_dtype_cast():
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16")
    win = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.bfloat16)
    assert win.k.dtype == jnp.bfloat16 and win.v.dtype == jnp.bfloat16


def test_paged_decode_attention_rejects_unknown_path():
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16")
    with pytest.raises(ValueError, match="unknown attn_path"):
        kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                   out_dtype=jnp.float32, attn_path="ring")


# ---------------------------------------------------------------------------
# planner: ring vs gather vs fused as a costed decision
# ---------------------------------------------------------------------------

def _problem(**kw):
    base = dict(B=4, Hq=32, Hkv=8, D=128, cache_len=4096, page_size=16,
                kv_format="kv8_channel", paged=True, backend="tpu")
    base.update(kw)
    return planning.AttentionProblem(**base)


def test_plan_attention_backend_split():
    """The acceptance decision: fused wins on TPU for long-context paged
    decode (one trip over the pool); the interpret penalty keeps the XLA
    gather in front on CPU hosts."""
    assert planning.plan_attention(_problem()).path == "fused"
    assert planning.plan_attention(_problem(kv_format="kv_fp16")).path \
        == "fused"
    assert planning.plan_attention(_problem(backend="cpu")).path == "gather"
    # non-paged engines only have the ring layout
    assert planning.plan_attention(
        _problem(paged=False, kv_format="kv_fp16")).path == "ring"


def test_plan_attention_costs_charge_gather_roundtrip():
    """The roofline entries price the gather's HBM round-trip: on TPU the
    gather path is strictly more bytes (and time) than fused for the same
    problem, and the gap grows with context."""
    from repro.core import costmodel as cm
    for ctx in (1024, 4096, 16384):
        gb = cm.paged_attn_bytes("gather", 4, 32, 8, 128, ctx,
                                 quantized=True)
        fb = cm.paged_attn_bytes("fused", 4, 32, 8, 128, ctx,
                                 quantized=True, kv_partitions=8)
        assert fb < gb
        assert cm.attn_decode_time_tpu("fused", 4, 32, 8, 128, ctx,
                                       quantized=True, kv_partitions=8) < \
            cm.attn_decode_time_tpu("gather", 4, 32, 8, 128, ctx,
                                    quantized=True)


def test_plan_attention_forced_path_validation():
    with pytest.raises(ValueError, match="unknown attention path"):
        planning.plan_attention(_problem(), path="flash3")
    with pytest.raises(ValueError, match="does not support"):
        planning.plan_attention(_problem(), path="ring")      # paged
    with pytest.raises(ValueError, match="does not support"):
        planning.plan_attention(_problem(paged=False), path="fused")
    plan = planning.plan_attention(_problem(backend="cpu"), path="fused")
    assert plan.path == "fused"            # forcing beats the cost ranking


def test_choose_kv_partitions_occupancy(monkeypatch):
    # one v5e TensorCore: nothing to fill, never split
    assert planning.choose_kv_partitions(1, 1, 64) == 1
    cores = 8                               # a chip with cores to fill
    monkeypatch.setattr(planning, "num_cores", lambda: cores)
    # grid already full → no split
    assert planning.choose_kv_partitions(cores, 1, 64) == 1
    # underfilled grid → split up to the core count, power-of-2 divisor
    s = planning.choose_kv_partitions(1, 1, 64)
    assert 1 < s <= cores and 64 % s == 0 and (s & (s - 1)) == 0
    # never more partitions than pages
    assert planning.choose_kv_partitions(1, 1, 1) == 1


def test_choose_kv_partitions_q_tiles_occupancy(monkeypatch):
    """Multi-query tiles count toward grid occupancy: a chunk that already
    fills the cores leaves no reason to Split-K."""
    cores = 8
    monkeypatch.setattr(planning, "num_cores", lambda: cores)
    assert planning.choose_kv_partitions(1, 1, 64, q_tiles=cores) == 1
    assert planning.choose_kv_partitions(1, 1, 64, q_tiles=1) > 1


def test_choose_pages_per_block():
    """Page blocks come from shapes alone: a power-of-two divisor of the
    partition's pages, at most 512 tokens, K/V double-buffered with
    padded tiles under the VMEM budget."""
    cell = dict(T=512, ps=8, Hkv=8, D=80, kv_dtype=jnp.bfloat16, QG=4)
    ppb = planning.choose_pages_per_block(**cell)
    assert ppb == planning.choose_pages_per_block(**cell)   # pure
    assert 512 % ppb == 0 and ppb & (ppb - 1) == 0
    assert 256 <= ppb * 8 <= planning.ATTN_BLOCK_TOKENS
    # bf16 (8, 80) head tiles pad to (8, 128): 2 KiB, x8 heads, K and V
    # double-buffered
    assert 4 * ppb * 8 * 8 * 128 * 2 <= planning.ATTN_VMEM_BUDGET
    # pages of 32 rows tile (16, 128) in bf16, (32, 128) in int8: the
    # budget, not the token cap, binds a bf16 block of 16 wide heads
    big = dict(cell, ps=32, Hkv=16, D=128)
    b16 = planning.choose_pages_per_block(**big)
    assert 4 * b16 * 16 * 32 * 128 * 2 <= planning.ATTN_VMEM_BUDGET
    assert b16 * 32 < planning.ATTN_BLOCK_TOKENS
    for S in (1, 2, 4, 8):
        p = planning.choose_pages_per_block(**dict(cell, S=S))
        assert (512 // S) % p == 0
    # odd and tiny tables: the divisor rule holds down to one page
    assert planning.choose_pages_per_block(**dict(cell, T=7)) == 1
    assert planning.choose_pages_per_block(**dict(cell, T=12)) == 4
    # wide heads shrink the block to stay under the budget
    wide = planning.choose_pages_per_block(**dict(cell, Hkv=64, D=128))
    assert wide < ppb
    assert 4 * wide * 64 * 8 * 128 * 2 <= planning.ATTN_VMEM_BUDGET
    # the plan carries what the kernel derives from the same shapes
    plan = planning.plan_attention(
        _problem(Hq=32, Hkv=8, D=80, page_size=8, kv_format="kv_fp16"),
        path="fused")
    assert plan.pages_per_block == planning.choose_pages_per_block(
        4096 // 8, 8, 8, 80, jnp.bfloat16, 4, plan.kv_partitions)


def test_choose_q_block():
    """Q-tile sizing: the largest divisor of q_len whose row block
    (tile × group) stays within one 128-lane register tile."""
    assert planning.choose_q_block(1, 8) == 1
    assert planning.choose_q_block(32, 4) == 32        # 32·4 = 128 exactly
    assert planning.choose_q_block(32, 8) == 16        # cap 128//8
    assert planning.choose_q_block(5, 6) == 5          # k+1 verify widths fit
    t = planning.choose_q_block(12, 16)
    assert t == 6 and 12 % t == 0
    assert planning.choose_q_block(7, 64) == 1         # prime over a tiny cap


def test_plan_attention_multi_query_costed():
    """The q_len-aware decision: fused wins on TPU for chunked prefill
    (q_len=chunk) and speculative verify (q_len=k+1) because gather still
    materializes the full window per call; CPU hosts keep gather. The
    byte model itself must rank fused strictly cheaper."""
    from repro.core import costmodel as cm
    for ql in (5, 32):
        assert planning.plan_attention(
            _problem(B=1, q_len=ql)).path == "fused"
        assert planning.plan_attention(
            _problem(B=1, q_len=ql, backend="cpu")).path == "gather"
        gb = cm.paged_attn_bytes("gather", 1, 32, 8, 128, 4096,
                                 quantized=True, q_len=ql)
        fb = cm.paged_attn_bytes("fused", 1, 32, 8, 128, 4096,
                                 quantized=True, kv_partitions=8, q_len=ql)
        assert fb < gb
        assert cm.attn_decode_time_tpu(
            "fused", 1, 32, 8, 128, 4096, quantized=True,
            kv_partitions=8, q_len=ql) < cm.attn_decode_time_tpu(
            "gather", 1, 32, 8, 128, 4096, quantized=True, q_len=ql)


# ---------------------------------------------------------------------------
# gather_window live-page clamp (satellite)
# ---------------------------------------------------------------------------

def test_gather_window_live_pages_clamp():
    """Clamping at (or above) the per-slot high-water mark drops only
    never-written pages: the surviving window is identical and the
    attention output unchanged — the over-gather fix for young slots."""
    q, pool, tables, pos, fmt = _filled_pool("kv_fp16", fill=6)  # 2 pages hot
    full = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32)
    assert np.all(np.asarray(full.pos[:, 8:]) == -1)   # tail is empty anyway
    clamped = kvc.gather_window(pool, tables, fmt=fmt,
                                out_dtype=jnp.float32, live_pages=2)
    assert clamped.k.shape[1] == 2 * 4                 # 2 pages × page_size 4
    np.testing.assert_array_equal(np.asarray(clamped.k),
                                  np.asarray(full.k[:, :8]))
    np.testing.assert_array_equal(np.asarray(clamped.pos),
                                  np.asarray(full.pos[:, :8]))
    ref = kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                     out_dtype=jnp.float32)
    out = kvc.paged_decode_attention(q, pool, tables, pos, fmt=fmt,
                                     out_dtype=jnp.float32, live_pages=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-6)
    # a clamp wider than the table is a no-op, and the floor is one page
    wide = kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32,
                             live_pages=99)
    assert wide.k.shape == full.k.shape
    assert kvc.gather_window(pool, tables, fmt=fmt, out_dtype=jnp.float32,
                             live_pages=0).k.shape[1] == 4


def test_engine_live_bucket():
    """_live_bucket covers the high-water mark with a power-of-2 fraction
    of the slot table (bounded recompiles), returning None (= full table)
    once the mark is past half the ring."""
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              w4a16_strategy="xla")
    eng = ServingEngine(cfg, _params(cfg), max_batch=2, max_prompt_len=8,
                        max_new_tokens=4, page_size=4)
    w = eng.pages_slot
    assert eng._live_bucket(w) is None
    assert eng._live_bucket(w + 5) is None             # clamped, not wider
    for hw in range(1, w + 1):
        b = eng._live_bucket(hw)
        if b is None:
            assert 2 * hw > w or w % 2 == 1
        else:
            assert hw <= b < w and w % b == 0


# ---------------------------------------------------------------------------
# engine-level token parity: fused ≡ gather across archs × formats
# ---------------------------------------------------------------------------

def _params(cfg, quantized=True):
    p = T.init_params(KEY, cfg)
    return T.quantize_params(p, cfg, min_size=0) if quantized else p


def _requests(cfg, n, P, G, *, same_prompt=False):
    toks = jax.random.randint(KEY, (n, P), 0, cfg.vocab_size)
    reqs = []
    for i in range(n):
        kw = {}
        if cfg.vision_prefix:
            kw["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(KEY, 0 if same_prompt else i),
                (cfg.vision_prefix, cfg.d_model), cfg.dtype)
        reqs.append(Request(rid=i, prompt=toks[0] if same_prompt else toks[i],
                            max_new_tokens=G, **kw))
    return reqs


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "internvl2-1b"])
@pytest.mark.parametrize("kv_format", ["kv_fp16", "kv8_channel"])
def test_fused_engine_parity(arch, kv_format):
    """Fused-paged decode is token-identical to gather decode on the SWA
    (ring-wrap) and vision-prefix archs, both KV formats — the tentpole
    acceptance. Prompts run past the danube window so pages wrap."""
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="xla")
    P, G, n = 12, 6, 2
    params = _params(cfg)

    def run(path):
        eng = ServingEngine(cfg, params, max_batch=n, max_prompt_len=P,
                            max_new_tokens=G, page_size=4,
                            kv_format=kv_format, attn_path=path)
        assert eng.attn_path == path
        return eng.run(_requests(cfg, n, P, G)).results

    got, want = run("fused"), run("gather")
    assert got == want and sorted(got) == list(range(n))


def test_fused_engine_parity_shared_prefix_cow():
    """Shared-prefix CoW arch case: identical prompts alias prompt pages
    until the divergent decode write copies them — the fused walk reads
    the exact same physical pages the gather path does."""
    cfg = dataclasses.replace(configs.get_reduced("internvl2-1b"),
                              w4a16_strategy="xla")
    P, G, n = 8, 4, 2
    params = _params(cfg)

    def run(path):
        eng = ServingEngine(cfg, params, max_batch=n, max_prompt_len=P,
                            max_new_tokens=G, page_size=4, attn_path=path)
        rep = eng.run(_requests(cfg, n, P, G, same_prompt=True))
        return rep.results, rep.peak_pages

    got, pages_f = run("fused")
    want, pages_g = run("gather")
    assert got == want
    assert got[0] == got[1]                 # same prompt → same greedy run
    assert pages_f == pages_g               # identical allocator behavior


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "internvl2-1b"])
def test_fused_chunked_prefill_parity(arch):
    """Multi-chunk prefill (prompt split 5 tokens at a time) through the
    fused multi-query kernel is token-identical to the gather path — SWA
    ring-wrap and vision-prefix archs, quantized pool."""
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="xla")
    P, G, n = 12, 4, 2
    params = _params(cfg)

    def run(path):
        eng = ServingEngine(cfg, params, max_batch=n, max_prompt_len=P,
                            max_new_tokens=G, page_size=4, prefill_chunk=5,
                            kv_format="kv8_channel", attn_path=path)
        assert eng.prefill_attn_path == path
        return eng.run(_requests(cfg, n, P, G)).results

    got, want = run("fused"), run("gather")
    assert got == want and sorted(got) == list(range(n))


def test_fused_verify_parity_ngram():
    """Speculative verify (q_len = k+1) through the fused kernel: same
    tokens AND same acceptance counts as the gather path on repetitive
    prompts the ngram proposer actually drafts against."""
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              w4a16_strategy="xla")
    G, n = 8, 2
    params = _params(cfg)
    prompt = jnp.asarray([5, 6, 7, 5, 6, 7, 5, 6, 7, 5], jnp.int32)

    def run(path):
        eng = ServingEngine(cfg, params, max_batch=n,
                            max_prompt_len=len(prompt), max_new_tokens=G,
                            page_size=4, speculate="ngram", spec_k=3,
                            attn_path=path)
        assert eng.verify_attn_path == path
        rep = eng.run([Request(rid=i, prompt=prompt, max_new_tokens=G)
                       for i in range(n)])
        return rep.results, rep.proposed_tokens, rep.accepted_tokens

    (got, prop_f, acc_f), (want, prop_g, acc_g) = run("fused"), run("gather")
    assert got == want and sorted(got) == list(range(n))
    assert (prop_f, acc_f) == (prop_g, acc_g)


def test_engine_multi_query_path_metrics():
    """Per-regime plan resolution is exported: chunked engines surface the
    prefill path gauge, speculative engines the verify path gauge."""
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              w4a16_strategy="xla")
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_prompt_len=8,
                        max_new_tokens=3, page_size=4, prefill_chunk=4,
                        speculate="ngram", spec_k=2)
    want = "fused" if jax.default_backend() == "tpu" else "gather"
    assert eng.prefill_attn_path == want
    assert eng.verify_attn_path == want
    eng.metrics = rmetrics.MetricsRegistry()
    eng.run(_requests(cfg, 2, 8, 3))
    text = eng.metrics.render()
    assert "engine_prefill_attn_path" in text
    assert "engine_verify_attn_path" in text
    assert "engine_verify_attn_pages_per_block " \
        f"{eng.verify_pages_per_block}" in text


def test_engine_attn_path_resolution_and_metrics():
    """auto resolves per backend (gather on CPU CI), the resolved path is
    exported as a /metrics gauge + per-path step counter, and fused on a
    non-paged engine is refused loudly."""
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              w4a16_strategy="xla")
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_prompt_len=8,
                        max_new_tokens=3, page_size=4)
    assert eng.attn_path == ("fused" if jax.default_backend() == "tpu"
                             else "gather")
    eng.metrics = rmetrics.MetricsRegistry()
    eng.run(_requests(cfg, 2, 8, 3))
    text = eng.metrics.render()
    assert f"engine_attn_path {float(1 if eng.attn_path == 'gather' else 2)}" \
        in text.replace(".0", "") or "engine_attn_path" in text
    assert f"engine_attn_path_steps_{eng.attn_path}" in text
    # pages per fused-kernel grid step, per regime, as planned
    assert f"engine_attn_pages_per_block {eng.pages_per_block}" in text
    assert "engine_prefill_attn_pages_per_block " \
        f"{eng.prefill_pages_per_block}" in text
    fused = ServingEngine(cfg, params, max_batch=2, max_prompt_len=8,
                          max_new_tokens=3, page_size=4, attn_path="fused")
    assert fused.pages_per_block == planning.choose_pages_per_block(
        fused.pages_slot, 4, cfg.num_kv_heads, cfg.head_dim, cfg.dtype,
        cfg.num_heads // cfg.num_kv_heads, fused.kv_partitions)
    with pytest.raises(ValueError, match="does not support"):
        ServingEngine(cfg, params, max_batch=2, max_prompt_len=8,
                      max_new_tokens=3, paged=False, attn_path="fused")
    ring = ServingEngine(cfg, params, max_batch=2, max_prompt_len=8,
                         max_new_tokens=3, paged=False)
    assert ring.attn_path == "ring"


# ---------------------------------------------------------------------------
# multi-device parity (subprocess with 8 fake CPU devices)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax

from repro import configs
from repro.kernels import planning
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.runtime.engine import Request, ServingEngine

out = {}
P, G, R, SLOTS = 8, 4, 2, 2
arch = "h2o-danube-1.8b"
cfg = configs.get_reduced(arch)
key = jax.random.PRNGKey(0)
params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
toks = jax.random.randint(key, (R, P), 0, cfg.vocab_size)


def run_engine(mesh, attn_path, **kw):
    planning.PLAN_CACHE.clear()
    eng = ServingEngine(cfg, params, mesh=mesh, max_batch=SLOTS,
                        max_prompt_len=P, max_new_tokens=G, page_size=4,
                        attn_path=attn_path, **kw)
    reqs = [Request(rid=i, prompt=toks[i], max_new_tokens=G)
            for i in range(R)]
    return {str(k): v for k, v in sorted(eng.run(reqs).results.items())}


single_gather = run_engine(None, "gather")
single_fused = run_engine(None, "fused")
out["single/fused==gather"] = single_fused == single_gather
mesh = make_local_mesh(data=2, model=4)
# multi-query regimes on the mesh: 5-token prefill chunks + ngram verify
# (q_len=k+1) all forced through the fused kernel — greedy speculative
# decode is lossless, so tokens must still match plain single-device gather
sharded_fused = run_engine(mesh, "fused", prefill_chunk=5,
                           speculate="ngram", spec_k=2)
out["tp4xdp2/mq fused==single"] = sharded_fused == single_gather
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_fused_engine_parity():
    """Forced-fused decode on a TP=4 x DP=2 mesh (8 fake CPU devices) is
    token-identical to single-device gather decode."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out and all(out.values()), {k: v for k, v in out.items() if not v}
