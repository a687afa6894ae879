"""Paged, prefix-shared KV cache: block pool + ref-counted allocator.

The decode step is memory-bandwidth-bound (the paper's K ≫ N regime caps at
1.48x because of weight bytes); at serving scale the KV cache is the other
tensor whose HBM footprint and traffic decide throughput. This module
replaces the per-slot contiguous ring caches with a **block pool**:

  device side  — :class:`PagedKVCache`: ``k_pool``/``v_pool`` of
                 ``num_blocks × Hkv × page_size × D`` (per layer; the model
                 stacks an L axis on top) plus per-slot ``page_pos`` tags
                 and optional ``kv8_channel`` scales. Gather/scatter run
                 through per-slot **block tables** ``(B, pages_per_slot)``.
  host side    — :class:`BlockAllocator`: ref-counted alloc/free driven by
                 the engine's admit/evict scheduler, with a chain-hash
                 prefix index so identical prompt prefixes across slots map
                 to the *same* physical blocks (copy-on-write at the first
                 divergent write).

Layout invariant (what makes paged decode token-identical to the ring):
a slot's logical window is ``cache_len`` entries (rounded up to a page
multiple — see ``configs.shapes.serve_cache_len``), and a token at absolute
position ``p`` lives at logical offset ``p % cache_len``, i.e. page
``offset // page_size`` slot ``offset % page_size`` of the slot's table.
Gathering a table therefore reconstructs *exactly* the ring buffer the
pre-paged engine kept per slot — same entries, same order, same pos-tag
masking — so ``attention.decode_attention`` runs unchanged on the gathered
window and SWA/vision-prefix semantics carry over verbatim.

Physical block 0 is reserved as the permanently-empty **null block**: table
entries of ``-1`` gather it (all ``pos`` tags ``-1`` → fully masked), and
writes from inactive slots are redirected into it with ``-1`` tags so they
can never materialize a valid entry.
"""
from __future__ import annotations

import collections
import hashlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant import (
    DEFAULT_KV_FORMAT, KVFormat, get_kv_format, kv_dequantize, kv_quantize,
)
from repro.models import attention

__all__ = [
    "PagedKVCache", "BlockAllocator", "NULL_BLOCK",
    "init_pool", "pages_per_slot", "paged_insert", "paged_decode_attention",
    "gather_window", "scatter_chunk", "scatter_chunks", "scatter_ring",
    "copy_blocks",
    "reset_blocks", "position_units", "page_keys",
]

NULL_BLOCK = 0


class PagedKVCache(NamedTuple):
    """Block-pool KV cache (one layer; the model stacks L in front).

    ``k_pool``/``v_pool``: (num_blocks, Hkv, page_size, D) — cache dtype
    for ``kv_fp16``, int8 for ``kv8_channel`` with per-(token, head) fp32
    scales in ``k_scale``/``v_scale`` (num_blocks, Hkv, page_size).
    ``page_pos``: (num_blocks, page_size) int32 absolute positions, -1 empty
    — the same validity tags ``attention.KVCache`` masks on.

    Heads sit outside the page's token axis so that one (block, head) tile
    is a whole ``(page_size, D)`` matrix: the fused kernel streams it
    through VMEM as a block whose last two dims are full axes, which the
    TPU's (8, 128) tiling rule requires.
    """

    k_pool: jax.Array
    v_pool: jax.Array
    page_pos: jax.Array
    k_scale: Optional[jax.Array] = None
    v_scale: Optional[jax.Array] = None

    @property
    def num_blocks(self) -> int:
        return self.page_pos.shape[-2]

    @property
    def page_size(self) -> int:
        return self.page_pos.shape[-1]


def init_pool(num_blocks: int, page_size: int, num_kv_heads: int,
              head_dim: int, dtype, kv_format: str = DEFAULT_KV_FORMAT
              ) -> PagedKVCache:
    """Fresh pool; block 0 is the null block (never allocated)."""
    fmt = get_kv_format(kv_format)
    shape = (num_blocks, num_kv_heads, page_size, head_dim)
    payload_dtype = jnp.int8 if fmt.quantized else dtype

    def scale():
        return jnp.zeros(shape[:-1], jnp.float32) if fmt.quantized else None

    return PagedKVCache(
        k_pool=jnp.zeros(shape, payload_dtype),
        v_pool=jnp.zeros(shape, payload_dtype),
        page_pos=jnp.full((num_blocks, page_size), -1, jnp.int32),
        k_scale=scale(),
        v_scale=scale(),
    )


def pages_per_slot(cache_len: int, page_size: int) -> int:
    if cache_len % page_size:
        raise ValueError(
            f"cache_len {cache_len} must be a page multiple (page_size "
            f"{page_size}); round it with configs.shapes.serve_cache_len")
    return cache_len // page_size


# ---------------------------------------------------------------------------
# device ops: gather / scatter through block tables
# ---------------------------------------------------------------------------

def gather_window(pool: PagedKVCache, tables: jax.Array, *,
                  fmt: KVFormat, out_dtype,
                  live_pages: Optional[int] = None) -> attention.KVCache:
    """Reassemble each slot's logical ring window from its block table.

    tables: (B, T) int32, -1 → null block. Returns a virtual
    :class:`attention.KVCache` (B, T*page_size, Hkv, D) in ``out_dtype`` —
    the exact array layout the ring cache kept, so ``decode_attention``'s
    pos-tag masking (and therefore SWA / vision-prefix semantics) applies
    unchanged.

    ``live_pages`` (static) clamps the gather to the leading that-many
    table entries: ring offsets fill pages front-to-back until the stream
    wraps, so a caller that knows the batch's live-page high-water mark
    (the engine tracks it per step) skips materializing the dead
    page-rounded tail of ``cache_len`` — the over-gather that made the
    fallback path look worse than it is early in every request's life.
    Masking is unchanged; callers must not clamp below the high-water
    mark (dropped pages would silently vanish from attention).
    """
    bt = jnp.where(tables < 0, NULL_BLOCK, tables)         # (B, T)
    if live_pages is not None:
        bt = bt[:, :max(1, min(int(live_pages), bt.shape[1]))]
    B, T = bt.shape
    ps = pool.page_size

    def take(leaf):               # (nb, Hkv, ps, ...) → (B, T*ps, Hkv, ...)
        g = jnp.swapaxes(jnp.take(leaf, bt.reshape(-1), axis=0), 1, 2)
        return g.reshape(B, T * ps, *g.shape[2:])

    pos = jnp.take(pool.page_pos, bt.reshape(-1), axis=0).reshape(B, T * ps)

    if not fmt.quantized:
        # passthrough formats store the cache dtype directly: no dequant
        # pass, and no scale pools to gather (they are None anyway)
        k = take(pool.k_pool)
        v = take(pool.v_pool)
        if k.dtype != jnp.dtype(out_dtype):
            k = k.astype(out_dtype)
            v = v.astype(out_dtype)
        return attention.KVCache(k=k, v=v, pos=pos)
    k = kv_dequantize(take(pool.k_pool),
                      None if pool.k_scale is None else take(pool.k_scale),
                      fmt, out_dtype)
    v = kv_dequantize(take(pool.v_pool),
                      None if pool.v_scale is None else take(pool.v_scale),
                      fmt, out_dtype)
    return attention.KVCache(k=k, v=v, pos=pos)


def _scatter(pool: PagedKVCache, flat_idx: jax.Array, k_new, v_new,
             pos_tag: jax.Array, fmt: KVFormat) -> PagedKVCache:
    """Write token vectors at flat pool slots (shared scatter core).

    flat_idx/pos_tag: (n,) with ``flat = block * page_size + offset``;
    k_new/v_new: (n, Hkv, D) in compute dtype.
    """
    bid, off = jnp.divmod(flat_idx, pool.page_size)
    kq, ks = kv_quantize(k_new, fmt)
    vq, vs = kv_quantize(v_new, fmt)

    def put(leaf, rows):          # leaf[bid, :, off] is (n, Hkv, ...)
        return leaf if rows is None else \
            leaf.at[bid, :, off].set(rows.astype(leaf.dtype))

    return PagedKVCache(
        k_pool=put(pool.k_pool, kq),
        v_pool=put(pool.v_pool, vq),
        page_pos=pool.page_pos.at[bid, off].set(pos_tag),
        k_scale=put(pool.k_scale, ks),
        v_scale=put(pool.v_scale, vs),
    )


def _write_target(tables: jax.Array, offset: jax.Array, page_size: int,
                  fallback: jax.Array):
    """Flat pool index for logical ``offset`` per row; rows whose table
    entry is unassigned (-1) redirect into the null block at ``fallback``
    (with the caller writing a -1 tag there, keeping it empty)."""
    page = offset // page_size
    bid = jnp.take_along_axis(tables, page[:, None], axis=1)[:, 0]
    ok = bid >= 0
    flat = jnp.where(ok, bid * page_size + offset % page_size,
                     fallback % page_size)
    return flat, ok


def paged_insert(pool: PagedKVCache, tables: jax.Array, k_new: jax.Array,
                 v_new: jax.Array, pos: jax.Array, *, cache_len: int,
                 fmt: KVFormat) -> PagedKVCache:
    """Decode-step insert: one token per slot at logical ``pos % cache_len``.

    k_new/v_new: (B, Hkv, D); pos: (B,). Slots with no block mapped for the
    target page (inactive slots) write a ``-1`` tag into the null block —
    a no-op for every reader.
    """
    B = k_new.shape[0]
    offset = (pos % cache_len).astype(jnp.int32)
    flat, ok = _write_target(tables, offset, pool.page_size,
                             jnp.arange(B, dtype=jnp.int32))
    tag = jnp.where(ok, pos.astype(jnp.int32), -1)
    return _scatter(pool, flat, k_new, v_new, tag, fmt)


def scatter_chunk(pool: PagedKVCache, table: jax.Array, k_chunk: jax.Array,
                  v_chunk: jax.Array, positions: jax.Array, *,
                  cache_len: int, fmt: KVFormat) -> PagedKVCache:
    """Chunked-prefill scatter: C tokens of one slot into its pages.

    k_chunk/v_chunk: (C, Hkv, D); positions: (C,) absolute, -1 = padding
    (padded tail of the last chunk). table: (T,). Requires C <= cache_len
    so logical offsets within one chunk are distinct.
    """
    C = positions.shape[0]
    safe = jnp.maximum(positions, 0)
    offset = (safe % cache_len).astype(jnp.int32)
    page = offset // pool.page_size
    bid = jnp.take(table, page)
    ok = (positions >= 0) & (bid >= 0)
    flat = jnp.where(ok, bid * pool.page_size + offset % pool.page_size,
                     jnp.arange(C, dtype=jnp.int32) % pool.page_size)
    tag = jnp.where(ok, positions.astype(jnp.int32), -1)
    return _scatter(pool, flat, k_chunk, v_chunk, tag, fmt)


def scatter_chunks(pool: PagedKVCache, tables: jax.Array,
                   k_chunk: jax.Array, v_chunk: jax.Array,
                   positions: jax.Array, *, cache_len: int,
                   fmt: KVFormat) -> PagedKVCache:
    """Batched :func:`scatter_chunk`: C tokens for each of B slots at once
    (the speculative-verify write path — every active slot lands its draft
    window in one scatter).

    k_chunk/v_chunk: (B, C, Hkv, D); positions: (B, C) absolute, -1 =
    padding (shorter-than-C proposals, inactive rows). tables: (B, T).
    Rows with ``-1`` positions or unmapped pages spread into distinct null
    block offsets with ``-1`` tags — never a valid entry, and (because
    each slot's writable pages are exclusively owned after the engine's
    CoW pass) never a cross-slot collision on a real page.
    """
    B, C = positions.shape
    safe = jnp.maximum(positions, 0)
    offset = (safe % cache_len).astype(jnp.int32)            # (B, C)
    page = offset // pool.page_size
    bid = jnp.take_along_axis(tables, page, axis=1)          # (B, C)
    ok = (positions >= 0) & (bid >= 0)
    flat = jnp.where(
        ok, bid * pool.page_size + offset % pool.page_size,
        jnp.arange(B * C, dtype=jnp.int32).reshape(B, C) % pool.page_size)
    tag = jnp.where(ok, positions.astype(jnp.int32), -1)
    Hkv, D = k_chunk.shape[-2:]
    return _scatter(pool, flat.reshape(-1),
                    k_chunk.reshape(B * C, Hkv, D),
                    v_chunk.reshape(B * C, Hkv, D),
                    tag.reshape(-1), fmt)


def scatter_ring(pool: PagedKVCache, table: np.ndarray,
                 ring: attention.KVCache, *, fmt: KVFormat) -> PagedKVCache:
    """Write a prefilled ring cache (one slot, B=1) into pool pages.

    The ring's slot index IS the logical offset (ring size == the slot's
    logical window), so ring slot ``j`` lands at page ``j // ps`` offset
    ``j % ps`` of ``table``. Used by the whole-prompt prefill fallback
    (recurrent / encoder-decoder families) and stacked over L by the
    engine; empty ring entries (pos -1) keep a -1 tag.
    """
    ps = pool.page_size
    W = ring.pos.shape[-1]
    bid = jnp.asarray(np.asarray(table, np.int32)[
        np.arange(W) // ps])                               # (W,)
    ok = bid >= 0
    within = jnp.arange(W, dtype=jnp.int32) % ps
    flat = jnp.where(ok, bid * ps + within, within)        # -1 → null block

    if ring.pos.ndim == 3:                                 # stacked (L, 1, W)
        kseq, vseq, ptag = ring.k[:, 0], ring.v[:, 0], ring.pos[:, 0]
        tag = jnp.where(ok[None], ptag.astype(jnp.int32), -1)

        def one_layer(pool_l, k_l, v_l, tag_l):
            return _scatter(pool_l, flat, k_l, v_l, tag_l, fmt)

        return jax.vmap(one_layer)(pool, kseq, vseq, tag)
    tag = jnp.where(ok, ring.pos[0].astype(jnp.int32), -1)
    return _scatter(pool, flat, ring.k[0], ring.v[0], tag, fmt)


def paged_decode_attention(q: jax.Array, pool: PagedKVCache,
                           tables: jax.Array, pos: jax.Array, *,
                           window: int = 0, fmt: KVFormat, out_dtype,
                           attn_path: str = "gather",
                           kv_partitions=None, live_pages=None,
                           interpret=None) -> jax.Array:
    """Decode attention over the paged pool, on the planned path.

    ``"gather"`` reassembles the slot windows to HBM and runs the
    unchanged ring-cache attention (same masking, same dots) — two passes
    over the KV working set; ``live_pages`` (static) clamps that gather
    to the batch's live-page high-water mark (see ``gather_window``).
    ``"fused"`` walks the block table inside the Pallas kernel
    (``kernels/paged_attention.py``): blocks of whole pages stream
    through VMEM, `kv8_channel` dequant and online softmax fuse into one
    pass, and the clamp is moot — an unwritten page costs its share of a
    block's DMAs and masked scores, not an HBM materialization. Both are
    token-identical;
    ``planning.plan_attention`` picks per backend (gather on CPU, fused
    on TPU for long contexts).
    """
    if attn_path == "fused":
        from repro.kernels.paged_attention import fused_paged_attention

        return fused_paged_attention(
            q, pool, tables, pos, window=window, fmt=fmt,
            out_dtype=out_dtype, kv_partitions=kv_partitions,
            interpret=interpret)
    if attn_path != "gather":
        raise ValueError(
            f"unknown attn_path {attn_path!r} for paged decode (expected "
            f"gather | fused; 'ring' is the non-paged engine's path)")
    cache = gather_window(pool, tables, fmt=fmt, out_dtype=out_dtype,
                          live_pages=live_pages)
    return attention.decode_attention(q, cache, pos, window=window)


def copy_blocks(pool: PagedKVCache, src: int, dst: int) -> PagedKVCache:
    """Copy-on-write: duplicate physical block ``src`` into ``dst``.

    Works on a per-layer pool or the layer-stacked one — the block axis is
    always ``page_pos.ndim - 2`` for every leaf family.
    """
    axis = pool.page_pos.ndim - 2

    def cp_leaf(leaf):
        idx_src = (slice(None),) * axis + (src,)
        idx_dst = (slice(None),) * axis + (dst,)
        return leaf.at[idx_dst].set(leaf[idx_src])

    return PagedKVCache(
        k_pool=cp_leaf(pool.k_pool),
        v_pool=cp_leaf(pool.v_pool),
        page_pos=cp_leaf(pool.page_pos),
        k_scale=None if pool.k_scale is None else cp_leaf(pool.k_scale),
        v_scale=None if pool.v_scale is None else cp_leaf(pool.v_scale),
    )


def reset_blocks(pool: PagedKVCache, blocks: Sequence[int]) -> PagedKVCache:
    """Wipe the pos tags of freed blocks (eviction hygiene, the paged
    counterpart of ``attention.cache_reset_slots``): stale K/V bytes stay
    but become unreachable, and a block re-entering the free pool can never
    leak a previous occupant's entries to its next owner."""
    idx = jnp.asarray(np.asarray(blocks, np.int32))
    axis = pool.page_pos.ndim - 2
    sl = (slice(None),) * axis + (idx,)
    return pool._replace(page_pos=pool.page_pos.at[sl].set(-1))


# ---------------------------------------------------------------------------
# host side: ref-counted block allocator + prefix-sharing index
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Ref-counted physical-block allocator with a prefix-sharing index and
    cross-request warm-prefix retention.

    Pure host-side bookkeeping: the engine's admit/evict scheduler drives
    alloc/free, and the chain-hash ``lookup``/``publish`` index maps
    page-aligned prompt-prefix content to physical blocks so identical
    prefixes across slots share pages (ref > 1) until the first divergent
    write copy-on-writes them apart (:meth:`cow`).

    With a nonzero ``warm_bytes`` budget, a *published* block whose
    refcount drops to 0 is not freed — it parks in a warm LRU (its index
    entry stays live), so a returning prompt re-adopts its prefix chain
    with zero prefill work. Warm blocks are reclaimed coldest-first when
    the budget overflows or the free list runs dry; reclaimed block ids
    accumulate in :meth:`take_reclaimed` so the engine can wipe their
    stale pos tags before reuse (warm blocks skip the decref-time wipe —
    their content IS the cache).
    """

    def __init__(self, num_blocks: int, page_size: int, *,
                 warm_bytes: int = 0, block_bytes: int = 1):
        if num_blocks < 2:
            raise ValueError("need at least 2 blocks (block 0 is the null "
                             "block)")
        self.num_blocks = int(num_blocks)
        self.page_size = int(page_size)
        self.warm_bytes = int(warm_bytes)
        self.block_bytes = max(1, int(block_bytes))
        self._free = collections.deque(range(1, num_blocks))
        self._ref: dict = {}          # bid -> refcount (live blocks only)
        self._index: dict = {}        # prefix key -> bid
        self._key_of: dict = {}       # bid -> prefix key
        self._meta: dict = {}         # prefix key -> cached payload
        self._warm = collections.OrderedDict()   # bid -> key, LRU order
        self._reclaimed: List[int] = []          # warm blocks freed, tags
                                                 # not yet wiped on device

    # -- capacity ---------------------------------------------------------
    @property
    def pages_in_use(self) -> int:
        return len(self._ref)

    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def warm_pages(self) -> int:
        return len(self._warm)

    @property
    def warm_bytes_used(self) -> int:
        return len(self._warm) * self.block_bytes

    def refcount(self, bid: int) -> int:
        return self._ref.get(bid, 0)

    def is_warm(self, bid: int) -> bool:
        return bid in self._warm

    # -- alloc / free -----------------------------------------------------
    def _drop_key(self, bid: int) -> None:
        key = self._key_of.pop(bid, None)
        if key is not None:
            self._index.pop(key, None)
            self._meta.pop(key, None)

    def _reclaim_warm(self) -> Optional[int]:
        """Free the coldest warm block; returns its id (or None)."""
        if not self._warm:
            return None
        bid, _key = self._warm.popitem(last=False)
        self._drop_key(bid)
        self._free.append(bid)
        self._reclaimed.append(bid)
        return bid

    def take_reclaimed(self) -> List[int]:
        """Warm blocks freed since the last call — the engine must wipe
        their pos tags (``reset_blocks``) before they are written again."""
        out, self._reclaimed = self._reclaimed, []
        return out

    def purge_warm(self) -> List[int]:
        """Drop every warm block back to the free list (run boundaries,
        property tests). Returns the purged block ids."""
        purged = []
        while self._warm:
            purged.append(self._reclaim_warm())
        return purged

    def alloc(self) -> int:
        if not self._free:
            self._reclaim_warm()
        if not self._free:
            raise RuntimeError(
                f"KV block pool exhausted ({self.num_blocks - 1} usable "
                f"blocks of {self.page_size} tokens, all referenced); size "
                f"the pool with configs.shapes.serve_num_pages or admit "
                f"fewer concurrent requests")
        bid = self._free.popleft()
        self._ref[bid] = 1
        return bid

    def incref(self, bid: int) -> None:
        self._ref[bid] += 1

    def decref(self, bid: int) -> bool:
        """Drop one reference; returns True when the block was freed (the
        caller must then wipe its tags via :func:`reset_blocks`). A
        published block under a nonzero warm budget is *retained* instead
        (returns False — its content stays adoptable); the coldest warm
        blocks are reclaimed if the byte budget would overflow."""
        self._ref[bid] -= 1
        if self._ref[bid]:
            return False
        del self._ref[bid]
        key = self._key_of.get(bid)
        if key is not None and self.warm_bytes >= self.block_bytes:
            while self.warm_bytes_used + self.block_bytes > self.warm_bytes:
                self._reclaim_warm()
            self._warm[bid] = key
            self._warm.move_to_end(bid)
            return False
        self._drop_key(bid)
        self._free.append(bid)
        return True

    def cow(self, bid: int) -> int:
        """Copy-on-write bookkeeping for a shared block the caller is about
        to write: allocate a private replacement (the caller device-copies
        the payload via :func:`copy_blocks`) and release the shared ref.
        The published prefix key stays with the *old* block, whose content
        still matches it."""
        if self.refcount(bid) < 2:
            raise ValueError(f"block {bid} is not shared (ref "
                             f"{self.refcount(bid)}); nothing to CoW")
        new = self.alloc()
        self.decref(bid)
        return new

    # -- prefix sharing ---------------------------------------------------
    def peek(self, key: str) -> Optional[int]:
        """Like :meth:`lookup` but without taking a reference (admit-gate
        capacity previews)."""
        return self._index.get(key)

    def lookup(self, key: str) -> Optional[int]:
        """Find a published block for ``key`` and take a reference on it.
        A warm (refcount-0, retained) block is adopted back to live."""
        bid = self._index.get(key)
        if bid is None:
            return None
        if bid in self._warm:
            del self._warm[bid]
            self._ref[bid] = 1
        else:
            self.incref(bid)
        return bid

    # -- first-token metadata --------------------------------------------
    def set_meta(self, key: str, value) -> None:
        """Attach a payload (the engine caches the first decoded token) to
        a *published* chain key; dropped whenever the key is."""
        if key in self._index:
            self._meta[key] = value

    def meta(self, key: str):
        return self._meta.get(key)

    def publish(self, key: str, bid: int) -> None:
        """Register ``bid``'s content under ``key`` (first writer wins; a
        block carries at most one key)."""
        if key in self._index or bid in self._key_of:
            return
        self._index[key] = bid
        self._key_of[bid] = key

    def unpublish(self, bid: int) -> None:
        """Drop ``bid``'s index entry because its content is about to be
        overwritten in place (a refcount-1 owner writing without CoW —
        e.g. a wrapped SWA decode recycling its own prompt pages). A
        published key must always describe the block's current bytes, or
        a later identical prompt would adopt destroyed content."""
        key = self._key_of.pop(bid, None)
        if key is not None:
            self._index.pop(key, None)
            self._meta.pop(key, None)


# ---------------------------------------------------------------------------
# prefix keys: chain hash over page-aligned prompt content
# ---------------------------------------------------------------------------

def position_units(tokens, prefix_embeds=None) -> List[bytes]:
    """One canonical byte string per prefill position.

    The prefill stream is ``[vision-prefix embeds] + prompt tokens`` —
    embeds hash by value so two requests share pages only when *both* the
    patches and the token prefix agree.
    """
    units: List[bytes] = []
    if prefix_embeds is not None:
        arr = np.asarray(jax.device_get(prefix_embeds))
        for row in arr.reshape(arr.shape[0], -1):
            units.append(b"E" + row.tobytes())
    for t in np.asarray(jax.device_get(tokens), np.int64).reshape(-1):
        units.append(b"T" + int(t).to_bytes(8, "little", signed=True))
    return units


def page_keys(units: Sequence[bytes], page_size: int, *,
              seed: bytes = b""
              ) -> Tuple[List[str], Optional[Tuple[str, int]]]:
    """Chain-hash keys for the page-aligned prefix of a prefill stream.

    Returns ``(full_page_keys, partial)``: one key per *full* page (key i
    commits to every position <= page i's end, so matching keys imply
    matching whole prefixes), plus ``(key, fill)`` for a trailing partial
    page when the stream doesn't end on a page boundary.

    ``seed`` folds request-level context that shapes *every* cached
    position into the chain — e.g. encoder-decoder audio frames, which
    feed each decoder layer's input through cross-attention, so two
    identical token prompts over different audio must never share pages.
    """
    h = hashlib.sha256()
    if seed:
        h.update(seed)
    full: List[str] = []
    partial = None
    n = len(units)
    for i, u in enumerate(units):
        h.update(len(u).to_bytes(4, "little"))
        h.update(u)
        if (i + 1) % page_size == 0:
            full.append(h.hexdigest())
    fill = n % page_size
    if fill:
        partial = (h.hexdigest() + f"+{fill}", fill)
    return full, partial
