"""Mesh-sharded serving engine: continuous batched decode over request slots.

The paper's deployment regime — decode GEMMs with small M and K ≫ N — only
materializes when a *serving loop* drives the kernels: a fixed pool of batch
slots, requests admitted and evicted per step, one jitted decode step over
the whole pool. This module provides that loop:

  :class:`Request`       — one generation request (prompt, budget, arrival).
  :class:`ServingEngine` — slot scheduler + compiled prefill/decode steps.
  :class:`ServeReport`   — per-request tokens/latency + per-step throughput.

Context is stored in a **paged, prefix-shared KV cache** by default
(``runtime/kvcache.py``): one physical block pool per layer, per-slot block
tables, and a ref-counted host-side allocator driven by the admit/evict
scheduler. Identical prompt prefixes across slots map to the same physical
blocks (chain-hash index) until the first divergent write copies them apart
— so B slots serving the same prompt hold ~1 slot's worth of pages. A
slot's logical window keeps the exact ring layout (token at ``pos %
cache_len``), which makes paged decode token-identical to the legacy ring
engine (``paged=False``), SWA/vision-prefix masking included.

Slot lifecycle (see docs/serving.md):

  admit   — a free slot takes the next arrived request; its pages are
            shared-or-allocated and its prompt prefills in **chunks of
            ``prefill_chunk`` tokens interleaved with decode steps** —
            the single prefill path for every family (recurrent carries
            and enc-dec cross-KV thread through the chunk step) — a long
            prompt never stalls decode for the already-running slots. A
            page-aligned prefix retained warm in the allocator (see
            ``warm_cache_mb``) re-admits with zero prefill steps.
  decode  — one ``serve_step`` over all ``max_batch`` slots; inactive
            slots' writes are redirected into the null block and their
            outputs ignored.
  evict   — a finished slot's blocks are dereferenced; blocks reaching
            refcount 0 get their pos tags wiped and return to the free
            pool.

On a mesh the steps are jitted with the shardings of ``runtime/steps.py``
(params TP/FSDP-sharded; pool pages replicated over DP with heads over TP,
block tables batch-sharded), and kernel plans are chosen shard-local.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.shapes import serve_cache_len, serve_num_pages
from repro.core.quant import (
    DEFAULT_KV_FORMAT, QuantizedTensor, get_kv_format,
)
from repro.kernels import planning
from repro.models import attention, layers
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.runtime import kvcache as kvc
from repro.runtime import metrics as rmetrics
from repro.runtime import sharding as shd
from repro.runtime import speculative as spec
from repro.runtime import steps as rsteps

__all__ = ["Request", "ServeReport", "ServingEngine", "StepEvents",
           "insert_slot", "reset_slot"]

_NO_SPAN = contextlib.nullcontext()
# counts a step tallies as it goes; admitted/finished come from its events
_TALLIES = ("decode_rows", "prefill_chunks", "prefill_tokens",
            "pages_allocated")
_PATH_CODE = {"ring": 0, "gather": 1, "fused": 2}


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.

    ``prompt`` is a 1-D int32 token array; ``max_new_tokens`` counts every
    generated token including the one produced by prefill. ``arrival_step``
    simulates request arrival: the scheduler won't admit the request before
    that decode step. Prefix/audio embeddings are per-request frontends
    ((vision_prefix, d) / (encoder_seq, d)); when the arch needs them and
    the request doesn't carry them, the engine substitutes zeros.

    ``deadline_s`` (client SLO, seconds from submission) and ``priority``
    (higher admits first) only shape *admission ordering*, and only under
    ``admission="priority"`` (the front door's mode) — the default FIFO
    scheduler, and therefore every existing :meth:`ServingEngine.run`
    caller, ignores both. Deadline *enforcement* (408 drops) lives in the
    front door's queue, before the engine ever sees the request.
    """

    rid: int
    prompt: Any
    max_new_tokens: int
    arrival_step: int = 0
    prefix_embeds: Any = None
    audio_embeds: Any = None
    deadline_s: Optional[float] = None
    priority: int = 0


@dataclasses.dataclass
class ServeReport:
    """What a :meth:`ServingEngine.run` (or a front-door session) produced."""

    results: Dict[int, List[int]]          # rid → generated token ids
    latencies: Dict[int, float]            # rid → admit→finish seconds
    steps: int = 0
    decode_tokens: int = 0                 # tokens EMITTED (accepted), not
                                           # positions scored — speculative
                                           # and baseline runs compare 1:1
    decode_s: float = 0.0
    prefill_s: float = 0.0
    warm_hits: int = 0                     # admits that adopted ≥1 warm page
    warm_misses: int = 0                   # admits that found none warm
    prefill_steps_saved: int = 0           # chunk steps avoided by shared /
                                           # warm prefix pages, summed
    step_records: List[dict] = dataclasses.field(default_factory=list)
    peak_pages: int = 0                    # paged: max live blocks seen
    proposed_tokens: int = 0               # speculative: drafts scored
    accepted_tokens: int = 0               # speculative: drafts accepted
    ttft: Dict[int, float] = dataclasses.field(default_factory=dict)
    # rid → admit→first-token seconds
    cancelled: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    # rid → tokens emitted before cancellation (waiting-queue cancels: [])
    admitted: int = 0                      # requests that reached a slot
    # front-door admission outcomes (the engine never counts these itself;
    # a 429/408 by definition never touched the engine)
    rejected_429: int = 0                  # queue-full rejections
    rejected_408: int = 0                  # expired-deadline drops
    peak_queue_depth: int = 0              # front-door queue high-water mark
    queue_wait: Dict[int, float] = dataclasses.field(default_factory=dict)
    # rid → seconds in the front-door queue before engine submission

    @property
    def tokens_per_s(self) -> float:
        """*Accepted* tokens per decode second (every counted token is a
        committed output token; rejected drafts cost time, not tokens)."""
        return self.decode_tokens / self.decode_s if self.decode_s else 0.0

    @property
    def acceptance_rate(self) -> float:
        return (self.accepted_tokens / self.proposed_tokens
                if self.proposed_tokens else 0.0)

    def latency_stats(self) -> Dict[str, float]:
        """Nearest-rank p50/p95/p99 (+ mean/max) over per-request
        admit→finish latency — the one percentile code path shared by the
        serve CLI, the front door and ``GET /metrics``."""
        return rmetrics.summarize(list(self.latencies.values()))

    def ttft_stats(self) -> Dict[str, float]:
        """Same summary over per-request time-to-first-token."""
        return rmetrics.summarize(list(self.ttft.values()))


@dataclasses.dataclass
class StepEvents:
    """What one :meth:`ServingEngine.step` did — the streaming contract.

    The front door turns ``emitted`` into SSE chunks (tokens flush to the
    client per engine step, not per run) and ``finished`` into stream
    terminations. ``worked`` is False when the engine had nothing resident
    (the step was a no-op and the step counter did not advance).
    """

    step: int
    emitted: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    finished: List[int] = dataclasses.field(default_factory=list)
    admitted: List[int] = dataclasses.field(default_factory=list)
    worked: bool = True


class _Slot:
    """Mutable per-slot scheduler record."""

    __slots__ = ("req", "tokens", "remaining", "pos_next", "t_admit",
                 "phase", "pf_stream", "pf_next", "pf_total", "pf_keys",
                 "prompt_ids")

    def __init__(self, req: Request, pos0: int, t_admit: float):
        self.req = req
        self.prompt_ids: Optional[List[int]] = None   # set when speculating
        self.tokens: List[int] = []
        self.remaining = req.max_new_tokens
        self.pos_next = pos0
        self.t_admit = t_admit
        self.phase = "prefill"          # "prefill" → "active"
        self.pf_stream = None           # (S_total, d) embedding stream
        self.pf_next = 0                # next prefill position
        self.pf_total = 0               # prompt + vision-prefix length
        self.pf_keys = ([], None)       # prefix-share keys to publish

    def emit_first(self, first_token: int) -> None:
        self.tokens.append(first_token)
        self.remaining -= 1
        self.phase = "active"


def insert_slot(state, rstate, slot: int):
    """Write a B=1 prefilled decode state into batch slot ``slot``.

    Every per-slot decode-state leaf is (L, B, ...) — ring KV caches,
    rwkv/ssm states, encoder cross-attention KV — so one rule covers all
    families. The whole slot row is overwritten, ring pos tags included: a
    reused slot can never see a stale entry from its previous occupant.
    (Paged pool leaves are not per-slot; the paged engine scatters into
    them via ``kvcache.scatter_ring`` instead.)
    """
    return jax.tree.map(
        lambda s, r: s.at[:, slot].set(r[:, 0].astype(s.dtype)),
        state, rstate)


def reset_slot(state, slot: int):
    """Evict ``slot`` (ring mode): wipe its KV ring tags so the row reads
    as empty. The paged engine's counterpart is block-level
    (``kvcache.reset_blocks`` on blocks whose refcount hits 0)."""
    def visit(leaf):
        if isinstance(leaf, attention.KVCache):
            return attention.cache_reset_slots(leaf, slot)
        return leaf

    return jax.tree.map(
        visit, state, is_leaf=lambda x: isinstance(x, attention.KVCache))


class ServingEngine:
    """Continuous-batching decode over ``max_batch`` request slots.

    ``paged=True`` (default) stores context in the paged, prefix-shared
    block pool; ``paged=False`` keeps the legacy per-slot ring caches
    (the reference the parity suite compares against). Paged mode always
    prefills in chunks — the one prefill path, every family: at most
    ``prefill_chunk`` (default 32) prompt tokens are processed per engine
    step, interleaved with decode; recurrent carries (rwkv/hybrid) and
    enc-dec cross-KV thread through the chunk step. ``kv_format`` selects
    the KV block storage (``kv_fp16`` passthrough or ``kv8_channel``
    per-head INT8 — paged mode only). ``warm_cache_mb`` budgets the
    allocator's warm prefix retention: fully-released page-aligned prefix
    chains stay resident (LRU by chain) up to that many MiB, and a
    returning prefix re-admits without recomputing its prefill.

    ``mesh=None`` runs single-device (plain ``jax.jit``); with a mesh the
    steps are jitted with explicit shardings and the kernel plans are
    chosen shard-local (see module docstring).

    ``spans`` (also settable as ``engine.spans``) is an optional
    :class:`~repro.runtime.metrics.SpanRecorder`: with one, every
    :meth:`step` records ``serve.step`` and its phases, with the work
    counted at each (see docs/serving.md, "Spans"); without, each span
    site costs one ``None`` check.
    """

    def __init__(self, cfg: ModelConfig, params, *, mesh=None,
                 max_batch: int = 8, max_prompt_len: int = 128,
                 max_new_tokens: int = 64, refine_plans: bool = False,
                 cache_len: Optional[int] = None, paged: bool = True,
                 page_size: int = 16, prefill_chunk: Optional[int] = None,
                 kv_format: Optional[str] = None,
                 num_pages: Optional[int] = None,
                 warm_cache_mb: float = 0.0,
                 speculate=None, spec_k: int = 4,
                 admission: str = "fifo",
                 attn_path: str = "auto",
                 spans: Optional[rmetrics.SpanRecorder] = None):
        self.mesh = mesh
        if admission not in ("fifo", "priority"):
            raise ValueError(f"admission must be 'fifo' or 'priority', "
                             f"got {admission!r}")
        self.admission = admission
        self.max_batch = int(max_batch)
        self.max_prompt_len = int(max_prompt_len)
        self.max_new_tokens = int(max_new_tokens)
        # rwkv holds no KV cache at all — "paged" degenerates to the ring
        # state (nothing to page); everything else pages by default
        self.paged = bool(paged) and cfg.family != "rwkv"
        self.page_size = int(page_size)
        self.kv_format = kv_format or DEFAULT_KV_FORMAT
        self._kvfmt = get_kv_format(self.kv_format)
        if self._kvfmt.quantized and not self.paged:
            if cfg.attn_free:
                raise ValueError(
                    f"kv_format {self.kv_format!r} does not apply to "
                    f"{cfg.family!r} archs — they hold no KV cache to "
                    f"quantize; use kv_fp16")
            raise ValueError(
                f"kv_format {self.kv_format!r} quantizes KV blocks, which "
                f"needs the paged cache (paged=True)")
        ps = self.page_size if self.paged else None
        if cache_len is None:
            self.cache_len = serve_cache_len(cfg, max_prompt_len,
                                             max_new_tokens, ps)
        else:
            self.cache_len = int(cache_len)
            if ps:
                self.cache_len = -(-self.cache_len // ps) * ps
        # chunked prefill is the single prefill path whenever the caller
        # asked for the paged engine — including rwkv, whose "paged" mode
        # degenerates to ring state but still streams its prompt in chunks
        self.chunked = bool(paged)
        # prefix pages can only be *skipped* when no recurrent carry must
        # consume every prompt token — carry families recompute each token
        self.share_prefix = self.paged and cfg.family not in T.CARRY_FAMILIES
        if self.paged:
            self.pages_slot = self.cache_len // self.page_size
            self.num_pages = int(
                num_pages if num_pages is not None
                else serve_num_pages(cfg, max_prompt_len, max_new_tokens,
                                     page_size=self.page_size,
                                     max_batch=self.max_batch))
            if self.num_pages < self.pages_slot + 1:
                raise ValueError(
                    f"num_pages={self.num_pages} cannot hold even one "
                    f"slot's window ({self.pages_slot} pages + the null "
                    f"block) — the admit gate would wait forever; size "
                    f"the pool with configs.shapes.serve_num_pages")
            # bytes one block occupies across every layer's pool leaves
            # (scales + pos tags included) — the warm LRU budget unit
            pool_abs = jax.eval_shape(
                lambda: kvc.init_pool(
                    self.num_pages, self.page_size, cfg.num_kv_heads,
                    cfg.head_dim, cfg.dtype, kv_format=self.kv_format))
            block_bytes = sum(
                l.size * jnp.dtype(l.dtype).itemsize
                for l in jax.tree_util.tree_leaves(pool_abs)
            ) // self.num_pages * cfg.num_layers
            warm_bytes = int(float(warm_cache_mb) * (1 << 20)) \
                if self.share_prefix else 0
            self.alloc = kvc.BlockAllocator(
                self.num_pages, self.page_size,
                warm_bytes=warm_bytes, block_bytes=block_bytes)
        else:
            self.pages_slot = 0
            self.num_pages = 0
            self.alloc = None
        self.prefill_chunk = max(
            1, min(int(prefill_chunk) if prefill_chunk is not None else 32,
                   self.cache_len))

        # decode-attention path: a costed plan decision, same shape as the
        # matmul planner — "auto" ranks ring/gather/fused on the engine's
        # true decode problem (gather on CPU hosts and on a mesh of several
        # TPU chips, whose GSPMD steps cannot hold a compiled kernel; fused
        # on one TPU chip for long contexts); a forced path is validated
        # against the engine mode (e.g. "fused" without the paged cache is
        # refused loudly)
        attn_problem = planning.AttentionProblem(
            B=self.max_batch, Hq=cfg.num_heads, Hkv=cfg.num_kv_heads,
            D=cfg.head_dim, cache_len=self.cache_len,
            page_size=self.page_size, window=cfg.sliding_window,
            kv_format=self.kv_format, paged=self.paged,
            backend=jax.default_backend(),
            act_bytes=jnp.dtype(cfg.dtype).itemsize,
            spmd=mesh is not None and mesh.size > 1)
        forced_path = None if attn_path == "auto" else attn_path
        attn_plan = planning.plan_attention(attn_problem, path=forced_path)
        self.attn_path = attn_plan.path
        self.kv_partitions = attn_plan.kv_partitions
        self.pages_per_block = attn_plan.pages_per_block
        # chunked prefill is a *different* attention problem than decode —
        # q_len = the prefill chunk, one slot per call — so it gets its
        # own costed plan (the multi-query fused kernel serves q_len > 1;
        # the gather/fused tradeoff is priced per regime, not copied from
        # the decode pick). A forced path forces every regime.
        if self.paged and self.chunked:
            pf_plan = planning.plan_attention(
                dataclasses.replace(attn_problem, B=1,
                                    q_len=self.prefill_chunk),
                path=forced_path)
            self.prefill_attn_path = pf_plan.path
            self.prefill_kv_partitions = pf_plan.kv_partitions
            self.prefill_pages_per_block = pf_plan.pages_per_block
        else:
            self.prefill_attn_path = self.attn_path
            self.prefill_kv_partitions = self.kv_partitions
            self.prefill_pages_per_block = self.pages_per_block

        self.spec_k = int(spec_k)
        self.proposer: Optional[spec.Proposer] = None
        if speculate is not None and speculate != "off":
            if isinstance(speculate, spec.Proposer):
                spec.validate_speculate(speculate.name, self.spec_k,
                                        cfg=cfg, paged=self.chunked)
                self.proposer = speculate
            else:
                spec.validate_speculate(str(speculate), self.spec_k,
                                        cfg=cfg, paged=self.chunked)
                self.proposer = spec.make_proposer(str(speculate),
                                                   target_cfg=cfg)
        # speculative verify: q_len = k+1 queries per slot, full batch —
        # same plan shape as prefill, at the verify step's true width
        if self.paged and self.proposer is not None:
            vf_plan = planning.plan_attention(
                dataclasses.replace(attn_problem, q_len=self.spec_k + 1),
                path=forced_path)
            self.verify_attn_path = vf_plan.path
            self.verify_kv_partitions = vf_plan.kv_partitions
            self.verify_pages_per_block = vf_plan.pages_per_block
        else:
            self.verify_attn_path = self.attn_path
            self.verify_kv_partitions = self.kv_partitions
            self.verify_pages_per_block = self.pages_per_block

        self.plans: Dict[str, planning.KernelPlan] = {}
        if (getattr(cfg, "w4a16_strategy", "auto") == "auto"
                and getattr(cfg, "w4a16_plan", None) is None
                and any(isinstance(l, QuantizedTensor)
                        for l in jax.tree_util.tree_leaves(
                            params,
                            is_leaf=lambda t: isinstance(t, QuantizedTensor)))):
            # pre-plan the decode-regime GEMMs on the shapes each rank will
            # execute; the per-layer decisions pin the trace-time lookups.
            # Speculative verify widens every decode GEMM to M = B*(k+1)
            # rows — plan at that true local shape, not the M=B decode one
            M = self.max_batch * (self.spec_k + 1) \
                if self.proposer is not None else self.max_batch
            self.plans = planning.plan_for_params(
                params, M=M, mesh=mesh, refine=refine_plans)
            cfg = dataclasses.replace(cfg, w4a16_plan=self.plans)
        self.cfg = cfg

        with self._ctx():
            if mesh is not None:
                pshard = shd.param_shardings(
                    jax.eval_shape(lambda: params), mesh)
                params = jax.device_put(params, pshard)
        self.params = params

        self._prefill_fns: Dict[tuple, Any] = {}
        # decode/chunk/verify steps compile per live-page bucket (None =
        # full table; gather path only — see _live_bucket), so the dicts
        # hold at most 1 + log2(pages_slot) variants each
        self._serve_fns: Dict[Optional[int], Any] = {}
        self._chunk_fns: Dict[Optional[int], Any] = {}
        self._verify_fns: Dict[Optional[int], Any] = {}
        self._embed_fn = None
        self._encode_fn = None
        # interleaved decode steps must not clobber the carries of slots
        # still mid-prefill — those step functions take an "active" mask
        self._needs_active = self.chunked and cfg.family in T.CARRY_FAMILIES
        self._tables = None          # (B, pages_slot) np.int32 block tables
        self._keys_cache: Dict[int, Any] = {}   # id(req) → prefix keys
        self._reserve: Dict[int, int] = {}      # slot → outstanding worst-
                                                # case future allocations
        self.last_state = None       # decode-state snapshot (tests/debug)

        # re-entrant stepper state (armed by start(); run() is a wrapper)
        self.metrics: Optional[rmetrics.MetricsRegistry] = None
        self.spans = spans
        # work of the current step, put on its serve.step span
        self._tally = dict.fromkeys(_TALLIES, 0)
        self.report: Optional[ServeReport] = None
        self._started = False
        self._waiting: collections.deque = collections.deque()
        self._slots: List[Optional[_Slot]] = []
        self._events: Optional[StepEvents] = None
        self._state = None
        self._state_dirty = False
        self._serve = None
        self._tok = self._pos = None
        self._step_no = 0

    # -- compiled steps ----------------------------------------------------

    def _ctx(self):
        return jax.set_mesh(self.mesh) if self.mesh is not None \
            else contextlib.nullcontext()

    def _span(self, name: str, rid: Optional[int] = None,
              step: Optional[int] = None):
        """A span of the recorder, or a no-op context (yielding None)
        when tracing is off."""
        rec = self.spans
        return _NO_SPAN if rec is None else rec.span(name, rid, step)

    def _prefill_inputs(self, req: Request):
        prompt = jnp.asarray(req.prompt, jnp.int32)[None]
        inputs = {"tokens": prompt}
        cfg = self.cfg
        if cfg.vision_prefix:
            inputs["prefix_embeds"] = self._prefix_embeds(req)[None]
        if cfg.family == "encdec":
            ae = req.audio_embeds
            if ae is None:
                ae = jnp.zeros((cfg.encoder_seq, cfg.d_model), cfg.dtype)
            inputs["audio_embeds"] = jnp.asarray(ae, cfg.dtype)[None]
        return inputs

    def _prefix_embeds(self, req: Request):
        pe = req.prefix_embeds
        if pe is None:
            pe = jnp.zeros((self.cfg.vision_prefix, self.cfg.d_model),
                           self.cfg.dtype)
        return jnp.asarray(pe, self.cfg.dtype)

    def _prefill_fn(self, inputs):
        key = tuple(sorted((k, v.shape) for k, v in inputs.items()))
        fn = self._prefill_fns.get(key)
        if fn is None:
            if self.mesh is None:
                fn = jax.jit(rsteps.make_prefill_step(self.cfg,
                                                      self.cache_len))
            else:
                fn = rsteps.jit_prefill_step(
                    self.cfg, self.mesh, self.cache_len,
                    jax.eval_shape(lambda: self.params),
                    jax.eval_shape(lambda: inputs))
            self._prefill_fns[key] = fn
        return fn

    def _init_state(self):
        if self.paged:
            return T.init_paged_state(
                self.cfg, self.max_batch, self.cache_len,
                page_size=self.page_size, num_blocks=self.num_pages,
                kv_format=self.kv_format)
        return T.init_decode_state(self.cfg, self.max_batch, self.cache_len)

    def _serve_inputs_abstract(self):
        inputs = {
            "state": jax.eval_shape(self._init_state),
            "tokens": jax.ShapeDtypeStruct((self.max_batch,), jnp.int32),
            "pos": jax.ShapeDtypeStruct((self.max_batch,), jnp.int32),
        }
        if self.paged:
            inputs["tables"] = jax.ShapeDtypeStruct(
                (self.max_batch, self.pages_slot), jnp.int32)
        if self._needs_active:
            inputs["active"] = jax.ShapeDtypeStruct((self.max_batch,),
                                                    jnp.bool_)
        return inputs

    def _live_bucket(self, hw: int) -> Optional[int]:
        """Live-page bucket for a gather step whose high-water mark is
        ``hw`` pages: halve the full table width while it stays a
        multiple of 2 covering ``hw``, so recompiles are bounded at
        log2(pages_slot) variants while a young batch stops paying the
        page-rounded ``cache_len`` gather. None = full table."""
        w = self.pages_slot
        hw = max(1, min(int(hw), w))
        while w % 2 == 0 and w // 2 >= hw:
            w //= 2
        return None if w >= self.pages_slot else w

    def _serve_step(self, live_pages: Optional[int] = None):
        fn = self._serve_fns.get(live_pages)
        if fn is None:
            kw = dict(cache_len=self.cache_len, kv_format=self.kv_format,
                      attn_path=self.attn_path,
                      kv_partitions=self.kv_partitions,
                      live_pages=live_pages)
            if self.mesh is None:
                fn = jax.jit(rsteps.make_serve_step(self.cfg, **kw))
            else:
                inputs_abs = self._serve_inputs_abstract()
                self._state_shardings = shd.decode_state_shardings(
                    inputs_abs["state"], self.cfg, self.mesh)
                fn = rsteps.jit_serve_step(
                    self.cfg, self.mesh,
                    jax.eval_shape(lambda: self.params), inputs_abs, **kw)
            self._serve_fns[live_pages] = fn
        return fn

    def _chunk_step(self, live_pages: Optional[int] = None):
        fn = self._chunk_fns.get(live_pages)
        if fn is None:
            C = self.prefill_chunk
            kw = dict(kv_format=self.kv_format,
                      attn_path=self.prefill_attn_path,
                      kv_partitions=self.prefill_kv_partitions,
                      live_pages=live_pages)
            if self.mesh is None:
                fn = jax.jit(
                    rsteps.make_prefill_chunk_step(
                        self.cfg, self.cache_len, **kw),
                    donate_argnums=(1,))
            else:
                inputs_abs = {
                    "state": jax.eval_shape(self._init_state),
                    "h": jax.ShapeDtypeStruct((1, C, self.cfg.d_model),
                                              self.cfg.dtype),
                    "positions": jax.ShapeDtypeStruct((1, C), jnp.int32),
                    "slot": jax.ShapeDtypeStruct((), jnp.int32),
                }  # "state" is split out as its own (donated) argument
                if self.paged:
                    inputs_abs["table"] = jax.ShapeDtypeStruct(
                        (1, self.pages_slot), jnp.int32)
                fn = rsteps.jit_prefill_chunk_step(
                    self.cfg, self.mesh, self.cache_len,
                    jax.eval_shape(lambda: self.params), inputs_abs, **kw)
            self._chunk_fns[live_pages] = fn
        return fn

    def _verify_step(self, live_pages: Optional[int] = None):
        """Compiled speculative-verify step: (B, spec_k+1) positions per
        call, replacing the plain decode step whenever a proposer is
        wired (a slot with no drafts just pads its row to one live
        position — byte-identical to plain decode for that slot)."""
        fn = self._verify_fns.get(live_pages)
        if fn is None:
            C = self.spec_k + 1
            kw = dict(kv_format=self.kv_format,
                      attn_path=self.verify_attn_path,
                      kv_partitions=self.verify_kv_partitions,
                      live_pages=live_pages)
            if self.mesh is None:
                fn = jax.jit(
                    rsteps.make_verify_step(self.cfg, self.cache_len,
                                            **kw),
                    donate_argnums=(1,))
            else:
                inputs_abs = {
                    "state": jax.eval_shape(self._init_state),
                    "tokens": jax.ShapeDtypeStruct((self.max_batch, C),
                                                   jnp.int32),
                    "positions": jax.ShapeDtypeStruct((self.max_batch, C),
                                                      jnp.int32),
                }
                if self.paged:
                    inputs_abs["tables"] = jax.ShapeDtypeStruct(
                        (self.max_batch, self.pages_slot), jnp.int32)
                self._state_shardings = shd.decode_state_shardings(
                    inputs_abs["state"], self.cfg, self.mesh)
                fn = rsteps.jit_verify_step(
                    self.cfg, self.mesh, self.cache_len,
                    jax.eval_shape(lambda: self.params), inputs_abs, **kw)
            self._verify_fns[live_pages] = fn
        return fn

    def _embed(self, tokens):
        if self._embed_fn is None:
            self._embed_fn = jax.jit(
                lambda p, t: layers.embed(p["embed"], t))
        return self._embed_fn(self.params, tokens)

    def _reset_carry(self, state, i: int):
        """Zero slot ``i``'s recurrent carry rows (wkv/shift/ssm …) before
        its chunked prefill starts streaming real tokens through them."""
        carry_names = ("wkv", "shift", "cm_shift", "ssm")
        cache = {k: (v.at[:, i].set(0) if k in carry_names else v)
                 for k, v in state["cache"].items()}
        return dict(state, cache=cache)

    def _insert_enc_kv(self, state, i: int, req: Request):
        """Run the audio encoder + per-layer cross K/V projections for
        ``req`` and write them into slot ``i``'s rows — the only
        whole-sequence work left outside the chunk step (it consumes the
        audio, not the prompt, so chunking does not apply)."""
        if self._encode_fn is None:
            self._encode_fn = jax.jit(
                lambda p, a: T.encode_cross_kv(p, self.cfg, a))
        ae = req.audio_embeds
        if ae is None:
            ae = jnp.zeros((self.cfg.encoder_seq, self.cfg.d_model),
                           self.cfg.dtype)
        ek, ev = self._encode_fn(self.params,
                                 jnp.asarray(ae, self.cfg.dtype)[None])
        sk, sv = state["enc_kv"]
        return dict(state, enc_kv=(
            sk.at[:, i].set(ek[:, 0].astype(sk.dtype)),
            sv.at[:, i].set(ev[:, 0].astype(sv.dtype))))

    def _apply_carry_selection(self, state, carries, sel):
        """Commit the verify step's carry checkpoints: for each row, write
        back checkpoint ``sel[b]`` — 0 restores the pre-verify carry
        (inactive rows), n commits the carry after n consumed positions
        (1 + accepted drafts). The verify step leaves the state's own
        carry leaves untouched, so this is the only writer."""
        idx = jnp.asarray(sel, jnp.int32)
        cache = dict(state["cache"])
        for name, stack in carries.items():
            ix = idx.reshape((1, -1, 1) + (1,) * (stack.ndim - 3))
            taken = jnp.take_along_axis(stack, ix, axis=2)[:, :, 0]
            cache[name] = taken.astype(cache[name].dtype)
        return dict(state, cache=cache)

    def _constrain_state(self, state):
        """Pin ``state`` back onto the decode-state shardings. The eager
        slot insert/reset/scatter ops re-commit leaves with whatever
        sharding propagation picked; the jitted steps' in_shardings refuse
        a committed mismatch, so re-place explicitly (a no-op when already
        placed right)."""
        if self.mesh is None:
            return state
        return jax.device_put(state, self._state_shardings)

    # -- paged block bookkeeping ------------------------------------------

    def _pool_map(self, state, fn):
        return jax.tree.map(
            lambda l: fn(l) if isinstance(l, kvc.PagedKVCache) else l,
            state, is_leaf=lambda x: isinstance(x, kvc.PagedKVCache))

    def _consume_reserve(self, i: int) -> None:
        self._reserve[i] = max(0, self._reserve.get(i, 0) - 1)

    def _drain_reclaimed(self, state):
        """Wipe the pos tags of blocks the allocator evicted from the warm
        set since the last drain. A warm block keeps real (published)
        content; once reclaimed it re-enters the free list and its stale
        tags would read as valid context for its next owner. Returns
        (state, device_dirty)."""
        if self.alloc is None:
            return state, False
        bids = self.alloc.take_reclaimed()
        if not bids:
            return state, False
        state = self._pool_map(
            state, lambda pool: kvc.reset_blocks(pool, bids))
        return state, True

    def _slot_alloc(self, i: int) -> int:
        """Allocate a block on slot ``i``'s behalf, consuming one unit of
        its admit-time reservation (see :meth:`_required_pages`)."""
        bid = self.alloc.alloc()
        self._consume_reserve(i)
        return bid

    def _ensure_pages(self, state, i: int, offsets, txn=None):
        """Make the pages covering logical ``offsets`` writable for slot
        ``i``: allocate unmapped pages, copy-on-write shared ones (the
        "first divergent write" of prefix sharing). Returns (state,
        device_dirty). With ``txn`` (a list), every reversible mapping
        change is recorded — ("alloc", page, bid) / ("cow", page,
        old_bid, new_bid) — so a speculative step whose drafts get
        rejected can hand the list to :meth:`_rollback_pages`."""
        tbl = self._tables[i]
        dirty = False
        for p in sorted({o // self.page_size for o in offsets}):
            bid = int(tbl[p])
            if bid < 0:
                tbl[p] = self._slot_alloc(i)
                self._tally["pages_allocated"] += 1
                if txn is not None:
                    txn.append(("alloc", p, int(tbl[p])))
            elif self.alloc.refcount(bid) > 1:
                new = self.alloc.cow(bid)
                self._consume_reserve(i)
                self._tally["pages_allocated"] += 1
                state = self._pool_map(
                    state, lambda pool: kvc.copy_blocks(pool, bid, new))
                tbl[p] = new
                dirty = True
                if txn is not None:
                    txn.append(("cow", p, bid, new))
            else:
                # exclusive owner writing in place: the block's published
                # prefix key (if any) no longer describes its bytes —
                # without this, a wrapped decode recycles its prompt pages
                # and a later identical prompt adopts destroyed content
                self.alloc.unpublish(bid)
        # allocation pressure above may have evicted warm blocks — wipe
        # their stale tags before this step's gather can see them
        state, d = self._drain_reclaimed(state)
        return state, dirty or d

    def _rollback_pages(self, state, i: int, txn, last_page: int):
        """Allocator-level rollback of a speculative step's page mappings
        beyond ``last_page`` (the page holding the last *accepted*
        position). Fresh allocations are unmapped and freed; CoW'd pages
        re-adopt the shared block (the copy is dropped before any
        divergent content was committed) — so a shared prefix is never
        left pointing at rejected-draft bytes, and in-place unpublishes
        are never re-published (their tags no longer describe the key).
        Entries at or below ``last_page`` stay: pos-tag masking keeps a
        kept page's stale tail invisible until the next window overwrites
        it. Returns (state, device_dirty)."""
        tbl = self._tables[i]
        freed = []
        for op in reversed(txn):
            if op[1] <= last_page:
                continue
            if op[0] == "alloc":
                _, p, bid = op
                tbl[p] = -1
                if self.alloc.decref(bid):
                    freed.append(bid)
            else:                               # ("cow", p, old, new)
                _, p, old, new = op
                self.alloc.incref(old)          # retake the shared ref
                tbl[p] = old
                if self.alloc.decref(new):
                    freed.append(new)
            self._reserve[i] = self._reserve.get(i, 0) + 1
        if freed:
            state = self._pool_map(
                state, lambda pool: kvc.reset_blocks(pool, freed))
            return state, True
        return state, False

    def _prefix_keys(self, req: Request):
        """(stream length, (full page keys, partial)) for ``req``, hashed
        once per request: the admit gate re-checks the queue head every
        step and admit itself needs the keys twice more — device_get'ing
        and SHA-chaining the prompt (and vision embeds) each time would
        put per-admit host latency on the serving path. Wrapping streams
        (longer than the logical window) share nothing: their offsets are
        no longer page-aligned prefix content."""
        cached = self._keys_cache.get(id(req))
        if cached is None:
            cfg = self.cfg
            if not self.share_prefix:
                # carry families compute every prompt token regardless, so
                # prefix pages are never skipped — don't pay the hashing
                S_total = len(req.prompt) + (cfg.vision_prefix or 0)
                cached = (S_total, ([], None))
                self._keys_cache[id(req)] = cached
                return cached
            pe = self._prefix_embeds(req) if cfg.vision_prefix else None
            units = kvc.position_units(req.prompt, pe)
            seed = b""
            if cfg.family == "encdec":
                # decoder K/V at every position depend on the audio via
                # cross-attention: identical prompts over different audio
                # must hash to different pages
                ae = req.audio_embeds
                if ae is None:
                    ae = jnp.zeros((cfg.encoder_seq, cfg.d_model), cfg.dtype)
                seed = np.asarray(
                    jax.device_get(jnp.asarray(ae, cfg.dtype))).tobytes()
            S_total = len(units)
            keys = kvc.page_keys(units, self.page_size, seed=seed) \
                if S_total <= self.cache_len else ([], None)
            cached = (S_total, keys)
            self._keys_cache[id(req)] = cached
        return cached

    def _try_share(self, i: int, keys) -> int:
        """Map slot ``i``'s page-aligned prompt prefix onto published
        blocks; returns how many leading positions are covered."""
        full_keys, partial = keys
        tbl = self._tables[i]
        shared = 0
        for pi, key in enumerate(full_keys):
            bid = self.alloc.lookup(key)
            if bid is None:
                return shared
            tbl[pi] = bid
            shared = (pi + 1) * self.page_size
        if partial is not None:
            key, fill = partial
            bid = self.alloc.lookup(key)
            if bid is not None:
                tbl[len(full_keys)] = bid
                shared = len(full_keys) * self.page_size + fill
        return shared

    def _publish_keys(self, i: int, slot: _Slot,
                      upto: Optional[int] = None) -> None:
        """Index slot ``i``'s prefix pages for sharing. ``upto`` (a prefill
        progress position) limits publication to *fully written* pages, so
        chunked prefill publishes incrementally — a concurrently admitted
        identical prompt adopts pages as its peer produces them."""
        full_keys, partial = slot.pf_keys
        tbl = self._tables[i]
        done = slot.pf_total if upto is None else upto
        for pi, key in enumerate(full_keys):
            if (pi + 1) * self.page_size <= done and tbl[pi] >= 0:
                self.alloc.publish(key, int(tbl[pi]))
        if partial is not None and done >= slot.pf_total \
                and tbl[len(full_keys)] >= 0:
            self.alloc.publish(partial[0], int(tbl[len(full_keys)]))

    def _share_ahead(self, i: int, slot: _Slot) -> None:
        """Adopt prefix pages published since this slot's admit (typically
        by a peer prefilling the same prompt a few chunks ahead): any
        not-yet-written page at the slot's prefill frontier whose key is
        now indexed maps to the shared block and its positions are
        skipped. At least the final position is always computed locally
        (it produces the first token's logits)."""
        full_keys, partial = slot.pf_keys
        if not full_keys and partial is None:
            return          # wrapping stream: sharing disabled, and the
                            # frontier offset may exceed the table length
        tbl = self._tables[i]
        ps = self.page_size
        while slot.pf_next < slot.pf_total - 1 and slot.pf_next % ps == 0:
            p = slot.pf_next // ps
            if tbl[p] >= 0:
                break
            if p < len(full_keys):
                bid = self.alloc.lookup(full_keys[p])
                if bid is None:
                    break
                tbl[p] = bid
                slot.pf_next = min((p + 1) * ps, slot.pf_total - 1)
            else:
                if partial is not None:
                    bid = self.alloc.lookup(partial[0])
                    if bid is not None:
                        tbl[p] = bid
                        slot.pf_next = min(p * ps + partial[1],
                                           slot.pf_total - 1)
                break

    def _required_pages(self, req: Request) -> int:
        """Worst-case new blocks this request may need over its lifetime
        (admit gate for under-provisioned pools). Shared prefix pages are
        discounted, minus one for a potential divergent-write copy — but
        only when decode cannot wrap the logical window: a wrapping decode
        may copy-on-write *every* shared page, so no discount applies."""
        if not self.paged:
            return 0
        S_total, (full_keys, partial) = self._prefix_keys(req)
        if S_total + req.max_new_tokens > self.cache_len:
            return self.pages_slot
        # count only *live* shared pages — warm pages are already counted
        # on the admit gate's supply side (pages_free + warm_pages), so
        # discounting them here would double-count and deadlock the gate
        shared = 0
        for key in full_keys:
            bid = self.alloc.peek(key)
            if bid is None or self.alloc.is_warm(bid):
                break
            shared += 1
        else:
            if partial is not None:
                bid = self.alloc.peek(partial[0])
                if bid is not None and not self.alloc.is_warm(bid):
                    shared += 1
        return self.pages_slot - max(0, shared - 1)

    def _evict_paged(self, state, i: int):
        self._reserve.pop(i, None)
        # decref may *retain* published prefix blocks warm instead of
        # freeing them (warm budget permitting) — those keep their bytes;
        # blocks the retention displaced land on the reclaimed list
        freed = [bid for bid in map(int, self._tables[i])
                 if bid >= 0 and self.alloc.decref(bid)]
        freed += self.alloc.take_reclaimed()
        self._tables[i] = -1
        if freed:
            state = self._pool_map(
                state, lambda pool: kvc.reset_blocks(pool, freed))
        return state, bool(freed)

    # -- admit paths -------------------------------------------------------

    def _flush_first_tokens(self, pending) -> None:
        """Emit the first token of every slot whose prefill completed this
        step. The prefill paths queue ``(slot, last-position logits)``
        rows here instead of argmax'ing one by one — one device-side
        argmax over the stacked rows and ONE host transfer replaces a
        per-slot sync chain."""
        if len(pending) == 1:
            slot, row = pending[0]
            slot.emit_first(int(jnp.argmax(row)))
            self._note_first(slot)
            self._cache_first_token(slot)
            return
        firsts = np.asarray(
            jnp.argmax(jnp.stack([row for _, row in pending]), axis=-1))
        for (slot, _), t in zip(pending, firsts):
            slot.emit_first(int(t))
            self._note_first(slot)
            self._cache_first_token(slot)

    def _cache_first_token(self, slot: _Slot) -> None:
        """Attach the freshly computed first token to the prompt's final
        chain key as allocator metadata: a later admit whose warm/live
        prefix covers the whole prompt can then skip prefill entirely —
        greedy decode makes the first token a pure function of the hashed
        prefix (prompt, vision embeds, audio seed)."""
        if not self.share_prefix:
            return
        fk = self._final_key(slot.pf_keys)
        if fk is not None and slot.tokens:
            self.alloc.set_meta(fk, int(slot.tokens[0]))

    def _note_first(self, slot: _Slot) -> None:
        """Record TTFT and queue the first token on the step's events."""
        rid = slot.req.rid
        ttft = time.perf_counter() - slot.t_admit
        if self.report is not None:
            self.report.ttft[rid] = ttft
        if self._events is not None:
            self._events.emitted.setdefault(rid, []).append(slot.tokens[-1])
        if self.metrics is not None:
            self.metrics.histogram(
                "engine_ttft_seconds",
                "admit to first token, per request").observe(ttft)

    def _final_key(self, keys) -> Optional[str]:
        """The chain key covering a prompt's *last* position — the key the
        first-token cache hangs off (a full match on it implies the whole
        prefix, vision embeds and audio seed included, matched)."""
        full_keys, partial = keys
        if partial is not None:
            return partial[0]
        return full_keys[-1] if full_keys else None

    def _admit_chunked(self, state, req: Request, i: int, t0: float,
                       pending):
        """Set up slot ``i`` for ``req`` on the chunked prefill path — the
        one admit path for every family. Returns (state, slot,
        device_dirty). The slot stays in the "prefill" phase (its chunks
        run inside the decode loop) unless the warm/live prefix covers the
        *whole* prompt and the allocator cached its first token — then the
        slot activates immediately with zero prefill steps."""
        if self.paged:
            self._reserve[i] = self._required_pages(req)
        S_total, keys = self._prefix_keys(req)
        self._keys_cache.pop(id(req), None)
        slot = _Slot(req, self.pos0(req), t0)
        slot.pf_total = S_total
        dirty = False
        shared = 0
        first_tok: Optional[int] = None
        if self.share_prefix:
            slot.pf_keys = keys
            warm_before = self.alloc.warm_pages
            shared = self._try_share(i, keys)
            warm_used = warm_before - self.alloc.warm_pages
            if self.alloc.warm_bytes > 0:
                if warm_used > 0:
                    self.report.warm_hits += 1
                else:
                    self.report.warm_misses += 1
                if self.metrics is not None:
                    self.metrics.counter(
                        "engine_warm_hits_total",
                        "admits that adopted warm prefix pages").inc(
                        1 if warm_used > 0 else 0)
                    self.metrics.counter(
                        "engine_warm_misses_total",
                        "admits that found no warm prefix pages").inc(
                        0 if warm_used > 0 else 1)
            if shared >= S_total:
                fk = self._final_key(keys)
                meta = self.alloc.meta(fk) if fk is not None else None
                if meta is not None:
                    first_tok = int(meta)
        C = self.prefill_chunk
        cold_steps = -(-S_total // C)
        if first_tok is not None:
            # full-coverage hit with a cached first token: nothing to
            # compute — the pool already holds every prompt position and
            # greedy decode from it is deterministic
            slot.pf_next = S_total
            saved = cold_steps
            slot.emit_first(first_tok)
            self._note_first(slot)
        else:
            # always compute at least the final position locally (it
            # produces the first token's logits)
            shared = min(shared, S_total - 1)
            saved = cold_steps - (-(-(S_total - shared) // C))
            emb = self._embed(jnp.asarray(req.prompt, jnp.int32)[None])[0]
            if self.cfg.vision_prefix:
                emb = jnp.concatenate(
                    [self._prefix_embeds(req), emb], axis=0)
            slot.pf_stream = emb
            slot.pf_next = shared
        if self.share_prefix:
            self.report.prefill_steps_saved += saved
            if self.metrics is not None:
                self.metrics.histogram(
                    "engine_prefill_steps_saved",
                    "chunk steps avoided per admit by shared or warm "
                    "prefix pages").observe(saved)
        if self.cfg.family in T.CARRY_FAMILIES:
            state = self._reset_carry(state, i)
            dirty = True
        if self.cfg.family == "encdec":
            state = self._insert_enc_kv(state, i, req)
            dirty = True
        return state, slot, dirty

    def _advance_prefill(self, state, i: int, slot: _Slot, pending):
        """Run one prefill chunk for slot ``i``; returns (state, number of
        prompt positions it computed)."""
        C = self.prefill_chunk
        if self.paged:
            self._share_ahead(i, slot)
        start, total = slot.pf_next, slot.pf_total
        end = min(start + C, total)
        if self.paged:
            offsets = {p % self.cache_len for p in range(start, end)}
            state, dirty = self._ensure_pages(state, i, offsets)
            if dirty and self.mesh is not None:
                state = self._constrain_state(state)
        seg = slot.pf_stream[start:end]
        n = end - start
        if n < C:
            pad = jnp.zeros((C - n, seg.shape[-1]), seg.dtype)
            seg = jnp.concatenate([seg, pad], axis=0)
        positions = np.full((C,), -1, np.int32)
        positions[:n] = np.arange(start, end, dtype=np.int32)
        inputs = {
            "h": seg[None],
            "positions": jnp.asarray(positions)[None],
            "slot": jnp.asarray(i, jnp.int32),
        }
        if self.paged:
            inputs["table"] = jnp.asarray(self._tables[i:i + 1])
        lp = None
        if self.paged and self.prefill_attn_path == "gather" \
                and start < self.cache_len:
            # gather only reads pool entries < start (the chunk itself is
            # the in-flight segment), so the live high-water mark is the
            # pages holding positions 0..start-1
            lp = self._live_bucket(max(1, -(-start // self.page_size)))
        res = self._chunk_step(lp)(self.params, state, inputs)
        state = res["state"]
        slot.pf_next = end
        if end == total:
            if self.paged:
                self._publish_keys(i, slot)
            pending.append((slot, res["logits"][0]))
        elif self.paged:
            self._publish_keys(i, slot, upto=end)
        return state, n

    # -- scheduler ---------------------------------------------------------

    def pos0(self, req: Request) -> int:
        """First decode position: prompt + vision prefix (prefill wrote
        exactly that many cache entries)."""
        return int(len(req.prompt)) + (self.cfg.vision_prefix or 0)

    def _validate(self, r: Request) -> None:
        if len(r.prompt) > self.max_prompt_len:
            raise ValueError(
                f"request {r.rid}: prompt length {len(r.prompt)} exceeds "
                f"engine max_prompt_len {self.max_prompt_len}")
        if r.max_new_tokens > self.max_new_tokens:
            raise ValueError(
                f"request {r.rid}: max_new_tokens {r.max_new_tokens} "
                f"exceeds engine budget {self.max_new_tokens}")
        if r.max_new_tokens < 1:
            raise ValueError(f"request {r.rid}: max_new_tokens must be "
                             f"at least 1 (prefill emits the first token)")

    # -- re-entrant stepper API (the front door drives these directly) ----

    def start(self) -> None:
        """Arm the stepper: fresh scheduler state, empty report, initial
        decode state. Compiled steps and kernel plans are engine-lifetime
        (cached on ``self``), so a second ``start()`` reuses them — only
        per-run state resets. :meth:`run` is a wrapper over
        start/submit/step; the front door calls these directly so it can
        interleave submissions, cancellations and token streaming between
        decode steps."""
        self._waiting = collections.deque()
        self._slots = [None] * self.max_batch
        self.report = ServeReport(results={}, latencies={})
        if self.paged:
            self._tables = np.full((self.max_batch, self.pages_slot),
                                   -1, np.int32)
            self._reserve.clear()
            # the device pool is about to be re-created zeroed — warm
            # blocks' bytes are gone, so their index entries must go too
            self.alloc.purge_warm()
            self.alloc.take_reclaimed()
        if self.proposer is not None:
            self.proposer.reset(self)
        with self._ctx():
            self._state = self._init_state()
            # warm the full-table step (live-page bucket variants compile
            # lazily on first use inside _step_body)
            self._serve = self._verify_step() if self.proposer is not None \
                else self._serve_step()
        self._state_dirty = True    # needs re-placing onto the serve
                                    # shardings (set after insert/reset)
        self._tok = np.zeros(self.max_batch, np.int32)
        self._pos = np.zeros(self.max_batch, np.int32)
        self._step_no = 0
        self._events = None
        self._started = True
        self._path_gauges()

    def submit(self, req: Request) -> None:
        """Queue ``req`` for admission (validated now, admitted by a later
        :meth:`step` when a slot and — paged — enough pages are free)."""
        if not self._started:
            raise RuntimeError("ServingEngine.submit() before start()")
        self._validate(req)
        self._waiting.append(req)

    def cancel(self, rid: int) -> bool:
        """Cancel request ``rid`` wherever it is: drop it from the waiting
        queue, or — mid-decode / mid-chunked-prefill — evict its slot and
        decref its pages (shared blocks stay with their peers; exclusive
        blocks get their tags wiped and return to the pool). Tokens emitted
        so far land in ``report.cancelled[rid]``; the request never shows
        up in ``report.results``. Returns False if ``rid`` is not resident
        (already finished, cancelled, or never submitted). Call between
        steps — the front door applies client disconnects exactly there."""
        if not self._started:
            return False
        for idx, r in enumerate(self._waiting):
            if r.rid == rid:
                del self._waiting[idx]
                self._keys_cache.pop(id(r), None)
                self.report.cancelled[rid] = []
                self._count_cancel()
                return True
        for i, s in enumerate(self._slots):
            if s is not None and s.req.rid == rid:
                self.report.cancelled[rid] = list(s.tokens)
                if self.paged:
                    self._state, d = self._evict_paged(self._state, i)
                else:
                    self._state, d = reset_slot(self._state, i), True
                self._state_dirty |= d
                if self.proposer is not None:
                    self.proposer.evict(self, i)
                self._slots[i] = None
                self._count_cancel()
                return True
        return False

    def _count_cancel(self) -> None:
        if self.metrics is not None:
            self.metrics.counter(
                "engine_cancelled_total",
                "requests cancelled while queued or resident").inc()

    def has_work(self) -> bool:
        """True while any request is waiting or resident in a slot."""
        return self._started and (bool(self._waiting)
                                  or any(s is not None
                                         for s in self._slots))

    def drain(self, *, verbose: bool = False) -> ServeReport:
        """Step until nothing is waiting or resident; returns the report."""
        while self.has_work():
            self.step(verbose=verbose)
        return self.report

    def _next_admissible(self) -> Optional[int]:
        """Waiting-queue index of the next request to admit, or None.

        FIFO gates on the queue head (strict submission order — the
        pre-stepper engine's behavior, byte-identical for ``run()``
        callers); "priority" picks the best *arrived* request by
        (priority desc, deadline asc, arrival, rid) — the front door's
        SLO-aware admission order.
        """
        w = self._waiting
        if not w:
            return None
        if self.admission == "fifo":
            return 0 if w[0].arrival_step <= self._step_no else None
        best = None
        for idx, r in enumerate(w):
            if r.arrival_step > self._step_no:
                continue
            key = (-(r.priority or 0),
                   r.deadline_s if r.deadline_s is not None else math.inf,
                   r.arrival_step, r.rid)
            if best is None or key < best[0]:
                best = (key, idx)
        return None if best is None else best[1]

    def _finish(self, state, i: int, slot: _Slot):
        report = self.report
        rid = slot.req.rid
        report.results[rid] = slot.tokens
        report.latencies[rid] = time.perf_counter() - slot.t_admit
        if self.paged:
            state, d = self._evict_paged(state, i)
        else:
            state, d = reset_slot(state, i), True
        if self.proposer is not None:
            self.proposer.evict(self, i)
        self._slots[i] = None
        if self._events is not None:
            self._events.finished.append(rid)
        if self.metrics is not None:
            self.metrics.histogram(
                "engine_e2e_seconds",
                "admit to finish, per request").observe(
                report.latencies[rid])
        return state, d

    def _sample_metrics(self, ev: StepEvents, decode_dt: float) -> None:
        """Per-step metrics sample (queue depth, residency, pages, rates)."""
        m = self.metrics
        if m is None:
            return
        m.counter("engine_steps_total", "scheduler steps executed").inc()
        n_tok = sum(len(v) for v in ev.emitted.values())
        if n_tok:
            m.counter("engine_tokens_total", "tokens emitted").inc(n_tok)
        if decode_dt > 0.0:
            m.histogram("engine_step_seconds",
                        "decode/verify wall time per step").observe(decode_dt)
            if n_tok:
                m.histogram("engine_token_seconds",
                            "decode wall time per emitted token").observe(
                    decode_dt / n_tok)
        m.gauge("engine_queue_depth",
                "requests waiting for a slot").set(len(self._waiting))
        m.gauge("engine_active_slots",
                "slots decoding or prefilling").set(
            sum(1 for s in self._slots if s is not None))
        if self.paged:
            m.gauge("engine_pages_in_use",
                    "live KV blocks").set(self.alloc.pages_in_use)
            m.gauge("engine_warm_pages",
                    "refcount-0 prefix blocks retained warm").set(
                self.alloc.warm_pages)
        m.counter(f"engine_attn_path_steps_{self.attn_path}",
                  "scheduler steps served by this attention path").inc()
        if self.proposer is not None and self.report is not None:
            m.gauge("engine_acceptance_rate",
                    "accepted/proposed draft tokens").set(
                self.report.acceptance_rate)

    def _path_gauges(self) -> None:
        """The planner's attention paths, fixed for the engine's life,
        surfaced on GET /metrics: 0=ring, 1=gather, 2=fused; beside each,
        the table pages one grid step of the fused kernel covers (1 on
        the XLA paths)."""
        m = self.metrics
        if m is None:
            return
        regimes = [("", "decode", self.attn_path, self.pages_per_block)]
        if self.chunked:
            regimes.append(("prefill_", "chunked-prefill",
                            self.prefill_attn_path,
                            self.prefill_pages_per_block))
        if self.proposer is not None:
            regimes.append(("verify_", "speculative-verify",
                            self.verify_attn_path,
                            self.verify_pages_per_block))
        for prefix, what, path, ppb in regimes:
            m.gauge(f"engine_{prefix}attn_path",
                    f"{what} attention path (0=ring 1=gather 2=fused)").set(
                _PATH_CODE.get(path, -1))
            m.gauge(f"engine_{prefix}attn_pages_per_block",
                    f"{what} attention: table pages per fused-kernel "
                    f"grid step").set(ppb)

    def step(self, *, verbose: bool = False) -> StepEvents:
        """One scheduler iteration: admit arrived requests into free slots,
        advance at most one prefill chunk per prefilling slot, run one
        batched decode (or speculative verify) step over the active slots,
        evict finished slots. Returns the step's :class:`StepEvents` so a
        caller can stream tokens per step; ``worked=False`` means nothing
        was resident and the step counter did not advance."""
        if not self._started:
            raise RuntimeError("ServingEngine.step() before start()")
        ev = StepEvents(step=self._step_no)
        if not self.has_work():
            ev.worked = False
            return ev
        self._events = ev
        self._tally = dict.fromkeys(_TALLIES, 0)
        try:
            with self._span("serve.step", step=self._step_no) as sp, \
                    self._ctx():
                decode_dt = self._step_body(ev, verbose)
                if sp is not None:
                    sp.counts.update(self._tally, admitted=len(ev.admitted),
                                     finished=len(ev.finished))
        finally:
            self._events = None
        self.report.steps = self._step_no
        self.last_state = self._state
        self._sample_metrics(ev, decode_dt)
        return ev

    def _step_body(self, ev: StepEvents, verbose: bool) -> float:
        report = self.report
        slots = self._slots
        proposer = self.proposer
        state = self._state
        state_dirty = self._state_dirty
        tok, pos = self._tok, self._pos
        step = self._step_no
        decode_dt = 0.0
        pending: List[Any] = []     # (slot, logits) rows awaiting
                                    # their batched first argmax
        # -- admit arrived requests into free slots ----------------
        admitted = 0
        for i in range(self.max_batch):
            idx = self._next_admissible()
            if idx is None:
                break
            if slots[i] is not None:
                continue
            cand = self._waiting[idx]
            if self.paged and (
                    self._required_pages(cand)
                    + sum(self._reserve.values())
                    > self.alloc.pages_free + self.alloc.warm_pages):
                break               # pool too full — wait for evicts
                                    # (warm pages count as supply: the
                                    # allocator reclaims them on demand)
            del self._waiting[idx]
            req = cand
            with self._span("serve.admit", req.rid):
                t0 = time.perf_counter()
                if self.chunked:
                    state, slot, d = self._admit_chunked(
                        state, req, i, t0, pending)
                    state_dirty |= d
                else:
                    inputs = self._prefill_inputs(req)
                    logits, rstate = self._prefill_fn(inputs)(
                        self.params, inputs)
                    state = insert_slot(state, rstate, i)
                    state_dirty = True
                    slot = _Slot(req, self.pos0(req), t0)
                    pending.append((slot, logits[0]))
                if proposer is not None:
                    slot.prompt_ids = [
                        int(t) for t in
                        np.asarray(req.prompt).reshape(-1)]
                    proposer.admit(self, i, slot)
                report.prefill_s += time.perf_counter() - t0
            report.admitted += 1
            slots[i] = slot
            ev.admitted.append(req.rid)
            admitted += 1
        if admitted and self.metrics is not None:
            self.metrics.counter(
                "engine_admitted_total",
                "requests admitted into a slot").inc(admitted)

        # -- advance chunked prefills ------------------------------
        # (pf_stream gates out warm full-hit slots, which activated
        # at admit with nothing left to compute)
        for i, s in enumerate(slots):
            if s is not None and s.phase == "prefill" \
                    and s.pf_stream is not None:
                with self._span("serve.prefill_chunk", s.req.rid) as sp:
                    t0 = time.perf_counter()
                    if state_dirty:
                        state = self._constrain_state(state)
                        state_dirty = False
                    state, n = self._advance_prefill(state, i, s, pending)
                    report.prefill_s += time.perf_counter() - t0
                    self._tally["prefill_chunks"] += 1
                    self._tally["prefill_tokens"] += n
                    if sp is not None:
                        sp.counts["tokens"] = n
        if pending:
            with self._span("serve.first_tokens") as sp:
                if sp is not None:
                    sp.counts["rows"] = len(pending)
                self._flush_first_tokens(pending)

        # -- settle freshly-activated slots ------------------------
        for i, s in enumerate(slots):
            if s is not None and s.phase == "active" and \
                    len(s.tokens) == 1 and s.remaining >= 0:
                if s.remaining == 0:
                    state, d = self._finish(state, i, s)
                    state_dirty |= d
                else:
                    tok[i], pos[i] = s.tokens[0], s.pos_next

        active = [i for i, s in enumerate(slots)
                  if s is not None and s.phase == "active"]
        if not active:
            self._state, self._state_dirty = state, state_dirty
            if self.has_work():
                self._step_no = step + 1
            return decode_dt

        self._tally["decode_rows"] = len(active)
        # -- speculative: propose → verify → accept → rollback -----
        # (drafting counts as building the verify step's inputs)
        if proposer is not None:
            k = self.spec_k
            C = k + 1
            n_drafts: Dict[int, int] = {}
            txns: Dict[int, list] = {}
            with self._span("serve.inputs"):
                views = [spec.ProposalView(
                    i, slots[i].prompt_ids + slots[i].tokens,
                    int(pos[i])) for i in active]
                t0 = time.perf_counter()
                proposals = proposer.propose(views, k)
                ptok = np.zeros((self.max_batch, C), np.int32)
                ppos = np.full((self.max_batch, C), -1, np.int32)
                for i in active:
                    s = slots[i]
                    props = list(proposals.get(i, []))[:k]
                    # clamp: (a) never emit past the request budget,
                    # (b) never let the draft overhang wrap the logical
                    # window — a wrapped speculative write would destroy
                    # a still-in-window entry, where plain decode only
                    # ever overwrites the exactly-expiring one
                    n = min(len(props), s.remaining - 1)
                    if int(pos[i]) + n >= self.cache_len:
                        n = max(0, self.cache_len - 1 - int(pos[i]))
                    n_drafts[i] = n
                    report.proposed_tokens += n
                    ptok[i, 0], ppos[i, 0] = tok[i], pos[i]
                    for j in range(n):
                        ptok[i, j + 1] = int(props[j])
                        ppos[i, j + 1] = int(pos[i]) + j + 1
                    txns[i] = []
            if self.paged:
                with self._span("serve.pages") as sp:
                    a0 = self._tally["pages_allocated"]
                    for i in active:
                        state, d = self._ensure_pages(
                            state, i,
                            [p % self.cache_len for p in
                             range(int(pos[i]),
                                   int(pos[i]) + n_drafts[i] + 1)],
                            txn=txns[i])
                        state_dirty |= d
                    if sp is not None:
                        sp.counts["allocated"] = \
                            self._tally["pages_allocated"] - a0
                report.peak_pages = max(report.peak_pages,
                                        self.alloc.pages_in_use)
            with self._span("serve.inputs"):
                if state_dirty:
                    state = self._constrain_state(state)
                    state_dirty = False
                vinputs = {
                    "tokens": jnp.asarray(ptok),
                    "positions": jnp.asarray(ppos),
                }
                if self.paged:
                    step_tables = self._tables.copy()
                    for i, s in enumerate(slots):
                        if s is None or s.phase != "active":
                            step_tables[i] = -1
                    vinputs["tables"] = jnp.asarray(step_tables)
                lp = None
                if self.paged and self.verify_attn_path == "gather":
                    mx = max(int(pos[i]) for i in active)
                    if mx + k < self.cache_len:
                        # gather reads pool entries < positions[:, 0]
                        # only (the k+1 in-flight rows are the segment),
                        # so the live high-water mark is
                        # ceil(max_pos / page_size)
                        lp = self._live_bucket(
                            max(1, -(-mx // self.page_size)))
            with self._span("serve.dispatch"):
                res = self._verify_step(lp)(self.params, state, vinputs)
                state = res["state"]
            with self._span("serve.readback"):
                nxt = np.asarray(res["next"])          # (B, C)
            dt = time.perf_counter() - t0
            report.decode_s += dt
            decode_dt = dt
            emitted_total = 0
            with self._span("serve.collect"):
                # exact greedy acceptance: draft j survives iff it equals
                # the target's own argmax at position j-1; the first
                # mismatch position contributes the target's choice as
                # the bonus token
                accepted: Dict[int, int] = {}
                for i in active:
                    a = 0
                    while a < n_drafts[i] and \
                            int(ptok[i, a + 1]) == int(nxt[i, a]):
                        a += 1
                    accepted[i] = a
                carries = res.get("carries")
                if carries is not None:
                    # recurrent families: commit each row's carry at its
                    # accepted frontier (checkpoint 1 + accepted consumed
                    # positions; 0 restores inactive rows untouched)
                    sel = np.zeros(self.max_batch, np.int32)
                    for i in active:
                        sel[i] = accepted[i] + 1
                    state = self._apply_carry_selection(state, carries,
                                                        sel)
                    state_dirty = True
                for i in active:
                    s = slots[i]
                    a = accepted[i]
                    emitted = [int(nxt[i, j]) for j in range(a + 1)]
                    report.accepted_tokens += a
                    if self.paged:
                        state, d = self._rollback_pages(
                            state, i, txns[i],
                            ((int(pos[i]) + a) % self.cache_len)
                            // self.page_size)
                        state_dirty |= d
                    emitted_total += len(emitted)
                    s.tokens.extend(emitted)
                    ev.emitted.setdefault(s.req.rid, []).extend(emitted)
                    s.remaining -= len(emitted)
                    s.pos_next += len(emitted)
                    tok[i], pos[i] = emitted[-1], s.pos_next
                    if s.remaining == 0:
                        state, d = self._finish(state, i, s)
                        state_dirty |= d
            report.decode_tokens += emitted_total
            report.step_records.append({
                "step": step, "active": len(active),
                "admitted": admitted, "decode_ms": dt * 1e3,
                "emitted": emitted_total})
            if verbose:
                print(f"[engine] step {step}: active={len(active)} "
                      f"emitted={emitted_total} {dt*1e3:.2f} ms")
            self._state, self._state_dirty = state, state_dirty
            self._step_no = step + 1
            return decode_dt

        # -- one batched decode step over every slot ---------------
        if self.paged:
            with self._span("serve.pages") as sp:
                a0 = self._tally["pages_allocated"]
                for i in active:
                    state, d = self._ensure_pages(
                        state, i, [int(pos[i]) % self.cache_len])
                    state_dirty |= d
                if sp is not None:
                    sp.counts["allocated"] = \
                        self._tally["pages_allocated"] - a0
            report.peak_pages = max(report.peak_pages,
                                    self.alloc.pages_in_use)
        with self._span("serve.inputs"):
            if state_dirty:
                # eager insert/reset/scatter ops re-committed leaves
                # off the serve shardings; steady-state steps skip this
                # (the serve output already carries its out_shardings)
                state = self._constrain_state(state)
                state_dirty = False
            t0 = time.perf_counter()
            inputs = {
                "state": state,
                "tokens": jnp.asarray(tok),
                "pos": jnp.asarray(pos),
            }
            if self._needs_active:
                # a decode step must not advance the recurrent carries of
                # rows that are free or still mid-chunked-prefill
                act = np.zeros(self.max_batch, bool)
                for i in active:
                    act[i] = True
                inputs["active"] = jnp.asarray(act)
            if self.paged:
                # non-active rows (free, or mid-chunked-prefill) are
                # masked to -1: their stale tok/pos writes redirect to
                # the null block instead of corrupting real pages (the
                # ring engine was immune — each slot owned its row)
                step_tables = self._tables.copy()
                for i, s in enumerate(slots):
                    if s is None or s.phase != "active":
                        step_tables[i] = -1
                inputs["tables"] = jnp.asarray(step_tables)
            lp = None
            if self.paged and self.attn_path == "gather":
                mx = max(int(pos[i]) for i in active)
                if mx < self.cache_len:
                    # insert-before-attend: the step writes position mx
                    # and reads entries <= mx, so the high water is
                    # ceil((mx+1)/ps)
                    lp = self._live_bucket(-(-(mx + 1) // self.page_size))
        with self._span("serve.dispatch"):
            res = self._serve_step(lp)(self.params, inputs)
            state = res["state"]
        with self._span("serve.readback"):
            nxt = np.asarray(res["next"])
        dt = time.perf_counter() - t0
        report.decode_s += dt
        decode_dt = dt
        report.decode_tokens += len(active)
        report.step_records.append({
            "step": step, "active": len(active),
            "admitted": admitted, "decode_ms": dt * 1e3})
        if verbose:
            print(f"[engine] step {step}: active={len(active)} "
                  f"admitted={admitted} {dt*1e3:.2f} ms")

        # -- collect tokens; evict finished slots ------------------
        with self._span("serve.collect"):
            for i in active:
                s = slots[i]
                s.tokens.append(int(nxt[i]))
                ev.emitted.setdefault(s.req.rid, []).append(int(nxt[i]))
                s.remaining -= 1
                s.pos_next += 1
                tok[i], pos[i] = nxt[i], s.pos_next
                if s.remaining == 0:
                    state, d = self._finish(state, i, s)
                    state_dirty |= d
        self._state, self._state_dirty = state, state_dirty
        self._step_no = step + 1
        return decode_dt

    def run(self, requests, *, verbose: bool = False) -> ServeReport:
        """Serve ``requests`` to completion; returns a :class:`ServeReport`.

        A thin wrapper over the stepper: validate everything up front,
        :meth:`start`, :meth:`submit` in (arrival, rid) order, then
        :meth:`drain` — continuous batching, not static batching: neither
        a long request nor (with chunked prefill) a long *prompt* blocks
        short requests from cycling through. Byte-identical to the
        pre-stepper engine for the same request set.
        """
        for r in requests:
            self._validate(r)
        self.start()
        for r in sorted(requests, key=lambda r: (r.arrival_step, r.rid)):
            self.submit(r)
        return self.drain(verbose=verbose)
