"""Plan-based W4A16 matmul API: problem → plan → execute.

The paper's central finding is that W4A16 wins or loses on *dispatch
decisions* — Split-K degree, tile shapes, and whether the dequant
round-trips through global memory. This module makes those decisions
first-class objects instead of string branches and scattered kwargs:

  :class:`MatmulProblem`  — a hashable description of one GEMM
                            (shapes, dtypes, quantization format, backend).
  :class:`KernelPlan`     — a serializable dispatch decision
                            (strategy + split_k + tile shape).
  registry                — ``@register_strategy("name")`` makes a strategy
                            pluggable; the planner ranks whatever is
                            registered by its cost model, so adding a
                            backend never edits a dispatcher. Strategies
                            declare the :class:`~repro.core.quant.
                            QuantFormat` names they can execute
                            (``formats=`` fnmatch patterns); the planner
                            only considers matching strategies and a forced
                            strategy/format mismatch is refused loudly.
  :func:`plan_matmul`     — cost-model planner folding the Split-K
                            occupancy heuristic and the roofline models of
                            ``core/costmodel.py`` into one ranked decision,
                            memoized in a JSON-persistent plan cache.
  :func:`execute`         — run a plan on concrete operands.

Primary path (what every in-repo call site uses)::

    problem = MatmulProblem.from_operands(x, qt)
    y = execute(plan_matmul(problem), x, qt)

``ops.w4a16_matmul(x, qt, strategy=...)`` remains as a thin
backwards-compatible shim over this module. See docs/api.md.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import functools
import json
import math
import os
import tempfile
import threading
from typing import Any, Callable, Dict, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import costmodel
from repro.core.quant import (
    DEFAULT_FORMAT,
    DEFAULT_KV_FORMAT,
    QuantizedTensor,
    dequantize,
    get_kv_format,
    w4a8_matmul_ref,
    w4a16_format_for,
)
from repro.kernels import common, ref
from repro.kernels.w4a8_fused import w4a8_fused
from repro.kernels.w4a16_decoupled import w4a16_decoupled
from repro.kernels.w4a16_fused import w4a16_fused
from repro.kernels.w8a16_fused import w8a16_fused

__all__ = [
    "MatmulProblem", "KernelPlan", "Strategy",
    "register_strategy", "get_strategy", "available_strategies",
    "strategies_for_format",
    "plan_matmul", "resolve_plan", "execute", "shard_problem",
    "PlanCache", "PLAN_CACHE", "load_plan_cache", "save_plan_cache",
    "choose_split_k", "num_cores",
    "AttentionProblem", "AttentionPlan", "register_attn_path",
    "available_attn_paths", "plan_attention", "choose_kv_partitions",
    "choose_pages_per_block",
]


# ---------------------------------------------------------------------------
# Problem
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MatmulProblem:
    """One W4A16 GEMM: C[M, N] = A[M, K] · Dequant(W[K, N]).

    Hashable and order-insensitive — the plan cache and the planner key on
    this. ``batch`` counts independent GEMMs sharing the plan (vmapped
    expert stacks); ``M`` is rows per GEMM. ``format`` is the registered
    :class:`~repro.core.quant.QuantFormat` name, so plans cache per-format
    and the planner can filter strategies on the formats they support.
    ``spmd`` marks a GEMM inside one program that GSPMD partitions over
    several devices, where a compiled Pallas kernel cannot run.
    """

    M: int
    N: int
    K: int
    group_size: int = 128
    act_dtype: str = "bfloat16"
    out_dtype: str = "bfloat16"
    has_zeros: bool = False
    backend: str = "cpu"
    batch: int = 1
    format: str = DEFAULT_FORMAT
    spmd: bool = False

    @classmethod
    def from_operands(cls, x: jax.Array, qt: QuantizedTensor, *,
                      out_dtype=None, backend: Optional[str] = None,
                      batch: int = 1) -> "MatmulProblem":
        """Describe ``x @ Dequant(qt)``; x may have arbitrary leading dims."""
        K = x.shape[-1]
        M = math.prod(x.shape[:-1]) if x.ndim > 1 else 1
        return cls(
            M=int(M), N=int(qt.N), K=int(K),
            group_size=int(qt.group_size),
            act_dtype=str(jnp.dtype(x.dtype)),
            out_dtype=str(jnp.dtype(out_dtype or x.dtype)),
            has_zeros=qt.zeros is not None,
            backend=backend or jax.default_backend(),
            batch=batch,
            format=qt.format.name,
            spmd=spmd_traced(),
        )

    @property
    def layer_key(self) -> str:
        """Weight-shape key ("KxN") — one entry per model layer."""
        return f"{self.K}x{self.N}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "MatmulProblem":
        d = dict(d)
        if "format" not in d:
            # pre-format plan caches: every entry was the W4A16 family —
            # derive the format name the same way the legacy QuantizedTensor
            # constructor does, so old and new keys collide correctly
            try:
                d["format"] = w4a16_format_for(
                    int(d.get("group_size", 128)),
                    symmetric=not d.get("has_zeros", False)).name
            except (TypeError, ValueError):
                d["format"] = DEFAULT_FORMAT
        return cls(**d)


def spmd_traced() -> bool:
    """Whether the current trace is one program that GSPMD partitions over
    several devices: the ambient mesh has a non-manual axis of size > 1.
    JAX refuses to lower a compiled Pallas (Mosaic) kernel into such a
    program; inside ``shard_map`` the axes are manual and kernels run."""
    mesh = jax.sharding.get_abstract_mesh()
    return any(mesh.shape[a] > 1 for a in mesh.axis_names
               if a not in mesh.manual_axes)


def pallas_lowers(backend: str, spmd: bool) -> bool:
    """Whether a Pallas kernel can run in a problem's program: always in
    interpret mode (it lowers to plain HLO, which partitions), but a
    compiled TPU kernel only in a program of one device."""
    return not (spmd and backend == "tpu")


def _mesh_axis_size(mesh, name: str) -> int:
    """Axis size by name; 0 when absent (works on Mesh and spec-level fakes)."""
    try:
        return int(mesh.shape[name])
    except (KeyError, TypeError):
        return 0


def shard_problem(problem: MatmulProblem, mesh, kind: str) -> MatmulProblem:
    """The per-rank LOCAL GEMM of ``problem`` under tensor-parallel sharding.

    Megatron TP shrinks exactly one weight dim per rank: ``kind="row"``
    (wo / w_down / out_proj — input features sharded) divides K by the
    "model" axis, ``kind="col"`` (wq / w_up / lm_head — output features
    sharded) divides N; ``kind="rep"`` leaves the weight whole. Data-parallel
    axes divide the activation rows M for every kind. A dim that the mesh
    doesn't divide stays global — mirroring ``runtime/sharding.py``, which
    only shards divisible dims. A mesh of several devices marks the problem
    ``spmd``: the sharded steps are GSPMD programs.

    Dispatch decisions (Split-K degree, tiles, memory round-trips) must be
    costed on THESE shapes: row-parallel sharding moves each rank's GEMM
    deeper into the K ≫ N decode regime the paper's Split-K analysis targets,
    and a plan chosen for the global shape systematically under-splits.
    """
    if mesh is None:
        return problem
    model = _mesh_axis_size(mesh, "model")
    M, N, K = problem.M, problem.N, problem.K
    # greedy per-axis batch division, EXACTLY mirroring sharding.batch_spec:
    # a batch divisible by "pod" but not pod*data still shards (and shrinks)
    # over pod alone
    dp = 1
    for a in ("pod", "data"):
        sz = _mesh_axis_size(mesh, a)
        if sz > 1 and M % (dp * sz) == 0:
            dp *= sz
    M //= dp
    if model > 1:
        if kind == "col" and N % model == 0:
            N //= model
        elif kind == "row" and K % model == 0:
            K //= model
    spmd = problem.spmd or math.prod(mesh.shape.values()) > 1
    return dataclasses.replace(problem, M=max(M, 1), N=N, K=K, spmd=spmd)


# ---------------------------------------------------------------------------
# Plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """A dispatch decision: which strategy, how to split K, which tiles.

    ``out_dtype`` of None means "the activation dtype at execute time".
    JSON round-trips exactly (see to_json/from_json).
    """

    strategy: str
    split_k: int = 1
    block_m: int = 128
    block_n: int = 256
    block_k: int = 512
    out_dtype: Optional[str] = None

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "KernelPlan":
        return cls(**dict(d))

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), sort_keys=True)

    @classmethod
    def from_json(cls, s: str) -> "KernelPlan":
        return cls.from_dict(json.loads(s))


# ---------------------------------------------------------------------------
# Strategy registry
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Strategy:
    """A pluggable execution strategy.

    execute(x2, qt, plan, interpret=None) -> (M, N) array, x2 always 2-D.
    cost(problem, plan) -> estimated seconds (planner ranking).
    supports(problem) -> shape/dtype eligibility gate.
    formats -> fnmatch patterns over QuantFormat names this strategy can
    execute (e.g. ``("w4a16_*",)`` covers every group size / asym variant).
    """

    name: str
    execute: Callable[..., jax.Array]
    cost: Callable[[MatmulProblem, KernelPlan], float]
    supports: Callable[[MatmulProblem], bool]
    formats: Tuple[str, ...] = ("w4a16_*",)
    splittable: bool = False    # honors plan.split_k / tile refinement
                                # (the tiled Pallas kernels; XLA paths don't)

    def supports_format(self, format_name: str) -> bool:
        return any(fnmatch.fnmatchcase(format_name, pat)
                   for pat in self.formats)


_REGISTRY: Dict[str, Strategy] = {}


def register_strategy(name: str, *, cost=None, supports=None,
                      formats: Tuple[str, ...] = ("w4a16_*",),
                      splittable: bool = False):
    """Register an execute fn under ``name``; the planner picks it up with
    no dispatcher edits. ``cost`` defaults to +inf (never auto-chosen,
    still explicitly runnable); ``supports`` defaults to always-eligible;
    ``formats`` defaults to the W4A16 family — a strategy for another
    precision declares its own patterns (e.g. ``formats=("w4a8_*",)``).
    ``splittable=True`` tells the planner the strategy honors
    ``plan.split_k`` and tile refinement (the tiled Pallas kernels)."""

    def deco(fn):
        _REGISTRY[name] = Strategy(
            name=name,
            execute=fn,
            cost=cost or (lambda problem, plan: float("inf")),
            supports=supports or (lambda problem: True),
            formats=tuple(formats),
            splittable=splittable,
        )
        return fn

    return deco


def get_strategy(name: str) -> Strategy:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown strategy {name!r}; registered: {available_strategies()}"
        ) from None


def available_strategies() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def strategies_for_format(format_name: str) -> Tuple[str, ...]:
    """Names of registered strategies that can execute ``format_name``."""
    return tuple(s.name for s in _REGISTRY.values()
                 if s.supports_format(format_name))


# ---------------------------------------------------------------------------
# Split-K heuristic (paper Fig. 2) and core counting
# ---------------------------------------------------------------------------

def num_cores() -> int:
    """TensorCores one kernel's "parallel" grid axes spread over: those of
    one chip of the planning target (``common.target_spec``). A kernel
    runs on one chip, so other local chips never count — a v5e has one."""
    return common.target_spec().cores_per_chip


def choose_split_k(M: int, N: int, K: int, *, group_size: int = 128,
                   block_m: int = 128, block_n: int = 256) -> int:
    """Paper-informed Split-K heuristic: split when output tiles underfill
    the chip's cores (:func:`num_cores`) and K is deep (K ≫ N — decode
    GEMMs)."""
    if group_size <= 0 or K % group_size:
        return 1          # K-slices could not stay group-aligned
    cores = num_cores()
    m_tiles = max(1, -(-M // block_m))
    n_tiles = max(1, -(-N // block_n))
    tiles = m_tiles * n_tiles
    if tiles >= cores or K < 2 * group_size:
        return 1
    want = min(cores // tiles, K // group_size)
    s = 1
    while s * 2 <= want and K % (s * 2) == 0 and (K // (s * 2)) % group_size == 0:
        s *= 2
    return s


# ---------------------------------------------------------------------------
# Cost models (seconds; lower wins). Pallas strategies pay a large factor
# off-TPU: interpret mode executes the grid as a Python loop, so the
# planner must never auto-pick them on a CPU host.
# ---------------------------------------------------------------------------

_INTERPRET_PENALTY = 1e4


def _pallas_factor(problem: MatmulProblem) -> float:
    return 1.0 if problem.backend == "tpu" else _INTERPRET_PENALTY


def _cost_fused(problem: MatmulProblem, plan: KernelPlan) -> float:
    return (costmodel.w4a16_time_tpu_fused(
        problem.M, problem.N, problem.K, spec=common.target_spec())
        * problem.batch * _pallas_factor(problem))


def _cost_decoupled(problem: MatmulProblem, plan: KernelPlan) -> float:
    return (costmodel.w4a16_time_tpu_decoupled(
        problem.M, problem.N, problem.K, split_k=max(plan.split_k, 1),
        spec=common.target_spec())
        * problem.batch * _pallas_factor(problem))


def _cost_xla(problem: MatmulProblem, plan: KernelPlan) -> float:
    """Dequant materialized once by XLA (int4 read + float write) + GEMM."""
    M, N, K = problem.M, problem.N, problem.K
    spec = common.target_spec()
    t_deq = (0.5 * K * N + 2 * K * N) / spec.hbm_bw
    t_mm = max((2 * M * N * K) / spec.flops,
               (2 * M * K + 2 * K * N + 2 * M * N) / spec.hbm_bw)
    return (t_deq + t_mm) * problem.batch


def _cost_reference(problem: MatmulProblem, plan: KernelPlan) -> float:
    # same math as "xla" but without the loop-invariance barrier — XLA may
    # hoist the dequant and re-materialize the model in bf16; keep it as a
    # correctness oracle, never the planner's pick
    return _cost_xla(problem, plan) * 1.25


def _supports_pallas(problem: MatmulProblem) -> bool:
    # the kernels pad M and re-pick blocks, but K must be packable/grouped
    return (problem.group_size > 0 and problem.K % 2 == 0
            and problem.K % problem.group_size == 0
            and pallas_lowers(problem.backend, problem.spmd))


def _cost_w4a8(problem: MatmulProblem, plan: KernelPlan) -> float:
    """W4A8 reference path: int8 activation read (half the fp16 bytes),
    packed int4 weight read, int32 MACs at MXU rate — plus the (M, G, N)
    fp32 group-accumulator the XLA einsum formulation materializes, which
    is exactly what the fused Pallas kernel avoids."""
    M, N, K = problem.M, problem.N, problem.K
    spec = common.target_spec()
    g = max(problem.group_size, 1)
    bytes_moved = (M * K + 0.5 * K * N + 4.0 * K * N / g + 2 * M * N
                   + 8.0 * M * N * (K // g))        # write + read the acc
    t = max((2 * M * N * K) / spec.flops, bytes_moved / spec.hbm_bw)
    return t * problem.batch


def _cost_w8a16_fused(problem: MatmulProblem, plan: KernelPlan) -> float:
    return (costmodel.w8a16_time_tpu_fused(
        problem.M, problem.N, problem.K, spec=common.target_spec())
        * problem.batch * _pallas_factor(problem))


def _cost_w4a8_fused(problem: MatmulProblem, plan: KernelPlan) -> float:
    return (costmodel.w4a8_time_tpu_fused(
        problem.M, problem.N, problem.K, group=problem.group_size,
        spec=common.target_spec())
        * problem.batch * _pallas_factor(problem))


# ---------------------------------------------------------------------------
# Registered strategies. "decoupled" (the paper-faithful pipeline) plugs in
# through the same decorator as everything else — the acceptance demo that
# a strategy needs no dispatcher edits.
# ---------------------------------------------------------------------------

def _exec_out_dtype(plan: KernelPlan, x: jax.Array):
    return jnp.dtype(plan.out_dtype) if plan.out_dtype else x.dtype


_FLOAT_ACT_FORMATS = ("w4a16_*", "w8a16_*")   # anything dequantize handles


@register_strategy("reference", cost=_cost_reference,
                   formats=_FLOAT_ACT_FORMATS)
def _run_reference(x2, qt, plan, *, interpret=None):
    return ref.w4a16_ref(x2, qt, out_dtype=_exec_out_dtype(plan, x2))


def _pinned_qt(qt: QuantizedTensor) -> QuantizedTensor:
    """qt behind an optimization barrier: pins dequantization INSIDE the
    enclosing (layer) loop. Without it XLA's loop-invariant code motion
    hoists Dequant(W) for every scanned layer out of the decode loop and
    materializes the whole model in bf16 — silently undoing the 4× (or 2×)
    quantized-weight memory win."""
    pinned = jax.lax.optimization_barrier(
        (qt.packed, qt.scales) + (() if qt.zeros is None else (qt.zeros,)))
    zeros = pinned[2] if qt.zeros is not None else None
    return QuantizedTensor(pinned[0], pinned[1], zeros,
                           qt.group_size, qt.out_dtype, qt.format)


@register_strategy("xla", cost=_cost_xla, formats=_FLOAT_ACT_FORMATS)
def _run_xla(x2, qt, plan, *, interpret=None):
    w = dequantize(_pinned_qt(qt))
    return jnp.dot(
        x2.astype(w.dtype), w, preferred_element_type=jnp.float32
    ).astype(_exec_out_dtype(plan, x2))


@register_strategy("w4a8_xla", cost=_cost_w4a8, supports=_supports_pallas,
                   formats=("w4a8_*",))
def _run_w4a8_xla(x2, qt, plan, *, interpret=None):
    # dynamic per-token int8 activations × int4 weights, int32 group
    # accumulation (LiquidGEMM-style); barrier for the same reason as "xla"
    return w4a8_matmul_ref(x2, _pinned_qt(qt)).astype(
        _exec_out_dtype(plan, x2))


@register_strategy("fused", cost=_cost_fused, supports=_supports_pallas,
                   splittable=True)
def _run_fused(x2, qt, plan, *, interpret=None):
    return w4a16_fused(
        x2, qt, split_k=max(plan.split_k, 1),
        block_m=plan.block_m, block_n=plan.block_n, block_k=plan.block_k,
        out_dtype=_exec_out_dtype(plan, x2), interpret=interpret)


@register_strategy("decoupled", cost=_cost_decoupled,
                   supports=_supports_pallas, splittable=True)
def _run_decoupled(x2, qt, plan, *, interpret=None):
    return w4a16_decoupled(
        x2, qt, split_k=max(plan.split_k, 1),
        block_m=plan.block_m, block_n=plan.block_n, block_k=plan.block_k,
        out_dtype=_exec_out_dtype(plan, x2), interpret=interpret)


def _supports_w8a16_pallas(problem: MatmulProblem) -> bool:
    # per-channel (or per-tensor) scales: one scale row spans all of K;
    # int8 rows have no packing constraint on K
    return (problem.group_size >= problem.K > 0
            and pallas_lowers(problem.backend, problem.spmd))


@register_strategy("w8a16_fused", cost=_cost_w8a16_fused,
                   supports=_supports_w8a16_pallas,
                   formats=("w8a16_channel*",), splittable=True)
def _run_w8a16_fused(x2, qt, plan, *, interpret=None):
    return w8a16_fused(
        x2, qt, split_k=max(plan.split_k, 1),
        block_m=plan.block_m, block_n=plan.block_n, block_k=plan.block_k,
        out_dtype=_exec_out_dtype(plan, x2), interpret=interpret)


@register_strategy("w4a8_fused", cost=_cost_w4a8_fused,
                   supports=_supports_pallas, formats=("w4a8_*",),
                   splittable=True)
def _run_w4a8_fused(x2, qt, plan, *, interpret=None):
    return w4a8_fused(
        x2, qt, split_k=max(plan.split_k, 1),
        block_m=plan.block_m, block_n=plan.block_n, block_k=plan.block_k,
        out_dtype=_exec_out_dtype(plan, x2), interpret=interpret)


# ---------------------------------------------------------------------------
# Plan cache (process-wide, JSON-persistent)
# ---------------------------------------------------------------------------

class PlanCache:
    """Problem → plan memo with hit/miss stats and JSON persistence.

    Only planner-chosen (strategy-unforced) plans are cached; forced or
    overridden plans are cheap to rebuild and would poison lookups.
    """

    _VERSION = 1

    def __init__(self) -> None:
        self._plans: Dict[MatmulProblem, KernelPlan] = {}
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._plans)

    def get(self, problem: MatmulProblem) -> Optional[KernelPlan]:
        with self._lock:
            plan = self._plans.get(problem)
            if plan is None:
                self.misses += 1
            else:
                self.hits += 1
            return plan

    def put(self, problem: MatmulProblem, plan: KernelPlan) -> None:
        with self._lock:
            self._plans[problem] = plan

    def clear(self) -> None:
        with self._lock:
            self._plans.clear()
            self.hits = self.misses = 0

    def save(self, path: str) -> int:
        """Persist every cached decision; returns the entry count.

        The write is atomic (tmp file + ``os.replace``): a crash mid-save
        can never truncate a shared plan-cache file that other runs
        warm-start from — they see either the old or the new contents.
        """
        with self._lock:
            entries = [{"problem": prob.to_dict(), "plan": plan.to_dict()}
                       for prob, plan in self._plans.items()]
        blob = json.dumps({"version": self._VERSION, "plans": entries},
                          indent=1, sort_keys=True)
        d = os.path.dirname(os.path.abspath(path)) or "."
        fd, tmp = tempfile.mkstemp(
            dir=d, prefix=os.path.basename(path) + ".", suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                f.write(blob)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)
            except OSError:
                pass
            raise
        return len(entries)

    def load(self, path: str, *, merge: bool = True) -> int:
        """Load persisted decisions (merging over the current contents by
        default); returns the number of entries loaded. Any malformed
        content raises ValueError (never TypeError/AttributeError), so
        callers can guard with one exception type."""
        with open(path) as f:
            blob = json.load(f)      # JSONDecodeError is a ValueError
        try:
            if blob.get("version") != self._VERSION:
                raise ValueError(
                    f"unsupported plan-cache version in {path}: "
                    f"{blob.get('version')!r}")
            loaded = {MatmulProblem.from_dict(e["problem"]):
                      KernelPlan.from_dict(e["plan"]) for e in blob["plans"]}
        except (TypeError, AttributeError, KeyError) as e:
            raise ValueError(f"malformed plan cache {path}: {e}") from e
        # a cache written by a build with extra strategies must not smuggle
        # un-executable plans past tolerant loading: keep only entries this
        # process can actually dispatch
        loaded = {prob: plan for prob, plan in loaded.items()
                  if plan.strategy in _REGISTRY}
        with self._lock:
            if not merge:
                self._plans.clear()
            self._plans.update(loaded)
        return len(loaded)


PLAN_CACHE = PlanCache()


def load_plan_cache(path: str, *, merge: bool = True,
                    tolerant: bool = False) -> int:
    """Load ``path`` into the process cache. With ``tolerant=True`` a
    missing or unreadable file is a no-op returning -1 — launchers warm-
    starting from an optional cache must never die on a stale file."""
    try:
        return PLAN_CACHE.load(path, merge=merge)
    except (OSError, ValueError):
        if tolerant:
            return -1
        raise


def save_plan_cache(path: str) -> int:
    return PLAN_CACHE.save(path)


# ---------------------------------------------------------------------------
# Planner
# ---------------------------------------------------------------------------

def _default_plan(problem: MatmulProblem, strategy: str,
                  refine: bool) -> KernelPlan:
    """Heuristic (or refined) plan parameters for one strategy."""
    split_k = 1
    block_m, block_n, block_k = 128, 256, 512
    if get_strategy(strategy).splittable:
        split_k = choose_split_k(problem.M, problem.N, problem.K,
                                 group_size=problem.group_size)
        if refine:
            # the former autotune.py search, now the planner's optional
            # measurement/refinement pass: rank tile candidates under the
            # VMEM budget with the v5e roofline
            from repro.kernels.autotune import autotune_w4a16

            block_m, block_n, block_k, split_k = autotune_w4a16(
                problem.M, problem.N, problem.K, group=problem.group_size)
    return KernelPlan(strategy=strategy, split_k=split_k, block_m=block_m,
                      block_n=block_n, block_k=block_k,
                      out_dtype=problem.out_dtype)


def plan_matmul(problem: MatmulProblem, *, strategy: Optional[str] = None,
                refine: bool = False, use_cache: bool = True,
                cache: Optional[PlanCache] = None) -> KernelPlan:
    """Choose a :class:`KernelPlan` for ``problem``.

    With ``strategy=None`` every registered strategy that supports the
    problem's quantization format (and shape) is ranked by its cost model
    and the cheapest wins; the decision is memoized in the plan cache
    (process-wide, JSON-persistable). A named ``strategy`` forces the
    choice — but a strategy/format pair the strategy doesn't declare
    support for is refused with a ValueError, not silently mis-executed.
    ``refine=True`` additionally runs the tile-search refinement
    (ex-autotune) for Pallas strategies.
    """
    if strategy is not None:
        strat = get_strategy(strategy)
        if not strat.supports_format(problem.format):
            eligible = list(strategies_for_format(problem.format)) or (
                "none — register one with "
                "@register_strategy(..., formats=...)")
            raise ValueError(
                f"strategy {strat.name!r} does not support quantization "
                f"format {problem.format!r} (it supports formats matching "
                f"{list(strat.formats)}); strategies that do: {eligible}")
        return _default_plan(problem, strat.name, refine)

    cache = cache if cache is not None else PLAN_CACHE
    if use_cache and not refine:
        # a refine request must reach the tile search even when a heuristic
        # plan is already cached; the refined plan then overwrites it
        hit = cache.get(problem)
        if hit is not None:
            return hit

    best: Optional[Tuple[float, int, KernelPlan]] = None
    for order, strat in enumerate(_REGISTRY.values()):
        if not strat.supports_format(problem.format) \
                or not strat.supports(problem):
            continue
        plan = _default_plan(problem, strat.name, refine)
        score = strat.cost(problem, plan)
        if best is None or (score, order) < (best[0], best[1]):
            best = (score, order, plan)
    if best is None:
        # the W4A16 family always has the unconditional "reference" oracle,
        # so reaching here means every strategy for this format rejected
        # the shape (or none exists) — refuse loudly rather than return a
        # plan that would crash at execute time
        candidates = strategies_for_format(problem.format)
        if candidates:
            raise ValueError(
                f"no strategy supporting format {problem.format!r} can "
                f"execute this problem shape (M={problem.M}, N={problem.N}, "
                f"K={problem.K}, group_size={problem.group_size}); "
                f"{list(candidates)} rejected it — for packed-int4 formats "
                f"K must be even and divisible by the group size")
        raise ValueError(
            f"no registered strategy supports quantization format "
            f"{problem.format!r} (strategies: "
            f"{list(available_strategies())}); register one with "
            f"@register_strategy(..., formats=({problem.format!r},))")
    plan = best[2]
    if use_cache:
        cache.put(problem, plan)
    return plan


def resolve_plan(problem: MatmulProblem, cfg=None) -> KernelPlan:
    """Plan for a model-layer matmul, honoring config overrides.

    ``cfg.w4a16_plan`` may be a :class:`KernelPlan` (applies to every
    quantized layer), a mapping from layer key ``"KxN"`` to a plan/dict
    (per-layer override), or None. Otherwise ``cfg.w4a16_strategy`` forces
    the strategy ("auto" defers fully to the planner).
    """
    override = getattr(cfg, "w4a16_plan", None) if cfg is not None else None
    if override is not None:
        if isinstance(override, KernelPlan):
            return override
        if isinstance(override, Mapping):
            hit = override.get(problem.layer_key)
            if hit is not None:
                return hit if isinstance(hit, KernelPlan) \
                    else KernelPlan.from_dict(hit)
        elif isinstance(override, str):
            return KernelPlan.from_json(override)
    strategy = getattr(cfg, "w4a16_strategy", "auto") if cfg is not None \
        else "auto"
    if strategy and strategy != "auto":
        return plan_matmul(problem, strategy=strategy)
    return plan_matmul(problem)


# ---------------------------------------------------------------------------
# Execution
# ---------------------------------------------------------------------------

def execute(plan: KernelPlan, x: jax.Array, qt: QuantizedTensor, *,
            interpret=None) -> jax.Array:
    """Run a planned quantized matmul: x (..., K) → (..., N)."""
    strat = get_strategy(plan.strategy)
    if not strat.supports_format(qt.format.name):
        raise ValueError(
            f"plan strategy {plan.strategy!r} cannot execute a "
            f"{qt.format.name!r} tensor (it supports formats matching "
            f"{list(strat.formats)}); re-plan with a problem built via "
            f"MatmulProblem.from_operands, or force one of "
            f"{list(strategies_for_format(qt.format.name))}")
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = strat.execute(x2, qt, plan, interpret=interpret)
    return out.reshape(*lead, qt.N)


def matmul(x: jax.Array, qt: QuantizedTensor, *, cfg=None,
           interpret=None) -> jax.Array:
    """One-call convenience over the primary path (plan cache included)."""
    problem = MatmulProblem.from_operands(x, qt)
    return execute(resolve_plan(problem, cfg), x, qt, interpret=interpret)


def plan_for_params(params, M: int, *, refine: bool = False,
                    backend: Optional[str] = None,
                    mesh=None) -> Dict[str, KernelPlan]:
    """Pre-plan every quantized layer GEMM in a param pytree for ``M`` rows.

    Returns ``{layer_key ("KxN"): plan}``; every decision lands in the
    process plan cache, so subsequent layer-time lookups (same M/dtypes)
    are hits. ``refine=True`` runs the tile-search refinement per layer —
    the launcher-facing replacement for the old per-call autotune kwarg.

    With ``mesh`` given the planner goes SHARD-LOCAL: each leaf's TP kind
    (col/row/rep, the same name rules ``runtime/sharding.py`` shards it by)
    derives the per-rank local GEMM via :func:`shard_problem`, the plan is
    chosen by costing that local shape, and the local problem is what lands
    in the plan cache — plan-cache keys carry the shape each rank actually
    executes. The returned dict stays keyed by the GLOBAL layer_key, which
    is what trace-time ``resolve_plan`` lookups (global shapes under GSPMD)
    see, so the dict plugs straight into ``cfg.w4a16_plan``.

    A global "KxN" key can be shared by leaves of DIFFERENT TP kinds
    (square attention projections: wq is col-parallel, wo row-parallel) —
    trace-time lookups can't tell them apart, so when their shard-local
    plans disagree the key is dropped from the returned dict (those layers
    fall back to global-shape planning) rather than handing one layer the
    other's wrong-shape plan.
    """
    if mesh is not None:
        # runtime.sharding owns the name→TP-kind rules; imported lazily so
        # the kernels layer has no import-time dependency on runtime/
        from repro.runtime.sharding import leaf_kind_for_path
    plans: Dict[str, KernelPlan] = {}
    ambiguous = set()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=lambda t: isinstance(t, QuantizedTensor))
    for path, leaf in flat:
        if not isinstance(leaf, QuantizedTensor):
            continue
        K = int(leaf.K)
        N = int(leaf.N)
        # batch=1, matching the layer-time lookup key: stacked (L, ...)
        # kernels execute as 2-D slices inside scan, so from_operands
        # builds batch=1 problems there — and batch scales every cost
        # uniformly, so the decision is stack-size-invariant anyway
        problem = MatmulProblem(
            M=int(M), N=N, K=K, group_size=leaf.group_size,
            act_dtype=str(jnp.dtype(leaf.out_dtype)),
            out_dtype=str(jnp.dtype(leaf.out_dtype)),
            has_zeros=leaf.zeros is not None,
            backend=backend or jax.default_backend(),
            format=leaf.format.name)
        if mesh is not None:
            local = shard_problem(problem, mesh, leaf_kind_for_path(path))
            plan = plan_matmul(local, refine=refine)
        else:
            plan = plan_matmul(problem, refine=refine)
        key = problem.layer_key
        if plans.get(key, plan) != plan:
            ambiguous.add(key)
        plans[key] = plan
    for key in ambiguous:
        del plans[key]
    return plans


# ---------------------------------------------------------------------------
# Decode-attention planning: ring vs gather vs fused-paged.
#
# The same decision structure as plan_matmul, transposed onto the KV cache:
# each path is a registered entry with a roofline cost
# (costmodel.attn_decode_time_tpu) and a supports() predicate, Pallas paths
# pay the interpret penalty off-TPU, and a forced path that can't serve the
# problem is refused loudly. Execution routing lives with the cache
# (runtime/kvcache.py:paged_decode_attention), not here — the planner only
# names the path, so kernels/ stays import-independent of runtime/.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttentionProblem:
    """One paged-attention step: B rows of ``q_len`` query tokens each
    against a ctx-token cached window, Hq query heads over Hkv KV heads
    of dim D. ``q_len`` distinguishes the three serving regimes the fused
    kernel covers — decode (1), speculative verify (k+1) and chunked
    prefill (the chunk size) — and shifts the gather/fused tradeoff: the
    gather path re-materializes the whole window per step regardless of
    q_len, so its amortized cost collapses as q_len grows only for the
    fused path. ``spmd`` marks a step that GSPMD partitions over several
    devices, where the compiled fused kernel cannot run."""
    B: int
    Hq: int
    Hkv: int
    D: int
    cache_len: int
    page_size: int = 16
    window: int = 0
    kv_format: str = DEFAULT_KV_FORMAT
    paged: bool = True
    backend: str = "cpu"
    act_bytes: int = 2
    q_len: int = 1
    spmd: bool = False

    @property
    def ctx(self) -> int:
        return self.window or self.cache_len

    @property
    def pages(self) -> int:
        return max(1, -(-self.cache_len // max(self.page_size, 1)))


@dataclasses.dataclass(frozen=True)
class AttentionPlan:
    path: str                     # "ring" | "gather" | "fused"
    kv_partitions: int = 1        # Split-K degree over the page axis
    pages_per_block: int = 1      # table pages per fused-kernel grid step


@dataclasses.dataclass(frozen=True)
class AttnPath:
    name: str
    cost: Callable[["AttentionProblem", "AttentionPlan"], float]
    supports: Callable[["AttentionProblem"], bool]


_ATTN_REGISTRY: Dict[str, AttnPath] = {}


def register_attn_path(name: str, *, cost, supports=None):
    _ATTN_REGISTRY[name] = AttnPath(
        name=name, cost=cost, supports=supports or (lambda p: True))


def available_attn_paths() -> Tuple[str, ...]:
    return tuple(_ATTN_REGISTRY)


def choose_kv_partitions(B: int, Hkv: int, pages: int, *,
                         q_tiles: int = 1) -> int:
    """Split-K over the page axis: decode attention runs at B·Hkv grid
    tiles, which underfills the chip exactly like the paper's K ≫ N GEMMs
    (Fig. 2) — partition the table until the cores fill, staying on a
    power-of-2 divisor of the table length so partitions tile evenly.
    ``q_tiles`` is the multi-query kernel's Q-tile grid axis (1 for
    decode): a chunk already fans out over B·Hkv·q_tiles tiles, so it
    needs proportionally less page-axis splitting to fill the chip."""
    cores = num_cores()
    tiles = max(1, B * Hkv * max(1, q_tiles))
    if tiles >= cores or pages < 2:
        return 1
    want = min(cores // tiles, pages)
    s = 1
    while s * 2 <= want and pages % (s * 2) == 0:
        s *= 2
    return s


# the fused attention kernel's page blocks: tokens one grid step aims for,
# and the VMEM its double-buffered K/V pages and per-head temporaries may
# take (half of common.VMEM_BUDGET, leaving the pipelined q, tag and
# partial blocks room)
ATTN_BLOCK_TOKENS = 512
ATTN_VMEM_BUDGET = 8 * 1024 * 1024


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def choose_pages_per_block(T: int, ps: int, Hkv: int, D: int, kv_dtype,
                           QG: int, S: int = 1) -> int:
    """Table pages the fused paged-attention kernel covers per grid step.

    A grid step costs a fixed overhead on top of its DMAs, so a step over
    one (page, head) tile is bound by that overhead, not by bytes; one
    step over ``ppb`` whole pages of every head amortizes it. ``ppb`` is
    the largest power-of-two divisor of the partition's ``T // S`` pages
    with at most :data:`ATTN_BLOCK_TOKENS` tokens whose VMEM fits
    :data:`ATTN_VMEM_BUDGET`: K and V pages double-buffered, each
    ``(ps, D)`` head tile padded to its VMEM tile — 128 lanes, and rows
    to 8 or to the dtype's packed tile (16 for bf16, 32 for int8),
    whichever ``ps`` reaches (a v5e tiles a bf16 page of 8 rows (8, 128))
    — plus each head's ``(ppb·ps, D)`` K/V rows and ``(QG, ppb·ps)``
    scores, counted at 4 bytes. Shapes only.
    """
    itemsize = jnp.dtype(kv_dtype).itemsize
    sublanes = min(8 * max(1, 4 // itemsize), _round_up(ps, 8))
    page = Hkv * _round_up(ps, sublanes) * _round_up(D, common.LANE) \
        * itemsize

    def vmem(ppb: int) -> int:
        L = ppb * ps
        return (4 * ppb * page                               # K, V × 2 bufs
                + 4 * L * _round_up(D, common.LANE) * 4      # K/V rows
                + 4 * _round_up(QG, 8) * L * 4)              # scores, probs

    per_part = max(1, T // max(1, S))
    ppb = 1
    while (per_part % (2 * ppb) == 0
           and 2 * ppb * ps <= ATTN_BLOCK_TOKENS
           and vmem(2 * ppb) <= ATTN_VMEM_BUDGET):
        ppb *= 2
    return ppb


def choose_q_block(q_len: int, group: int, *, target: int = 128) -> int:
    """Queries per Q-tile for the multi-query fused attention grid: the
    largest divisor Tq of ``q_len`` with Tq·group rows ≤ ``target`` (the
    sublane budget the q block and the (m, l, acc) scratch share). Decode
    (q_len=1) degenerates to Tq=1; a C=32 chunk at GQA group 4 tiles as
    one 128-row block."""
    cap = max(1, target // max(1, group))
    t = max(1, min(q_len, cap))
    while q_len % t:
        t -= 1
    return t


def _attn_quantized(problem: AttentionProblem) -> bool:
    return get_kv_format(problem.kv_format).quantized


def _attn_pallas_factor(problem: AttentionProblem) -> float:
    return 1.0 if problem.backend == "tpu" else _INTERPRET_PENALTY


def _cost_attn_ring(problem: AttentionProblem, plan: AttentionPlan) -> float:
    return costmodel.attn_decode_time_tpu(
        "ring", problem.B, problem.Hq, problem.Hkv, problem.D, problem.ctx,
        quantized=False, act_bytes=problem.act_bytes,
        q_len=problem.q_len, spec=common.target_spec())


def _cost_attn_gather(problem: AttentionProblem,
                      plan: AttentionPlan) -> float:
    return costmodel.attn_decode_time_tpu(
        "gather", problem.B, problem.Hq, problem.Hkv, problem.D,
        problem.ctx, quantized=_attn_quantized(problem),
        act_bytes=problem.act_bytes, q_len=problem.q_len,
        spec=common.target_spec())


def _cost_attn_fused(problem: AttentionProblem,
                     plan: AttentionPlan) -> float:
    return costmodel.attn_decode_time_tpu(
        "fused", problem.B, problem.Hq, problem.Hkv, problem.D,
        problem.ctx, quantized=_attn_quantized(problem),
        act_bytes=problem.act_bytes, q_len=problem.q_len,
        kv_partitions=plan.kv_partitions, spec=common.target_spec()
    ) * _attn_pallas_factor(problem)


register_attn_path("ring", cost=_cost_attn_ring,
                   supports=lambda p: not p.paged)
register_attn_path("gather", cost=_cost_attn_gather,
                   supports=lambda p: p.paged)
register_attn_path("fused", cost=_cost_attn_fused,
                   supports=lambda p: p.paged
                   and pallas_lowers(p.backend, p.spmd))


def _attn_plan_for(problem: AttentionProblem, name: str) -> AttentionPlan:
    parts = ppb = 1
    if name == "fused":
        group = max(1, problem.Hq // max(1, problem.Hkv))
        tq = choose_q_block(problem.q_len, group)
        q_tiles = problem.q_len // tq
        parts = choose_kv_partitions(problem.B, problem.Hkv, problem.pages,
                                     q_tiles=q_tiles)
        # every partition flushes O(q_len·Hq·D) unnormalized partials, so
        # Split-K traffic grows with S·q_len while the window it splits is
        # fixed at ctx tokens — cap S where the combine bytes would start
        # rivaling the gather staging the fused path exists to delete
        # (binds only for multi-query tiles over short contexts; decode's
        # q_len=1 never hits it)
        while parts > 1 and parts * problem.q_len * 2 > problem.ctx:
            parts //= 2
        kv_dtype = (jnp.int8 if _attn_quantized(problem)
                    else jnp.bfloat16 if problem.act_bytes == 2
                    else jnp.float32)
        ppb = choose_pages_per_block(problem.pages, problem.page_size,
                                     problem.Hkv, problem.D, kv_dtype,
                                     tq * group, parts)
    return AttentionPlan(path=name, kv_partitions=parts, pages_per_block=ppb)


def plan_attention(problem: AttentionProblem, *,
                   path: Optional[str] = None) -> AttentionPlan:
    """Choose the decode-attention path for ``problem``.

    With ``path=None`` every registered path that supports the problem is
    ranked by its roofline cost and the cheapest wins — on TPU that is the
    fused kernel for paged long-context decode (one trip over the KV pool);
    on CPU hosts the interpret penalty keeps the XLA gather in front. A
    named ``path`` forces the choice but is validated against supports()
    so e.g. "ring" on a paged engine fails loudly.
    """
    if path is not None:
        if path == "auto":
            return plan_attention(problem)
        entry = _ATTN_REGISTRY.get(path)
        if entry is None:
            raise ValueError(
                f"unknown attention path {path!r} (registered: "
                f"{list(available_attn_paths())})")
        if not entry.supports(problem):
            eligible = [e.name for e in _ATTN_REGISTRY.values()
                        if e.supports(problem)]
            raise ValueError(
                f"attention path {path!r} does not support this problem "
                f"(paged={problem.paged}, backend={problem.backend!r}, "
                f"spmd={problem.spmd}); paths that do: {eligible}")
        return _attn_plan_for(problem, path)

    best: Optional[Tuple[float, int, AttentionPlan]] = None
    for order, entry in enumerate(_ATTN_REGISTRY.values()):
        if not entry.supports(problem):
            continue
        plan = _attn_plan_for(problem, entry.name)
        score = entry.cost(problem, plan)
        if best is None or (score, order) < (best[0], best[1]):
            best = (score, order, plan)
    if best is None:
        raise ValueError(
            f"no registered attention path supports this problem "
            f"(paged={problem.paged}; registered: "
            f"{list(available_attn_paths())})")
    return best[2]
