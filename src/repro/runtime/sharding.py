"""Name-based sharding rules (Megatron TP + optional ZeRO-3/FSDP).

Rules are applied leaf-wise over the param pytree; a dim is sharded over a
mesh axis only when divisible, so every architecture — from whisper-small to
llama3-405b — lowers on the same fixed production mesh (small archs simply
replicate where they don't divide; see DESIGN.md).

W4A16 leaves: a QuantizedTensor's packed (K/2, N) payload and its (K/g, N)
scales shard with the *same* logical rule as the dense (K, N) weight, so
each TP rank dequantizes only its own shard — the paper's kernel made
TP-composable with zero cross-device dequant traffic.
"""
from __future__ import annotations

from typing import Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.quant import QuantizedTensor

# column-parallel: output features sharded over "model"
COL = {"wq", "wk", "wv", "w_gate", "w_up", "tm_r", "tm_k", "tm_v", "tm_g",
       "tm_w", "cm_k", "in_proj", "dt_proj", "lm_head"}
# row-parallel: input features (K) sharded over "model"
ROW = {"wo", "w_down", "tm_o", "cm_v", "out_proj"}
# always replicated (small / routing-sensitive)
REP = {"router", "bc_proj"}


def _names(path):
    return [str(getattr(k, "key", getattr(k, "name", k))) for k in path]


def _divisible(dim: int, n: int) -> bool:
    return n > 0 and dim % n == 0


def _axis_size(mesh, name: str) -> int:
    try:
        return mesh.shape[name]
    except (KeyError, TypeError):
        return 0


def _matrix_spec(shape, mesh, kind: str, fsdp: bool, fsdp_axis: str):
    """Spec for a (..., K, N) weight; leading dims are stacking (L/E)."""
    nd = len(shape)
    spec = [None] * nd
    model = _axis_size(mesh, "model")
    fs = _axis_size(mesh, fsdp_axis) if fsdp else 0
    if kind == "col":
        if _divisible(shape[-1], model):
            spec[-1] = "model"
        if fsdp and _divisible(shape[-2], fs):
            spec[-2] = fsdp_axis
    elif kind == "row":
        if _divisible(shape[-2], model):
            spec[-2] = "model"
        if fsdp and _divisible(shape[-1], fs):
            spec[-1] = fsdp_axis
    else:  # replicated matrix, optionally fsdp on K
        if fsdp and _divisible(shape[-2], fs):
            spec[-2] = fsdp_axis
    return P(*spec)


def _leaf_kind(names) -> str:
    for n in reversed(names):
        if n in REP:
            return "rep"
        if n in COL:
            return "col"
        if n in ROW:
            return "row"
    return "rep"


def leaf_kind_for_path(path) -> str:
    """TP kind ("col" | "row" | "rep") of a param-tree leaf by its key path.

    Public entry for shard-local planning (kernels/planning.py): the same
    name rules that decide how a weight is sharded decide which of its GEMM
    dims (N for col, K for row) shrinks per rank."""
    return _leaf_kind(_names(path))


def param_shardings(params, mesh, *, fsdp: bool = False,
                    fsdp_axis: str = "data"):
    """Pytree of NamedSharding matching ``params`` (QuantizedTensor-aware)."""
    model = _axis_size(mesh, "model")

    def spec_for(names, leaf) -> P:
        if "embed" in names:                       # (V, d): vocab-sharded
            s = [None] * leaf.ndim
            if _divisible(leaf.shape[-2], model):
                s[-2] = "model"
            if fsdp and _divisible(leaf.shape[-1], _axis_size(mesh, fsdp_axis)):
                s[-1] = fsdp_axis
            return P(*s)
        kind = _leaf_kind(names)
        if leaf.ndim >= 2 and "kernel" in names:
            return _matrix_spec(leaf.shape, mesh, kind, fsdp, fsdp_axis)
        return P()                                  # norms, biases, scalars

    def visit(path, leaf):
        names = _names(path)
        if isinstance(leaf, QuantizedTensor):
            pk = spec_for(names, leaf.packed)
            # scales/zeros follow the same rule applied to their own shapes
            sc = _matrix_spec(leaf.scales.shape, mesh, _leaf_kind(names),
                              fsdp, fsdp_axis) if "kernel" in names else P()
            mk = lambda s: NamedSharding(mesh, s)
            return QuantizedTensor(
                packed=mk(pk), scales=mk(sc),
                zeros=None if leaf.zeros is None else mk(sc),
                group_size=leaf.group_size, out_dtype=leaf.out_dtype,
                format=leaf.format)
        return NamedSharding(mesh, spec_for(names, leaf))

    return jax.tree_util.tree_map_with_path(
        visit, params,
        is_leaf=lambda x: isinstance(x, QuantizedTensor))


def _axis_entry(spec_axes):
    """First entry of a batch_spec as a PartitionSpec element, normalizing a
    singleton tuple to the bare axis name (older jax compares them unequal)."""
    if len(spec_axes) == 0 or spec_axes[0] is None:
        return None
    a = spec_axes[0]
    return a[0] if isinstance(a, tuple) and len(a) == 1 else a


def batch_spec(B: int, mesh) -> P:
    """Shard the batch dim over as many DP axes as divisibility allows."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen = []
    prod = 1
    for a in axes:
        if B % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
    return P(tuple(chosen) if chosen else None)


def batch_axis_entry(B: int, mesh):
    """The normalized PartitionSpec entry for a batch dim of size ``B``.

    The single source for batch-axis entries in BOTH input and output
    shardings: every caller (data_shardings, the jit step out_shardings)
    goes through the same singleton-tuple normalization, so prefill/serve
    out_shardings can never disagree with the input shardings on older jax
    (where ``P(("data",))`` and ``P("data")`` compare unequal).
    """
    return _axis_entry(batch_spec(B, mesh))


def data_shardings(tree, mesh, *, batch_axis: int = 0):
    """Shard every array leaf's batch dim per batch_spec; rest replicated.

    Leaves with no batch dim (0-d scalars, or fewer dims than
    ``batch_axis`` addresses) are replicated instead of indexing past the
    end of their spec."""

    def visit(leaf):
        if leaf.ndim <= batch_axis:            # scalar / missing batch dim
            return NamedSharding(mesh, P())
        spec = [None] * leaf.ndim
        spec[batch_axis] = batch_axis_entry(leaf.shape[batch_axis], mesh)
        return NamedSharding(mesh, P(*spec))

    return jax.tree.map(visit, tree)


def decode_state_shardings(state, cfg, mesh):
    """KV caches: batch over DP axes; cache length over "model" when the
    batch can't use it — sequence-parallel decode attention (beyond-paper
    distribution; see DESIGN.md).

    Paged-pool leaves (``runtime/kvcache.PagedKVCache``) have no batch dim:
    pages replicate over the DP axes (every rank sees the whole pool — the
    block tables are what shard with the batch) and the KV-head dim shards
    over "model", matching the per-step k/v "bhd" activation sharding so
    scatter/gather stay rank-local along heads.
    """
    model = _axis_size(mesh, "model")

    def visit(path, leaf):
        names = _names(path)
        spec = [None] * leaf.ndim
        if any(n in ("k_pool", "v_pool", "k_scale", "v_scale", "page_pos")
               for n in names):
            # (L, nb, Hkv, ps, D) / (L, nb, Hkv, ps) / (L, nb, ps):
            # replicate pages over DP; shard the head dim over model
            if leaf.ndim >= 4 and _divisible(leaf.shape[2], model):
                spec[2] = "model"
            return NamedSharding(mesh, P(*spec))
        # layer-stacked leaves: axis0=L, axis1=B, then shape-specific
        if leaf.ndim >= 2:
            spec[1] = _axis_entry(batch_spec(leaf.shape[1], mesh))
        if ("k" in names or "v" in names or "pos" in names) and leaf.ndim >= 3:
            # KVCache leaves (L, B, W, [Hkv, D]) — shard window over model
            if _divisible(leaf.shape[2], model):
                spec[2] = "model"
        elif "wkv" in names and leaf.ndim == 5:
            # rwkv state (L, B, H, hd, hd): shard heads over model
            if _divisible(leaf.shape[2], model):
                spec[2] = "model"
        elif "ssm" in names and leaf.ndim == 4:
            # (L, B, d_inner, n): shard d_inner over model
            if _divisible(leaf.shape[2], model):
                spec[2] = "model"
        elif "enc_kv" in names and leaf.ndim == 5:
            if _divisible(leaf.shape[2], model):
                spec[2] = "model"
        return NamedSharding(mesh, P(*spec))

    return jax.tree_util.tree_map_with_path(visit, state)
