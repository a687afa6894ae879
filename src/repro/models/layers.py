"""Base layers: (quantizable) Linear, norms, embeddings, RoPE.

Every matmul in the model zoo goes through :func:`linear`, which dispatches
on the weight leaf type: a plain array runs the dense path, a
``QuantizedTensor`` runs the paper's W4A16 kernel (strategy chosen by the
model config). ``quantize_tree`` is the serve-time transform that converts a
trained/dense checkpoint into W4A16 form.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.quant import QuantizedTensor, quantize
from repro.kernels import planning


# ---------------------------------------------------------------------------
# activation sharding hints (no-ops without an ambient mesh)
# ---------------------------------------------------------------------------

def shard_hint(x: jax.Array, kind: str) -> jax.Array:
    """Constrain activations under the ambient mesh: batch over DP axes,
    heads/features over "model" when divisible. A no-op outside jax.set_mesh
    so single-device tests and examples are unaffected.

    kinds: "bsd" (B,S,d) · "bshd" (B,S,H,D) · "bd" (B,d) · "bhd" (B,H,D)
         · "ecd" (E,cap,d) MoE dispatch buffers — capacity dim over DP axes
    """
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return x
    names = mesh.axis_names
    dp = tuple(a for a in ("pod", "data") if a in names)
    batch_axis = 1 if kind == "ecd" else 0
    B = x.shape[batch_axis]
    prod = 1
    chosen = []
    for a in dp:
        if B % (prod * mesh.shape[a]) == 0:
            chosen.append(a)
            prod *= mesh.shape[a]
    bax = tuple(chosen) if chosen else None
    model = mesh.shape.get("model", 0) if "model" in names else 0
    spec = [None] * x.ndim
    spec[batch_axis] = bax
    if kind in ("bshd", "bhd"):
        h_axis = 2 if kind == "bshd" else 1
        if model and x.shape[h_axis] % model == 0:
            spec[h_axis] = "model"
    if kind == "bsd_sp" and x.ndim == 3:
        # Megatron sequence parallelism: residual stream sharded over the
        # model axis on the SEQUENCE dim between TP blocks — activation
        # stacks (remat) shrink by the TP degree; GSPMD inserts AG/RS at
        # the block boundaries (same bytes as the plain all-reduce).
        if model and x.shape[1] % model == 0:
            spec[1] = "model"
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec))


# ---------------------------------------------------------------------------
# Linear
# ---------------------------------------------------------------------------

def init_linear(key, d_in: int, d_out: int, dtype, *, bias: bool = False):
    scale = d_in ** -0.5
    p = {"kernel": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["bias"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x: jax.Array, cfg=None) -> jax.Array:
    """y = x @ W (+ b); W may be dense or a QuantizedTensor (W4A16)."""
    w = p["kernel"]
    if isinstance(w, QuantizedTensor):
        y = planning.matmul(x, w, cfg=cfg)
    elif cfg is not None and getattr(cfg, "bf16_partials", False):
        # cross-shard partial sums in activation dtype (bf16): the GSPMD
        # all-reduce of row-parallel outputs moves half the bytes
        y = jnp.dot(x, w.astype(x.dtype))
    else:
        y = jnp.dot(x, w.astype(x.dtype), preferred_element_type=jnp.float32)
        y = y.astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(y.dtype)
    return y


def quantize_tree(params, *, format=None, group_size: Optional[int] = None,
                  symmetric: Optional[bool] = None,
                  min_size: int = 1 << 16,
                  skip_names=("embed", "lm_head", "router", "bc_proj")):
    """Convert every eligible 2-D/3-D 'kernel' leaf to a QuantizedTensor.

    ``format`` names a registered :class:`~repro.core.quant.QuantFormat`
    (default ``w4a16_g128``); the legacy ``group_size``/``symmetric``
    kwargs derive a variant of it, so pre-format call sites are unchanged.
    3-D kernels (stacked layers or MoE experts) are quantized slice-wise via
    vmap — scales are per (layer/expert, K-group, N), matching the paper's
    per-matrix group quantization.
    """
    base = quant.resolve_format(format)
    if group_size is not None:
        base = base.with_group_size(group_size)
    if symmetric is not None:
        base = base.with_symmetric(symmetric)

    def pick_format(K: int):
        """Adaptive group size: fall back to smaller groups for odd dims
        (e.g. hymba's d_model=1600 is not 128-aligned but is 64-aligned).
        Channel/tensor granularities only need K packable."""
        if base.pack_factor > 1 and K % 2:
            return None
        if base.scale_granularity != "group":
            return base
        for g in (base.group_size, 64, 32):
            if K % g == 0:
                return base.with_group_size(g)
        return None

    def visit(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", "")) for k in path]
        if any(s in names for s in skip_names) or "kernel" not in names:
            return leaf
        if not isinstance(leaf, jax.Array) or leaf.dtype == jnp.int8:
            return leaf
        if leaf.ndim < 2 or leaf.shape[-2] * leaf.shape[-1] < min_size:
            return leaf                  # per-matrix size, not stacked size
        fmt = pick_format(leaf.shape[-2])
        if fmt is None:
            return leaf
        qfn = lambda w: quantize(w, fmt, out_dtype=leaf.dtype)
        for _ in range(leaf.ndim - 2):   # stacked layers / experts
            qfn = jax.vmap(qfn)
        return qfn(leaf)

    return jax.tree_util.tree_map_with_path(visit, params)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm(p, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    h = x.astype(jnp.float32)
    h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True) + eps)
    return (h * p["scale"].astype(jnp.float32)).astype(x.dtype)


def init_layernorm(d: int, dtype):
    return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}


def layernorm(p, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    h = x.astype(jnp.float32)
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.mean((h - mu) ** 2, axis=-1, keepdims=True)
    h = (h - mu) * jax.lax.rsqrt(var + eps)
    return (h * p["scale"].astype(jnp.float32)
            + p["bias"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed(p, tokens: jax.Array) -> jax.Array:
    return jnp.take(p["table"], tokens, axis=0)


def unembed(p, x: jax.Array) -> jax.Array:
    """Tied unembedding: logits = x @ table.T (fp32)."""
    return jnp.dot(x, p["table"].T.astype(x.dtype),
                   preferred_element_type=jnp.float32)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, H, D); positions: (..., S) int32."""
    D = x.shape[-1]
    freqs = rope_freqs(D, theta)                                  # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs        # (..., S, D/2)
    cos = jnp.cos(ang)[..., None, :]                              # (..., S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)
