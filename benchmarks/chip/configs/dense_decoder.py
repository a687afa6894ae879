"""Plain float32 reference of a dense decoder-only transformer.

It follows the published description of the configuration that uses it
(h2o-danube-1.8b): token embedding; per layer pre-norm RMSNorm,
grouped-query attention with rotary embeddings (rotate-half form, query head
h reads key/value head h // (heads / kv_heads)), causal and, where the
configuration has one, a sliding window that keeps keys with
q - window < k <= q; then a pre-norm gated SiLU MLP (down(silu(gate) * up)),
without biases; a final RMSNorm and an untied head. Each
departure of the program from the published model is listed in the
configuration's file; this reference computes the configuration as that file
states it.

Everything is float32 with matmuls at ``Precision.HIGHEST``. Weights are the
benchmark's own packed int4 arrays, unpacked and scaled here one layer at a
time. No kernel, cache, planner or batching: one sequence, the whole
sequence, layer by layer, with attention taken in blocks of query rows so
that long sequences fit.

``act_dtype`` rounds every matmul's activation input to a lower precision
(float8_e4m3fn for the control) and is None for the reference itself.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F32 = jnp.float32
Q_BLOCK = 256
BUCKET = 512


def unpack_int4(packed: jax.Array) -> jax.Array:
    """(K/2, N) int8, low nibble = even row -> (K, N) float32 in [-8, 7]."""
    b = packed.astype(jnp.int32)
    lo = jnp.right_shift(jnp.left_shift(b, 28), 28)
    hi = jnp.right_shift(jnp.left_shift(b, 24), 28)
    K2, N = packed.shape
    return jnp.stack([lo, hi], axis=1).reshape(2 * K2, N).astype(F32)


def dequant(packed: jax.Array, scales: jax.Array) -> jax.Array:
    q = unpack_int4(packed)
    K, N = q.shape
    g = K // scales.shape[0]
    return (q.reshape(-1, g, N) * scales[:, None, :].astype(F32)
            ).reshape(K, N)


def _round(x, act_dtype):
    return x if act_dtype is None else x.astype(act_dtype).astype(F32)


def _lin(x, w, act_dtype):
    return jnp.dot(_round(x, act_dtype), w, precision=HI)


def rmsnorm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * scale.astype(F32)


def rope(x, pos, theta):
    """x (S, H, D), pos (S,) -> rotated, rotate-half convention."""
    D = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=F32) / D))
    ang = pos.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :D // 2], x[..., D // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window: int, act_dtype):
    """q (S, H, D), k/v (S, Hkv, D) -> (S, H*D), causal (+ window)."""
    S, H, D = q.shape
    Hkv = k.shape[1]
    G = H // Hkv
    kpos = jnp.arange(S)
    kk = _round(k, act_dtype)
    vv = _round(v, act_dtype)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, 0)
        qb = _round(qb, act_dtype).reshape(Q_BLOCK, Hkv, G, D)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.einsum("qhgd,khd->hgqk", qb, kk, precision=HI) / np.sqrt(D)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(ok[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("hgqk,khd->qhgd", _round(p, act_dtype), vv,
                       precision=HI)
        return o.reshape(Q_BLOCK, H * D)

    return jax.lax.map(block, jnp.arange(S // Q_BLOCK)).reshape(S, H * D)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(spec, h, layer, raw_layers, act_dtype):
    c = dict(spec)
    H, Hkv, D = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    eps = c["rms_norm_eps"]
    S = h.shape[0]
    pos = jnp.arange(S)

    def w(name):
        return dequant(raw_layers[name + ".packed"][layer],
                       raw_layers[name + ".scales"][layer])

    x = rmsnorm(h, raw_layers["norm1"][layer], eps)
    q = rope(_lin(x, w("wq"), act_dtype).reshape(S, H, D), pos,
             c["rope_theta"])
    k = rope(_lin(x, w("wk"), act_dtype).reshape(S, Hkv, D), pos,
             c["rope_theta"])
    v = _lin(x, w("wv"), act_dtype).reshape(S, Hkv, D)
    o = attention(q, k, v, c["sliding_window"], act_dtype)
    h = h + _lin(o, w("wo"), act_dtype)
    x = rmsnorm(h, raw_layers["norm2"][layer], eps)
    a = jax.nn.silu(_lin(x, w("w_gate"), act_dtype)) \
        * _lin(x, w("w_up"), act_dtype)
    return h + _lin(a, w("w_down"), act_dtype)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(spec, h, final_norm, lm_head, act_dtype):
    c = dict(spec)
    x = rmsnorm(h, final_norm, c["rms_norm_eps"])
    return _lin(x, lm_head.astype(F32), act_dtype)


SPEC_KEYS = ("num_hidden_layers", "num_attention_heads",
             "num_key_value_heads", "head_dim", "rms_norm_eps", "rope_theta",
             "sliding_window")


def logits(raw: dict, cfgj: dict, tokens: np.ndarray, rows: np.ndarray,
           act_dtype=None, size=(0, 0)) -> np.ndarray:
    """float32 logits (len(rows), padded vocab) of ``tokens`` at ``rows``.

    ``rows`` are positions whose next-token distribution is wanted. The
    sequence is padded to at least ``size[0]`` positions and the rows to at
    least ``size[1]``, each then up to a multiple of ``BUCKET``/``Q_BLOCK``,
    so that few shapes compile; padding sits after every real position and
    no real query attends it.
    """
    spec = tuple((k, cfgj[k]) for k in SPEC_KEYS)
    S = len(tokens)
    S_pad = -(-max(S, size[0]) // BUCKET) * BUCKET
    ids = np.zeros(S_pad, np.int32)
    ids[:S] = tokens
    h = jnp.take(raw["embed"], jnp.asarray(ids), axis=0).astype(F32)
    layer_keys = [k for k in raw if k not in ("embed", "lm_head",
                                              "final_norm")]
    raw_layers = {k: raw[k] for k in layer_keys}
    for layer in range(cfgj["num_hidden_layers"]):
        h = _layer(spec, h, jnp.int32(layer), raw_layers, act_dtype)
    n = len(rows)
    picked = np.zeros(-(-max(n, size[1]) // Q_BLOCK) * Q_BLOCK, np.int32)
    picked[:n] = rows
    sel = h[jnp.asarray(picked)]
    out = _head(spec, sel, raw["final_norm"], raw["lm_head"], act_dtype)
    return np.asarray(out)[:n]
