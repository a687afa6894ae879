"""Model assembly: init / forward / loss / prefill / decode for all families.

Layer parameters are stacked along a leading L axis and executed with
``jax.lax.scan`` (+ optional remat) so a 126-layer model lowers as one scanned
layer — essential for dry-run compile times and the standard structure for
pipeline-friendly HLO.

The serving steps (``decode_step``, ``prefill_chunk_step``, ``verify_step``)
name their parts with ``jax.named_scope``: ``attn_qkv`` (q/k/v projections
and RoPE), ``kv_write`` (the KV insert or scatter), ``attn_core`` (attention
over the cache, its combine included), ``attn_out`` (the o projection),
``mlp`` (post-attention norm, MLP or experts, residual) and ``head`` (final
norm and vocabulary projection); ``runtime/steps.py`` adds ``sample``. The
scopes are metadata only: each compiled op carries its scope in its
``op_name``, so a device trace can be read by part.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.quant import (
    DEFAULT_KV_FORMAT, get_kv_format, kv_dequantize, kv_quantize,
)
from repro.models import attention, layers, moe, rwkv, ssm
from repro.models.config import ModelConfig
from repro.runtime import kvcache as kvc


# ---------------------------------------------------------------------------
# per-layer init
# ---------------------------------------------------------------------------

def _init_attn(key, cfg: ModelConfig):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    return {
        "wq": layers.init_linear(k1, d, cfg.q_dim, cfg.dtype),
        "wk": layers.init_linear(k2, d, cfg.kv_dim, cfg.dtype),
        "wv": layers.init_linear(k3, d, cfg.kv_dim, cfg.dtype),
        "wo": layers.init_linear(k4, cfg.q_dim, d, cfg.dtype),
    }


def _init_mlp(key, cfg: ModelConfig):
    d, ff = cfg.d_model, cfg.d_ff
    if cfg.mlp_type == "swiglu":
        k1, k2, k3 = jax.random.split(key, 3)
        return {
            "w_gate": layers.init_linear(k1, d, ff, cfg.dtype),
            "w_up": layers.init_linear(k2, d, ff, cfg.dtype),
            "w_down": layers.init_linear(k3, ff, d, cfg.dtype),
        }
    k1, k2 = jax.random.split(key, 2)
    return {
        "w_up": layers.init_linear(k1, d, ff, cfg.dtype, bias=True),
        "w_down": layers.init_linear(k2, ff, d, cfg.dtype, bias=True),
    }


def _init_norm(cfg: ModelConfig):
    if cfg.norm_type == "layernorm":
        return layers.init_layernorm(cfg.d_model, cfg.dtype)
    return layers.init_rmsnorm(cfg.d_model, cfg.dtype)


def _norm(cfg, p, x):
    if cfg.norm_type == "layernorm":
        return layers.layernorm(p, x)
    return layers.rmsnorm(p, x)


def _init_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 4)
    p: Dict[str, Any] = {"norm1": _init_norm(cfg), "norm2": _init_norm(cfg)}
    if cfg.family in ("dense", "moe", "hybrid", "encdec"):
        p["attn"] = _init_attn(ks[0], cfg)
    if cfg.family in ("dense", "hybrid", "encdec"):
        p["mlp"] = _init_mlp(ks[1], cfg)
    if cfg.family == "moe":
        p["moe"] = moe.init_moe(ks[1], cfg.d_model, cfg.d_ff, cfg.num_experts,
                                cfg.dtype)
    if cfg.family == "rwkv":
        p.pop("attn", None)
        blk = rwkv.init_rwkv_block(ks[0], cfg.d_model, cfg.d_ff,
                                   cfg.num_heads, cfg.dtype)
        p.update(blk)
    if cfg.family == "hybrid":
        p["ssm"] = ssm.init_ssm(ks[2], cfg.d_model, cfg.d_inner,
                                cfg.ssm_state, cfg.dtype)
    if cfg.family == "encdec":
        p["cross"] = _init_attn(ks[2], cfg)
        p["norm3"] = _init_norm(cfg)
    return p


def _init_enc_layer(key, cfg: ModelConfig):
    ks = jax.random.split(key, 2)
    return {
        "norm1": _init_norm(cfg), "norm2": _init_norm(cfg),
        "attn": _init_attn(ks[0], cfg), "mlp": _init_mlp(ks[1], cfg),
    }


def init_params(key, cfg: ModelConfig):
    """Dense (trainable) parameters; quantize with ``quantize_params``."""
    kE, kL, kH, kEnc = jax.random.split(key, 4)
    params: Dict[str, Any] = {
        "embed": layers.init_embedding(kE, cfg.padded_vocab, cfg.d_model,
                                       cfg.dtype),
        "final_norm": _init_norm(cfg),
    }
    lkeys = jax.random.split(kL, cfg.num_layers)
    params["layers"] = jax.vmap(lambda k: _init_layer(k, cfg))(lkeys)
    if not cfg.tie_embeddings:
        params["lm_head"] = layers.init_linear(
            kH, cfg.d_model, cfg.padded_vocab, cfg.dtype)
    if cfg.family == "encdec":
        ekeys = jax.random.split(kEnc, cfg.encoder_layers)
        params["encoder"] = {
            "layers": jax.vmap(lambda k: _init_enc_layer(k, cfg))(ekeys),
            "final_norm": _init_norm(cfg),
        }
    return params


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree of params — no allocation (dry-run path)."""
    return jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg))


def quantize_params(params, cfg: ModelConfig, *, format=None,
                    min_size: int = 1 << 16):
    """Serve-time quantization transform (the paper's W4A16 by default;
    ``format``/``cfg.quant_format`` selects any registered format
    model-wide). ``cfg.group_size`` only re-groups the default format — a
    non-default format's grouping lives in its own name. The single place
    that derives the format/group precedence for launchers and models."""
    from repro.core import quant
    fmt = quant.get_format(
        format or getattr(cfg, "quant_format", quant.DEFAULT_FORMAT))
    gs = cfg.group_size if fmt.name == quant.DEFAULT_FORMAT else None
    return layers.quantize_tree(params, format=fmt.name, group_size=gs,
                                min_size=min_size)


# ---------------------------------------------------------------------------
# attention sub-block (sequence mode)
# ---------------------------------------------------------------------------

def _attn_seq(p, cfg: ModelConfig, x, positions, *, causal=True, window=None,
              return_kv=False):
    B, S, _ = x.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = layers.shard_hint(
        layers.linear(p["wq"], x, cfg).reshape(B, S, H, D), "bshd")
    k = layers.shard_hint(
        layers.linear(p["wk"], x, cfg).reshape(B, S, Hkv, D), "bshd")
    v = layers.shard_hint(
        layers.linear(p["wv"], x, cfg).reshape(B, S, Hkv, D), "bshd")
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    w = cfg.sliding_window if window is None else window
    if getattr(cfg, "attn_impl", "chunked") == "flash":
        from repro.kernels.flash_attention import flash_attention
        o = flash_attention(q, k, v, causal=causal, window=w)
    else:
        o = attention.chunked_attention(q, k, v, causal=causal, window=w)
    out = layers.linear(p["wo"], o.reshape(B, S, H * D), cfg)
    # materialize the row-parallel partial sum HERE (bf16) — otherwise GSPMD
    # defers the all-reduce into the next norm's fp32 region (2x ICI bytes)
    out = layers.shard_hint(out, "bsd")
    if return_kv:
        return out, (k, v)
    return out


def _cross_attn_seq(p, cfg, x, enc_kv):
    B, S, _ = x.shape
    H, D = cfg.num_heads, cfg.head_dim
    q = layers.linear(p["wq"], x, cfg).reshape(B, S, H, D)
    k, v = enc_kv                                     # (B, T, Hkv, D)
    o = attention.chunked_attention(q, k, v, causal=False, window=0)
    return layers.linear(p["wo"], o.reshape(B, S, H * D), cfg)


def _mlp(p, cfg, x):
    if cfg.mlp_type == "swiglu":
        g = layers.linear(p["w_gate"], x, cfg)
        u = layers.linear(p["w_up"], x, cfg)
        h = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * u
        return layers.shard_hint(layers.linear(p["w_down"], h, cfg), "bsd")
    h = layers.linear(p["w_up"], x, cfg)
    h = jax.nn.gelu(h.astype(jnp.float32)).astype(x.dtype)
    return layers.shard_hint(layers.linear(p["w_down"], h, cfg), "bsd")


# ---------------------------------------------------------------------------
# sequence-mode layer bodies (train / prefill)
# ---------------------------------------------------------------------------

def _layer_seq(p, cfg: ModelConfig, h, positions, *, collect_cache, cache_len,
               enc_kv=None):
    """One decoder layer in sequence mode. Returns (h, cache_entry)."""
    h = layers.shard_hint(
        h, "bsd_sp" if getattr(cfg, "seq_parallel", False) else "bsd")
    cache_entry = None
    if cfg.family == "rwkv":
        B = h.shape[0]
        st = rwkv.rwkv_state_init(B, cfg.d_model, cfg.num_heads)
        x1 = _norm(cfg, p["norm1"], h)
        tm, st = rwkv.time_mix_seq(
            {k: p[k] for k in ("tm_r", "tm_k", "tm_v", "tm_g", "tm_w",
                               "tm_o", "w_bias")},
            x1, st, num_heads=cfg.num_heads, cfg=cfg)
        h = h + tm
        x2 = _norm(cfg, p["norm2"], h)
        prev = jnp.concatenate(
            [jnp.zeros_like(x2[:, :1]), x2[:, :-1]], axis=1)
        h = h + rwkv.channel_mix(
            {k: p[k] for k in ("cm_k", "cm_v")}, x2, prev, cfg)
        if collect_cache:
            cache_entry = dict(st, cm_shift=x2[:, -1].astype(jnp.float32))
        return h, cache_entry

    x1 = _norm(cfg, p["norm1"], h)
    if cfg.family == "hybrid":
        B = h.shape[0]
        attn_out, kv = _attn_seq(p["attn"], cfg, x1, positions, return_kv=True)
        s0 = ssm.ssm_state_init(B, cfg.d_inner, cfg.ssm_state)
        ssm_out, s_fin = ssm.ssm_seq(p["ssm"], x1, s0, cfg)
        h = h + 0.5 * (attn_out + ssm_out)
        h = h + _mlp(p["mlp"], cfg, _norm(cfg, p["norm2"], h))
        if collect_cache:
            kvcache = attention.init_cache(
                B, cache_len, cfg.num_kv_heads, cfg.head_dim, cfg.dtype)
            kvcache = attention.cache_prefill(kvcache, *kv)
            cache_entry = {"kv": kvcache, "ssm": s_fin}
        return h, cache_entry

    attn_out, kv = _attn_seq(p["attn"], cfg, x1, positions, return_kv=True)
    h = h + attn_out
    if cfg.family == "encdec":
        h = h + _cross_attn_seq(p["cross"], cfg, _norm(cfg, p["norm3"], h),
                                enc_kv)
    if cfg.family == "moe":
        y, _aux = moe.moe_ffn(
            p["moe"], _norm(cfg, p["norm2"], h),
            num_experts=cfg.num_experts, top_k=cfg.experts_per_token,
            capacity_factor=cfg.moe_capacity_factor, cfg=cfg)
        h = h + y
    else:
        h = h + _mlp(p["mlp"], cfg, _norm(cfg, p["norm2"], h))
    if collect_cache:
        B = h.shape[0]
        kvcache = attention.init_cache(
            B, cache_len, cfg.num_kv_heads, cfg.head_dim, cfg.dtype)
        cache_entry = {"kv": attention.cache_prefill(kvcache, *kv)}
    return h, cache_entry


def _encoder_forward(params, cfg: ModelConfig, audio_embeds):
    """Whisper-style encoder over stub frame embeddings (B, T, d)."""
    h = audio_embeds
    B, T, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (B, T))

    def body(h, lp):
        h = layers.shard_hint(h, "bsd")
        x1 = _norm(cfg, lp["norm1"], h)
        h = h + _attn_seq(lp["attn"], cfg, x1, positions, causal=False,
                          window=0)
        h = h + _mlp(lp["mlp"], cfg, _norm(cfg, lp["norm2"], h))
        return h, None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, h, params["encoder"]["layers"])
    return _norm(cfg, params["encoder"]["final_norm"], h)


def encode_cross_kv(params, cfg: ModelConfig, audio_embeds):
    """Encoder forward + per-decoder-layer cross-attention K/V.

    audio_embeds: (B, T, d) → tuple of two (L, B, T, Hkv, D) stacks. The
    serving engine calls this once at admit (the enc-dec analogue of a
    recurrent family's carry init) and inserts the rows into the decode
    state; training/``forward`` consumes it inline.
    """
    enc_out = _encoder_forward(params, cfg, audio_embeds)
    B, T = enc_out.shape[:2]

    def cross_kv(lp):
        k = layers.linear(lp["cross"]["wk"], enc_out, cfg).reshape(
            B, T, cfg.num_kv_heads, cfg.head_dim)
        v = layers.linear(lp["cross"]["wv"], enc_out, cfg).reshape(
            B, T, cfg.num_kv_heads, cfg.head_dim)
        return (k, v)

    return jax.vmap(cross_kv)(params["layers"])       # (L, B, T, Hkv, D) ×2


# ---------------------------------------------------------------------------
# public: forward (train) / loss
# ---------------------------------------------------------------------------

def forward(params, cfg: ModelConfig, tokens: jax.Array, *,
            prefix_embeds: Optional[jax.Array] = None,
            audio_embeds: Optional[jax.Array] = None,
            collect_cache: bool = False, cache_len: int = 0):
    """tokens: (B, S_text) → logits (B, S_total, padded_vocab) fp32.

    prefix_embeds: (B, P, d) vision patches (VLM stub frontend), prepended.
    audio_embeds:  (B, T, d) audio frames (encdec stub frontend).
    """
    h = layers.embed(params["embed"], tokens)
    if prefix_embeds is not None:
        h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
    h = layers.shard_hint(h, "bsd")
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    enc_kv_stack = None
    if cfg.family == "encdec":
        enc_kv_stack = encode_cross_kv(params, cfg, audio_embeds)

    def body(h, xs):
        if cfg.family == "encdec":
            lp, ekv = xs
        else:
            lp, ekv = xs, None
        h, ce = _layer_seq(lp, cfg, h, positions,
                           collect_cache=collect_cache, cache_len=cache_len,
                           enc_kv=ekv)
        return h, ce

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (params["layers"], enc_kv_stack) if cfg.family == "encdec" \
        else params["layers"]
    h, cache = jax.lax.scan(body, h, xs)
    h = _norm(cfg, params["final_norm"], h)
    if cfg.tie_embeddings:
        logits = layers.unembed(params["embed"], h)
    else:
        logits = layers.linear(params["lm_head"], h, cfg).astype(jnp.float32)
    if collect_cache:
        extras = {"cache": cache}
        if cfg.family == "encdec":
            extras["enc_kv"] = enc_kv_stack
        return logits, extras
    return logits


def loss_fn(params, cfg: ModelConfig, batch):
    """Next-token cross entropy. batch: {tokens, labels, [embeds]}."""
    logits = forward(
        params, cfg, batch["tokens"],
        prefix_embeds=batch.get("vision_embeds"),
        audio_embeds=batch.get("audio_embeds"),
    )
    labels = batch["labels"]
    P = logits.shape[1] - labels.shape[1]
    if P > 0:                                   # vision prefix positions
        logits = logits[:, P:]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = (labels >= 0).astype(jnp.float32)
    return -(ll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


# ---------------------------------------------------------------------------
# public: prefill / decode (serving)
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, tokens, *, cache_len: int,
            prefix_embeds=None, audio_embeds=None):
    """Run the full prompt; returns (last-token logits, decode state)."""
    logits, extras = forward(
        params, cfg, tokens, prefix_embeds=prefix_embeds,
        audio_embeds=audio_embeds, collect_cache=True, cache_len=cache_len)
    return logits[:, -1], extras


def decode_step(params, cfg: ModelConfig, state, tokens: jax.Array,
                pos: jax.Array, *, tables=None, active=None,
                cache_len: int = 0,
                kv_format: str = DEFAULT_KV_FORMAT,
                attn_path: str = "gather", kv_partitions=None,
                live_pages=None):
    """One decode step. tokens: (B,) int32; pos: (B,) absolute positions.

    state: {"cache": stacked per-layer cache, ["enc_kv": ...]} from prefill.
    With ``tables`` (B, pages_per_slot) the KV entries of ``state`` are
    paged block pools (``kvcache.PagedKVCache``): the new token is
    scattered at ``pos % cache_len`` and attention runs on ``attn_path`` —
    ``"gather"`` reassembles each slot's ring window then runs the
    unchanged ring attention; ``"fused"`` walks the block table inside the
    Pallas kernel (one pass, token-identical). ``active`` (B,) bool masks
    recurrent-carry writes for rows that are not decoding (a slot mid
    chunked-prefill shares the batch: a masked table already protects its
    KV pages, but rwkv/ssm carries are per-row state and would be
    clobbered by the dummy token without the mask). Returns (logits
    (B, V) fp32, new state).
    """
    h = layers.embed(params["embed"], tokens)            # (B, d)
    B = h.shape[0]
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    kvfmt = get_kv_format(kv_format)

    def attn_step(lp, x, kvcache):
        with jax.named_scope("attn_qkv"):
            q = layers.shard_hint(
                layers.linear(lp["wq"], x, cfg).reshape(B, H, D), "bhd")
            k = layers.shard_hint(
                layers.linear(lp["wk"], x, cfg).reshape(B, Hkv, D), "bhd")
            v = layers.shard_hint(
                layers.linear(lp["wv"], x, cfg).reshape(B, Hkv, D), "bhd")
            q = layers.apply_rope(q[:, None], pos[:, None],
                                  cfg.rope_theta)[:, 0]
            k = layers.apply_rope(k[:, None], pos[:, None],
                                  cfg.rope_theta)[:, 0]
        if tables is None:
            with jax.named_scope("kv_write"):
                kvcache = attention.cache_insert(kvcache, k, v, pos)
            with jax.named_scope("attn_core"):
                o = attention.decode_attention(q, kvcache, pos,
                                               window=cfg.sliding_window)
        else:
            with jax.named_scope("kv_write"):
                kvcache = kvc.paged_insert(kvcache, tables, k, v, pos,
                                           cache_len=cache_len, fmt=kvfmt)
            with jax.named_scope("attn_core"):
                o = kvc.paged_decode_attention(
                    q, kvcache, tables, pos, window=cfg.sliding_window,
                    fmt=kvfmt, out_dtype=cfg.dtype, attn_path=attn_path,
                    kv_partitions=kv_partitions, live_pages=live_pages)
        with jax.named_scope("attn_out"):
            out = layers.linear(lp["wo"], o.reshape(B, H * D), cfg)
        return out, kvcache

    def body(h, xs):
        h = layers.shard_hint(h, "bd")
        if cfg.family == "encdec":
            lp, ce, ekv = xs
        else:
            (lp, ce), ekv = xs, None
        if cfg.family == "rwkv":
            x1 = _norm(cfg, lp["norm1"], h)
            tm, st = rwkv.time_mix_step(
                {k: lp[k] for k in ("tm_r", "tm_k", "tm_v", "tm_g", "tm_w",
                                    "tm_o", "w_bias")},
                x1, ce, num_heads=cfg.num_heads, cfg=cfg)
            h = h + tm
            x2 = _norm(cfg, lp["norm2"], h)
            h = h + rwkv.channel_mix(
                {k: lp[k] for k in ("cm_k", "cm_v")}, x2,
                ce["cm_shift"], cfg)
            ce_new = dict(st, cm_shift=x2.astype(jnp.float32))
            if active is not None:
                ce_new = {
                    k: jnp.where(
                        active.reshape((-1,) + (1,) * (ce_new[k].ndim - 1)),
                        ce_new[k], ce[k])
                    for k in ce_new}
            return h, ce_new
        x1 = _norm(cfg, lp["norm1"], h)
        if cfg.family == "hybrid":
            a, kvnew = attn_step(lp["attn"], x1, ce["kv"])
            s_out, s_new = ssm.ssm_step(lp["ssm"], x1, ce["ssm"], cfg)
            if active is not None:
                s_new = jnp.where(active[:, None, None], s_new, ce["ssm"])
            h = h + 0.5 * (a + s_out)
            return _ffn_seq(lp, cfg, h), {"kv": kvnew, "ssm": s_new}
        a, kvnew = attn_step(lp["attn"], x1, ce["kv"])
        h = h + a
        if cfg.family == "encdec":
            x3 = _norm(cfg, lp["norm3"], h)
            q = layers.linear(lp["cross"]["wq"], x3, cfg).reshape(B, 1, H, D)
            k, v = ekv
            o = attention.chunked_attention(q, k, v, causal=False, window=0)
            h = h + layers.linear(lp["cross"]["wo"],
                                  o.reshape(B, 1, H * D), cfg)[:, 0]
        return _ffn_seq(lp, cfg, h), {"kv": kvnew}

    xs = (params["layers"], state["cache"])
    if cfg.family == "encdec":
        xs = (params["layers"], state["cache"], state["enc_kv"])
    h, new_cache = jax.lax.scan(body, h, xs)
    with jax.named_scope("head"):
        logits = _logits_head(params, cfg,
                              _norm(cfg, params["final_norm"], h))
    new_state = dict(state, cache=new_cache)
    return logits, new_state


# Families whose decode state carries per-slot recurrent leaves (rwkv
# wkv/shift/cm_shift, hybrid ssm) that chunked prefill threads through
# `prefill_chunk_step` and speculative verify checkpoints per position.
# Every family chunks; this tuple only marks the ones that need carry
# plumbing (and whose carries a draft model cannot rewind).
CARRY_FAMILIES = ("rwkv", "hybrid")


def _logits_head(params, cfg: ModelConfig, h):
    if cfg.tie_embeddings:
        return layers.unembed(params["embed"], h)
    return layers.linear(params["lm_head"], h, cfg).astype(jnp.float32)


def _last_valid_row(h, positions):
    """h: (B, C, d); positions (B, C) with -1 padding → (B, d) at the last
    valid position (row 0 for fully-padded rows — callers discard them)."""
    last = jnp.maximum(
        jnp.sum((positions >= 0).astype(jnp.int32), axis=1) - 1, 0)
    return jnp.take_along_axis(
        h, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]


def _ffn_seq(lp, cfg: ModelConfig, hc):
    """Post-attention FFN tail (norm, MLP or experts, residual) shared by
    the decode/chunk/verify layer bodies: the step programs' ``mlp``
    scope."""
    with jax.named_scope("mlp"):
        if cfg.family == "moe":
            y, _aux = moe.moe_ffn(
                lp["moe"], _norm(cfg, lp["norm2"], hc),
                num_experts=cfg.num_experts, top_k=cfg.experts_per_token,
                capacity_factor=cfg.moe_capacity_factor, cfg=cfg)
            return hc + y
        return hc + _mlp(lp["mlp"], cfg, _norm(cfg, lp["norm2"], hc))


def _paged_chunk_attn(ap, cfg: ModelConfig, x1, pool, tables, positions,
                      safe_pos, *, fmt, cache_len: int, batched: bool,
                      attn_path: str = "gather", kv_partitions=None,
                      live_pages=None):
    """Self-attention for a (B, C) token window over the paged pool.

    Shared by chunked prefill (B=1, one slot table) and speculative verify
    (full batch, per-slot tables). Per layer the window's K/V are read
    from the slot pages *first*, then the chunk's own K/V attended as an
    explicit segment and scattered back — window BEFORE scatter, because
    when the stream wraps the logical window (prompt > cache_len on SWA
    archs) the chunk's offsets overwrite the oldest in-window entries,
    which this chunk's earliest queries still attend. Window entries at
    chunk positions (a sharing peer's copy of what this chunk recomputes,
    or its decode appends) are masked off to keep the softmax
    single-counted.

    ``attn_path`` picks how the window is read: ``"gather"``
    materializes it to HBM (``gather_window``, clamped to ``live_pages``
    when the caller knows the high-water mark) and runs
    ``prefix_chunk_attention`` over the concatenation; ``"fused"`` walks
    the block table inside the multi-query Pallas kernel
    (``kernels/paged_attention.fused_chunk_attention``) — one pass over
    pooled KV, no gathered copy, same masking. Returns
    (attn out (B, C, d), new pool).
    """
    B, C, _ = x1.shape
    H, Hkv, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    with jax.named_scope("attn_qkv"):
        q = layers.shard_hint(
            layers.linear(ap["wq"], x1, cfg).reshape(B, C, H, D), "bshd")
        k = layers.shard_hint(
            layers.linear(ap["wk"], x1, cfg).reshape(B, C, Hkv, D), "bshd")
        v = layers.shard_hint(
            layers.linear(ap["wv"], x1, cfg).reshape(B, C, Hkv, D), "bshd")
        q = layers.apply_rope(q, safe_pos, cfg.rope_theta)
        k = layers.apply_rope(k, safe_pos, cfg.rope_theta)
    with jax.named_scope("attn_core"):
        # the chunk segment takes the same quantize→dequantize round-trip
        # as its stored copy, so intra-chunk attention sees exactly what
        # later queries will gather (a no-op for kv_fp16)
        kr = kv_dequantize(*kv_quantize(k, fmt), fmt=fmt, dtype=cfg.dtype)
        vr = kv_dequantize(*kv_quantize(v, fmt), fmt=fmt, dtype=cfg.dtype)
        if attn_path == "fused":
            from repro.kernels.paged_attention import fused_chunk_attention

            o = fused_chunk_attention(
                q, kr, vr, pool, tables, positions,
                window=cfg.sliding_window, fmt=fmt, out_dtype=cfg.dtype,
                kv_partitions=kv_partitions)
        else:
            win = kvc.gather_window(pool, tables, fmt=fmt,
                                    out_dtype=cfg.dtype,
                                    live_pages=live_pages)
            start = positions[:, :1]                      # first chunk pos
            wpos = jnp.where(win.pos < start, win.pos, -1)
            seq = attention.KVCache(
                k=jnp.concatenate([win.k, kr.astype(win.k.dtype)], axis=1),
                v=jnp.concatenate([win.v, vr.astype(win.v.dtype)], axis=1),
                pos=jnp.concatenate([wpos, positions], axis=1))
            o = attention.prefix_chunk_attention(q, seq, positions,
                                                 window=cfg.sliding_window)
    with jax.named_scope("kv_write"):
        if batched:
            pool = kvc.scatter_chunks(pool, tables, k, v, positions,
                                      cache_len=cache_len, fmt=fmt)
        else:
            pool = kvc.scatter_chunk(pool, tables[0], k[0], v[0],
                                     positions[0], cache_len=cache_len,
                                     fmt=fmt)
    with jax.named_scope("attn_out"):
        a = layers.linear(ap["wo"], o.reshape(B, C, H * D), cfg)
    return layers.shard_hint(a, "bsd"), pool


def _tm_params(lp):
    return {k: lp[k] for k in ("tm_r", "tm_k", "tm_v", "tm_g", "tm_w",
                               "tm_o", "w_bias")}


def _cm_params(lp):
    return {k: lp[k] for k in ("cm_k", "cm_v")}


def prefill_chunk_step(params, cfg: ModelConfig, state, h: jax.Array,
                       positions: jax.Array, table=None, slot=None, *,
                       cache_len: int,
                       kv_format: str = DEFAULT_KV_FORMAT,
                       attn_path: str = "gather", kv_partitions=None,
                       live_pages=None):
    """One chunked-prefill step for one slot — the single prefill path for
    every architecture family.

    h: (1, C, d) embedding chunk (token embeds, or vision-prefix embeds for
    the leading positions — the engine builds the combined stream);
    positions: (1, C) absolute positions, -1 = padding in the final chunk;
    table: (1, T) the slot's block table (None for attention-free rwkv);
    slot: scalar int32 row index into the batched decode state — recurrent
    carries (rwkv wkv/shift/cm_shift, hybrid ssm) and enc-dec cross-KV are
    per-slot leaves, gathered with ``dynamic_slice_in_dim`` outside the
    layer scan, threaded through as scan xs/ys, and scattered back after.

    Attention families attend the window on ``attn_path`` — ``"gather"``
    materializes it and runs ``attention.prefix_chunk_attention``,
    ``"fused"`` one-passes the pooled pages in the multi-query Pallas
    kernel (see ``_paged_chunk_attn``) — then scatter the chunk's K/V
    into the slot's pages; recurrent families step their masked
    scans (``rwkv.time_mix_seq`` / ``ssm.ssm_seq`` with ``valid``), so a
    right-padded final chunk leaves the carry at the last real token.

    Note on MoE: expert-capacity dropping is computed over the routing
    batch, so chunked prefill (C tokens at a time) can drop different
    tokens than a whole-prompt pass — semantically valid but not
    bit-identical unless ``moe_capacity_factor`` is lifted to full
    capacity (dense families are token-identical at any chunk size).

    Returns (last-valid-position logits (1, V) fp32, new state).
    """
    fmt = get_kv_format(kv_format)
    B, C, _ = h.shape
    valid = positions >= 0                            # (B, C)
    safe_pos = jnp.maximum(positions, 0)
    cache = state["cache"]

    def row(leaf):
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=1)

    def unrow(leaf, new):
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, new.astype(leaf.dtype), slot, axis=1)

    if cfg.family == "rwkv":
        xs = (params["layers"], row(cache["wkv"]), row(cache["shift"]),
              row(cache["cm_shift"]))

        def body(hc, xs_):
            lp, wkv_l, sh_l, cm_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            tm, st = rwkv.time_mix_seq(
                _tm_params(lp), x1, {"wkv": wkv_l, "shift": sh_l},
                num_heads=cfg.num_heads, cfg=cfg, valid=valid)
            hc = hc + tm
            x2 = _norm(cfg, lp["norm2"], hc)
            prev = jnp.concatenate(
                [cm_l.astype(x2.dtype)[:, None], x2[:, :-1]], axis=1)
            hc = hc + rwkv.channel_mix(_cm_params(lp), x2, prev, cfg)
            last = jnp.maximum(jnp.sum(valid.astype(jnp.int32), 1) - 1, 0)
            cm_new = jnp.take_along_axis(x2, last[:, None, None], axis=1)[:, 0]
            cm_new = jnp.where(valid.any(1)[:, None],
                               cm_new.astype(jnp.float32), cm_l)
            return hc, (st["wkv"], st["shift"], cm_new)

        h, (wkv_n, sh_n, cm_n) = jax.lax.scan(body, h, xs)
        new_cache = dict(cache, wkv=unrow(cache["wkv"], wkv_n),
                         shift=unrow(cache["shift"], sh_n),
                         cm_shift=unrow(cache["cm_shift"], cm_n))
        new_state = dict(state, cache=new_cache)
    elif cfg.family == "hybrid":
        xs = (params["layers"], cache["kv"], row(cache["ssm"]))

        def body(hc, xs_):
            lp, pool, ssm_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, table, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=False,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            s_out, s_fin = ssm.ssm_seq(lp["ssm"], x1, ssm_l, cfg, valid=valid)
            hc = hc + 0.5 * (a + s_out)
            return _ffn_seq(lp, cfg, hc), (pool, s_fin)

        h, (new_pool, ssm_n) = jax.lax.scan(body, h, xs)
        new_state = dict(state, cache=dict(cache, kv=new_pool,
                                           ssm=unrow(cache["ssm"], ssm_n)))
    elif cfg.family == "encdec":
        xs = (params["layers"], cache["kv"], row(state["enc_kv"][0]),
              row(state["enc_kv"][1]))

        def body(hc, xs_):
            lp, pool, ek_l, ev_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, table, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=False,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            hc = hc + a
            hc = hc + _cross_attn_seq(
                lp["cross"], cfg, _norm(cfg, lp["norm3"], hc), (ek_l, ev_l))
            return _ffn_seq(lp, cfg, hc), pool

        h, new_pool = jax.lax.scan(body, h, xs)
        new_state = dict(state, cache=dict(cache, kv=new_pool))
    else:

        def body(hc, xs_):
            lp, pool = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, table, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=False,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            return _ffn_seq(lp, cfg, hc + a), pool

        h, new_pool = jax.lax.scan(body, h, (params["layers"], cache["kv"]))
        new_state = dict(state, cache=dict(cache, kv=new_pool))

    with jax.named_scope("head"):
        h = _norm(cfg, params["final_norm"], h)
        logits = _logits_head(params, cfg, _last_valid_row(h, positions))
    return logits, new_state


def verify_step(params, cfg: ModelConfig, state, tokens: jax.Array,
                positions: jax.Array, tables=None, *,
                cache_len: int, kv_format: str = DEFAULT_KV_FORMAT,
                attn_path: str = "gather", kv_partitions=None,
                live_pages=None):
    """Batched speculative-verify step — every family.

    tokens: (B, C) int32 — per slot, the last emitted token followed by up
    to C-1 draft tokens; positions: (B, C) absolute, -1 = padding (short
    proposals, inactive rows); tables: (B, T) block tables (None for
    attention-free rwkv). One forward pass scores every position of every
    slot with the same math as chunked prefill, so greedy acceptance
    against the returned per-position argmax is token-identical to plain
    decode.

    Attention families: rejected drafts leave stale pool entries *above*
    each slot's accepted frontier; their tags exceed every later query
    position until the next verify window overwrites them, so the masks
    (``win.pos < start`` here, ``kpos <= qpos`` in decode) keep them
    invisible throughout — the engine rolls pages back at the allocator.

    Carry families can't roll back by masking — the recurrence folds every
    consumed token into one state — so their carries are *checkpointed*:
    the third return value stacks, per leaf, C+1 snapshots along a new
    axis 2 (index 0 = the incoming carry, index n = the carry after
    consuming n window positions; rwkv shift/cm_shift checkpoints are the
    per-position x1/x2 rows the decode step would have latched). The
    engine selects index ``1 + accepted`` per row (0 for inactive rows)
    and writes it back — ``state``'s own carry leaves are returned
    UNCHANGED so the selection is the only write. Third value is None for
    attention-only families.

    Returns (logits (B, C, V) fp32, new state, carries-or-None).
    """
    fmt = get_kv_format(kv_format)
    h = layers.embed(params["embed"], jnp.maximum(tokens, 0))   # (B, C, d)
    B, C, _ = h.shape
    valid = positions >= 0
    safe_pos = jnp.maximum(positions, 0)
    cache = state["cache"]
    carries = None

    if cfg.family == "rwkv":
        xs = (params["layers"], cache["wkv"], cache["shift"],
              cache["cm_shift"])

        def body(hc, xs_):
            lp, wkv_l, sh_l, cm_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            tm, _st, wkv_steps = rwkv.time_mix_seq(
                _tm_params(lp), x1, {"wkv": wkv_l, "shift": sh_l},
                num_heads=cfg.num_heads, cfg=cfg, valid=valid,
                collect_states=True)
            hc = hc + tm
            x2 = _norm(cfg, lp["norm2"], hc)
            prev = jnp.concatenate(
                [cm_l.astype(x2.dtype)[:, None], x2[:, :-1]], axis=1)
            hc = hc + rwkv.channel_mix(_cm_params(lp), x2, prev, cfg)
            # checkpoint n = carry after n consumed positions; the decode
            # step latches shift=x1 and cm_shift=x2 at each token
            wkv_s = jnp.concatenate([wkv_l[:, None], wkv_steps], axis=1)
            sh_s = jnp.concatenate(
                [sh_l[:, None], x1.astype(jnp.float32)], axis=1)
            cm_s = jnp.concatenate(
                [cm_l[:, None], x2.astype(jnp.float32)], axis=1)
            return hc, (wkv_s, sh_s, cm_s)

        h, (wkv_s, sh_s, cm_s) = jax.lax.scan(body, h, xs)
        carries = {"wkv": wkv_s, "shift": sh_s, "cm_shift": cm_s}
        new_state = state
    elif cfg.family == "hybrid":
        xs = (params["layers"], cache["kv"], cache["ssm"])

        def body(hc, xs_):
            lp, pool, ssm_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, tables, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=True,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            s_out, _s_fin, s_steps = ssm.ssm_seq(
                lp["ssm"], x1, ssm_l, cfg, valid=valid, collect_states=True)
            hc = hc + 0.5 * (a + s_out)
            ssm_s = jnp.concatenate([ssm_l[:, None], s_steps], axis=1)
            return _ffn_seq(lp, cfg, hc), (pool, ssm_s)

        h, (new_pool, ssm_s) = jax.lax.scan(body, h, xs)
        carries = {"ssm": ssm_s}
        new_state = dict(state, cache=dict(cache, kv=new_pool))
    elif cfg.family == "encdec":
        xs = (params["layers"], cache["kv"], state["enc_kv"][0],
              state["enc_kv"][1])

        def body(hc, xs_):
            lp, pool, ek_l, ev_l = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, tables, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=True,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            hc = hc + a
            hc = hc + _cross_attn_seq(
                lp["cross"], cfg, _norm(cfg, lp["norm3"], hc), (ek_l, ev_l))
            return _ffn_seq(lp, cfg, hc), pool

        h, new_pool = jax.lax.scan(body, h, xs)
        new_state = dict(state, cache=dict(cache, kv=new_pool))
    else:

        def body(hc, xs_):
            lp, pool = xs_
            hc = layers.shard_hint(hc, "bsd")
            x1 = _norm(cfg, lp["norm1"], hc)
            a, pool = _paged_chunk_attn(
                lp["attn"], cfg, x1, pool, tables, positions, safe_pos,
                fmt=fmt, cache_len=cache_len, batched=True,
                attn_path=attn_path, kv_partitions=kv_partitions,
                live_pages=live_pages)
            return _ffn_seq(lp, cfg, hc + a), pool

        h, new_pool = jax.lax.scan(body, h, (params["layers"], cache["kv"]))
        new_state = dict(state, cache=dict(cache, kv=new_pool))

    with jax.named_scope("head"):
        logits = _logits_head(params, cfg,
                              _norm(cfg, params["final_norm"], h))
    return logits, new_state, carries


def init_decode_state(cfg: ModelConfig, batch: int, cache_len: int):
    """Fresh (empty) decode state — used when lowering decode shapes directly."""
    L = cfg.num_layers

    def stack(x):
        return jnp.broadcast_to(x, (L,) + x.shape)

    if cfg.family == "rwkv":
        st = rwkv.rwkv_state_init(batch, cfg.d_model, cfg.num_heads)
        cache = jax.tree.map(stack, dict(
            st, cm_shift=jnp.zeros((batch, cfg.d_model), jnp.float32)))
    else:
        kv = attention.init_cache(batch, cache_len, cfg.num_kv_heads,
                                  cfg.head_dim, cfg.dtype)
        entry = {"kv": kv}
        if cfg.family == "hybrid":
            entry["ssm"] = ssm.ssm_state_init(batch, cfg.d_inner,
                                              cfg.ssm_state)
        cache = jax.tree.map(stack, entry)
    state = {"cache": cache}
    if cfg.family == "encdec":
        state["enc_kv"] = (
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                       cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                       cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        )
    return state


def init_paged_state(cfg: ModelConfig, batch: int, cache_len: int, *,
                     page_size: int, num_blocks: int,
                     kv_format: str = DEFAULT_KV_FORMAT):
    """Paged decode state: one shared block pool instead of per-slot rings.

    The per-layer KV entry is a :class:`kvcache.PagedKVCache` of
    ``num_blocks × page_size`` token slots (stacked over L like every other
    decode-state leaf); per-slot block tables live OUTSIDE the state — the
    engine passes them as a step input. Recurrent families (rwkv) hold no
    KV cache and fall through to the ring state unchanged; hybrid/encdec
    keep their ssm / enc_kv leaves per-slot as before.
    """
    if cfg.family == "rwkv":
        return init_decode_state(cfg, batch, cache_len)
    kvc.pages_per_slot(cache_len, page_size)       # validate the multiple
    L = cfg.num_layers

    def stack(x):
        return jnp.broadcast_to(x, (L,) + x.shape)

    pool = kvc.init_pool(num_blocks, page_size, cfg.num_kv_heads,
                         cfg.head_dim, cfg.dtype, kv_format)
    entry = {"kv": pool}
    if cfg.family == "hybrid":
        entry["ssm"] = ssm.ssm_state_init(batch, cfg.d_inner, cfg.ssm_state)
    state = {"cache": jax.tree.map(stack, entry)}
    if cfg.family == "encdec":
        state["enc_kv"] = (
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                       cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_seq,
                       cfg.num_kv_heads, cfg.head_dim), cfg.dtype),
        )
    return state
