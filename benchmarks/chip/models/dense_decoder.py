"""Architecture module of a dense decoder-only transformer (h2o-danube-1.8b):
every layer the same, grouped-query attention under one sliding window and a
gated MLP without biases, all seven matrices W4A16.

``raw``, the benchmark's own weights, is a flat dict of arrays:

- ``embed`` (V, d), ``lm_head`` (d, V), ``final_norm`` (d,), bfloat16;
- ``norm1``, ``norm2`` (L, d) bfloat16;
- for each matrix ``wq wk wv wo w_gate w_up w_down``: ``<name>.packed``
  (L, K/2, N) int8 and ``<name>.scales`` (L, K/128, N) float32, made as
  :mod:`chipbench.weights` says.

:func:`program_params` hands the same arrays to the program in its pytree,
and :func:`program_config` gives the program's ``ModelConfig``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from chipbench import weights, work

REDUCED = dict(num_hidden_layers=2, hidden_size=128, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, head_dim=32,
               vocab_size=512, prefill_chunk=8, page_size=8)


def matrices(cfgj: dict) -> dict:
    """name -> (K, N) of every W4A16 matrix of one layer."""
    d, ff = cfgj["hidden_size"], cfgj["intermediate_size"]
    q = cfgj["num_attention_heads"] * cfgj["head_dim"]
    kv = cfgj["num_key_value_heads"] * cfgj["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    cfgj = dict(spec)
    L, d = cfgj["num_hidden_layers"], cfgj["hidden_size"]
    V = weights.padded_vocab(cfgj)
    mats = matrices(cfgj)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def layer(k):
        ks = jax.random.split(k, len(mats) + 2)
        out = {}
        for i, (name, (K, N)) in enumerate(sorted(mats.items())):
            w = jax.random.normal(ks[i], (K, N), jnp.float32) * K ** -0.5
            out[name + ".packed"], out[name + ".scales"] = \
                weights.quantize_pack(w)
        for j, name in enumerate(("norm1", "norm2")):
            out[name] = (1.0 + 0.1 * jax.random.normal(
                ks[len(mats) + j], (d,), jnp.float32)).astype(jnp.bfloat16)
        return out

    raw = jax.lax.map(layer, jax.random.split(k_layers, L))
    raw["embed"] = (0.02 * jax.random.normal(k_embed, (V, d), jnp.float32)
                    ).astype(jnp.bfloat16)
    raw["lm_head"] = (d ** -0.5 * jax.random.normal(
        k_head, (d, V), jnp.float32)).astype(jnp.bfloat16)
    raw["final_norm"] = (1.0 + 0.1 * jax.random.normal(
        k_norm, (d,), jnp.float32)).astype(jnp.bfloat16)
    return raw


def _spec(cfgj: dict) -> tuple:
    keys = ("num_hidden_layers", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size")
    return tuple((k, cfgj[k]) for k in keys)


def make_raw(cfgj: dict, seed: int) -> dict:
    """All weights of ``cfgj`` from ``seed``, on the default device, in one
    jitted call, one layer at a time inside it."""
    raw = _make(weights.seed_key(seed), _spec(cfgj))
    jax.block_until_ready(raw)
    return raw


def program_params(raw: dict, cfgj: dict, quant_format: str = "w4a16_g128"):
    """The program's parameter pytree over the same device arrays.

    ``quant_format`` names the program's format the packed int4 matrices
    are handed over as: ``w4a8_g128`` stores the same bytes and scales and
    makes the program quantize its activations to int8 (the control).
    """
    from repro.core.quant import QuantizedTensor, get_format

    fmt = get_format(quant_format)

    def qt(name):
        return {"kernel": QuantizedTensor(
            raw[name + ".packed"], raw[name + ".scales"], None,
            weights.GROUP, jnp.dtype(jnp.bfloat16), fmt)}

    attn = {n: qt(n) for n in ("wq", "wk", "wv", "wo")}
    mlp = {n: qt(n) for n in matrices(cfgj) if n.startswith("w_")}
    return {
        "embed": {"table": raw["embed"]},
        "final_norm": {"scale": raw["final_norm"]},
        "layers": {"norm1": {"scale": raw["norm1"]},
                   "norm2": {"scale": raw["norm2"]},
                   "attn": attn, "mlp": mlp},
        "lm_head": {"kernel": raw["lm_head"]},
    }


def program_config(cfgj: dict, quant_format: str):
    """The program's ModelConfig for ``cfgj``: its named configuration with
    every size taken from the benchmark's file."""
    from repro import configs

    cfg = configs.get_config(cfgj["program_config"])
    return dataclasses.replace(
        cfg, num_layers=cfgj["num_hidden_layers"],
        d_model=cfgj["hidden_size"], d_ff=cfgj["intermediate_size"],
        num_heads=cfgj["num_attention_heads"],
        num_kv_heads=cfgj["num_key_value_heads"],
        head_dim=cfgj["head_dim"], vocab_size=cfgj["vocab_size"],
        sliding_window=cfgj["sliding_window"],
        rope_theta=cfgj["rope_theta"],
        mlp_type="swiglu",
        norm_type="rmsnorm", tie_embeddings=False, dtype=jnp.bfloat16,
        quant_format=quant_format, w4a16_strategy="auto")


def gemm_call(cfgj: dict, rows: int) -> tuple:
    """(flops, bytes) of one model pass's W4A16 GEMMs over ``rows`` tokens."""
    L = cfgj["num_hidden_layers"]
    flops = nbytes = 0
    for K, N in matrices(cfgj).values():
        f, b = work.w4a16_gemm(rows, K, N)
        flops += f
        nbytes += b
    return L * flops, L * nbytes


def attn_decode(cfgj: dict, positions) -> tuple:
    """(flops, bytes) of one decode step's attention, rows at ``positions``,
    each over ``min(position + 1, sliding_window)`` keys in every layer."""
    w = cfgj["sliding_window"]
    L = cfgj["num_hidden_layers"]
    flops = nbytes = 0
    for p in positions:
        f, b = work.attn_row(cfgj["num_attention_heads"],
                             cfgj["num_key_value_heads"], cfgj["head_dim"],
                             min(p + 1, w) if w else p + 1)
        flops += f
        nbytes += b
    return L * flops, L * nbytes


def model_flops(cfgj: dict, rows: int, positions) -> float:
    """Model FLOPs of ``rows`` tokens at ``positions``: every weight
    matmul (the head included) and attention over each token's context."""
    d, V = cfgj["hidden_size"], weights.padded_vocab(cfgj)
    gemm, _ = gemm_call(cfgj, rows)
    attn, _ = attn_decode(cfgj, positions)
    return gemm + attn + 2 * rows * d * V


def reduced(cfgj: dict) -> dict:
    """``cfgj`` at the sizes the CPU tests serve: 2 layers, width 128, 4/2
    heads of 32, vocabulary 512, pages and chunks of 8, a window of 16."""
    out = dict(cfgj, **REDUCED)
    if out["sliding_window"]:
        out["sliding_window"] = 16
    return out
