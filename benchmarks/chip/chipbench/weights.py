"""Random weights from the seed, made on the device in the type they serve in.

The benchmark makes the weights itself, so that the plain reference can use
them without taking anything the program made: every W4A16 matrix is a
Gaussian of variance 1/K quantized per group of 128 rows to int4 (symmetric,
scale = group max / 7) and packed two to a byte along K, low nibble first,
with float32 group scales. One jitted call makes them all, one layer at a
time inside it, so no float copy of the whole model is ever held.

``raw`` is the benchmark's own layout, a flat dict of arrays:

- ``embed`` (V, d), ``lm_head`` (d, V), ``final_norm`` (d,), bfloat16;
- ``norm1``, ``norm2`` (L, d) bfloat16;
- for each matrix ``wq wk wv wo w_gate w_up w_down`` (a gated MLP without
  biases): ``<name>.packed`` (L, K/2, N) int8 and ``<name>.scales``
  (L, K/128, N) float32.

:func:`program_params` hands the same arrays to the program in its pytree.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 128


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, large ones included."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


def matrices(cfgj: dict) -> dict:
    """name -> (K, N) of every W4A16 matrix of one layer."""
    d, ff = cfgj["hidden_size"], cfgj["intermediate_size"]
    q = cfgj["num_attention_heads"] * cfgj["head_dim"]
    kv = cfgj["num_key_value_heads"] * cfgj["head_dim"]
    return {"wq": (d, q), "wk": (d, kv), "wv": (d, kv), "wo": (q, d),
            "w_gate": (d, ff), "w_up": (d, ff), "w_down": (ff, d)}


def padded_vocab(cfgj: dict) -> int:
    return -(-cfgj["vocab_size"] // 256) * 256


def quantize_pack(w: jax.Array):
    """(K, N) float32 -> (packed (K/2, N) int8, scales (K/128, N) float32)."""
    K, N = w.shape
    g = w.reshape(K // GROUP, GROUP, N)
    s = jnp.maximum(jnp.max(jnp.abs(g), axis=1, keepdims=True) / 7.0, 1e-8)
    q = jnp.clip(jnp.round(g / s), -8, 7).astype(jnp.int32).reshape(K, N)
    lo = q[0::2] & 0xF
    hi = q[1::2] & 0xF
    packed = ((hi << 4) | lo).astype(jnp.uint8).astype(jnp.int8)
    return packed, s[:, 0]


@functools.partial(jax.jit, static_argnums=(1,))
def _make(key, spec):
    cfgj = dict(spec)
    L, d = cfgj["num_hidden_layers"], cfgj["hidden_size"]
    V = padded_vocab(cfgj)
    mats = matrices(cfgj)
    k_embed, k_head, k_norm, k_layers = jax.random.split(key, 4)

    def layer(k):
        ks = jax.random.split(k, len(mats) + 2)
        out = {}
        for i, (name, (K, N)) in enumerate(sorted(mats.items())):
            w = jax.random.normal(ks[i], (K, N), jnp.float32) * K ** -0.5
            out[name + ".packed"], out[name + ".scales"] = quantize_pack(w)
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
    """All weights of ``cfgj`` from ``seed``, on the default device."""
    raw = _make(seed_key(seed), _spec(cfgj))
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
            raw[name + ".packed"], raw[name + ".scales"], None, GROUP,
            jnp.dtype(jnp.bfloat16), fmt)}

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


def nbytes(raw: dict) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in raw.values())
