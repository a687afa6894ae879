"""Random weights from the seed, made on the device in the type they serve in.

The benchmark makes the weights itself, so that the plain reference can use
them without taking anything the program made: every W4A16 matrix is a
Gaussian of variance 1/K quantized per group of 128 rows to int4 (symmetric,
scale = group max / 7) and packed two to a byte along K, low nibble first,
with float32 group scales (:func:`quantize_pack`). Each architecture module
(``models/<name>.py``) makes its model's weights with these helpers in one
jitted call, one layer at a time inside it, so no float copy of the whole
model is ever held.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

GROUP = 128


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed, large ones included."""
    word = np.random.SeedSequence(int(seed)).generate_state(1)[0]
    return jax.random.PRNGKey(int(word) & 0x7FFFFFFF)


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


def nbytes(raw: dict) -> int:
    return sum(int(a.size) * a.dtype.itemsize for a in raw.values())
