"""Compile the serving path's Pallas kernels for a described TPU v5e.

Interpret mode (what every other kernel test runs) cannot see the TPU
compiler's tiling-alignment and VMEM refusals. These tests lower each
main-path kernel at h2o-danube-1.8b widths (d_model 2560, d_ff 6912,
GQA 32/8, head_dim 80, page_size 8, prefill chunk 32) for one chip of a
``v5e:2x2`` topology that is described, not attached, and assert that the
Mosaic kernel survives into the compiled program (``tpu_custom_call``).
One test compiles the engine's whole decode step, to check the named
scopes the chip benchmark reads and the ops its kernel jobs assign.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import dataclasses
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import quant
from repro.models import transformer as T
from repro.runtime import kvcache as kvc
from repro.runtime.engine import ServingEngine

CFG = configs.get_config("h2o-danube-1.8b")
PAGE_SIZE = 8          # launch/presets.py SERVE_PRESETS["h2o-danube-1.8b"]
CHUNK = 32             # the same preset's prefill_chunk
SLOTS = 8
PAGES_PER_SLOT = 42    # cache_len 336 = a 300-token prompt + 32 generated


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _on(sharding, tree):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _assert_kernel_compiles(fn, *abstract_args):
    text = jax.jit(fn).lower(*abstract_args).compile().as_text()
    assert "tpu_custom_call" in text


def _weight(K, N, fmt_name):
    w = jax.ShapeDtypeStruct((K, N), jnp.bfloat16)
    return jax.eval_shape(
        lambda w: quant.quantize(w, fmt_name, out_dtype=jnp.bfloat16), w)


GEMMS = [
    # (kernel, format, M, K, N, split_k)
    ("w4a16_fused", "w4a16_g128", SLOTS, CFG.d_model, CFG.d_ff, 1),
    ("w4a16_fused", "w4a16_g128", SLOTS, CFG.d_model, CFG.d_ff, 4),
    ("w4a16_fused", "w4a16_g128", CHUNK, CFG.d_ff, CFG.d_model, 1),
    ("w4a16_fused", "w4a16_g128", SLOTS,
     CFG.d_model, CFG.num_kv_heads * CFG.head_dim, 2),
    ("w4a16_decoupled", "w4a16_g128", CHUNK, CFG.d_model, CFG.d_ff, 4),
    ("w8a16_fused", "w8a16_channel", SLOTS, CFG.d_model, CFG.d_ff, 1),
    ("w4a8_fused", "w4a8_g128", SLOTS, CFG.d_model, CFG.d_ff, 1),
]


@pytest.mark.parametrize("kernel,fmt,M,K,N,split_k", GEMMS)
def test_gemm_compiles_for_v5e(one_chip, no_persistent_cache,
                               kernel, fmt, M, K, N, split_k):
    import importlib
    fn = getattr(importlib.import_module(f"repro.kernels.{kernel}"), kernel)
    x = jax.ShapeDtypeStruct((M, K), jnp.bfloat16, sharding=one_chip)
    qt = _on(one_chip, _weight(K, N, fmt))
    _assert_kernel_compiles(
        lambda x, qt: fn(x, qt, split_k=split_k, interpret=False), x, qt)


def _pool(kv_format):
    nb = 1 + SLOTS * PAGES_PER_SLOT
    return jax.eval_shape(lambda: kvc.init_pool(
        nb, PAGE_SIZE, CFG.num_kv_heads, CFG.head_dim, jnp.bfloat16,
        kv_format=kv_format))


@pytest.mark.parametrize("kv_partitions", [1, 2])
@pytest.mark.parametrize("kv_format", ["kv_fp16", "kv8_channel"])
def test_paged_decode_attention_compiles_for_v5e(
        one_chip, no_persistent_cache, kv_format, kv_partitions):
    from repro.kernels.paged_attention import fused_paged_attention
    fmt = quant.get_kv_format(kv_format)
    q = jax.ShapeDtypeStruct((SLOTS, CFG.num_heads, CFG.head_dim),
                             jnp.bfloat16, sharding=one_chip)
    tables = jax.ShapeDtypeStruct((SLOTS, PAGES_PER_SLOT), jnp.int32,
                                  sharding=one_chip)
    pos = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)

    def step(q, pool, tables, pos):
        return fused_paged_attention(
            q, pool, tables, pos, window=CFG.sliding_window, fmt=fmt,
            out_dtype=jnp.bfloat16, kv_partitions=kv_partitions,
            interpret=False)

    _assert_kernel_compiles(step, q, _on(one_chip, _pool(kv_format)),
                            tables, pos)


@pytest.mark.parametrize("kv_format", ["kv_fp16", "kv8_channel"])
def test_paged_chunk_attention_compiles_for_v5e(
        one_chip, no_persistent_cache, kv_format):
    from repro.kernels.paged_attention import fused_chunk_attention
    fmt = quant.get_kv_format(kv_format)
    Hq, Hkv, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    q = jax.ShapeDtypeStruct((1, CHUNK, Hq, D), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, CHUNK, Hkv, D), jnp.bfloat16,
                               sharding=one_chip)
    tables = jax.ShapeDtypeStruct((1, PAGES_PER_SLOT), jnp.int32,
                                  sharding=one_chip)
    positions = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32,
                                     sharding=one_chip)

    def step(q, kseg, vseg, pool, tables, positions):
        return fused_chunk_attention(
            q, kseg, vseg, pool, tables, positions,
            window=CFG.sliding_window, fmt=fmt, out_dtype=jnp.bfloat16,
            interpret=False)

    _assert_kernel_compiles(step, q, seg, seg,
                            _on(one_chip, _pool(kv_format)), tables,
                            positions)


# danube.long's decode: 4 slots past the 4096 window, T = 4096 / 8 pages
CELL_SLOTS, CELL_PAGES = 4, 512


@pytest.mark.parametrize("regime", ["decode", "chunk"])
@pytest.mark.parametrize("kv_format", ["kv_fp16", "kv8_channel"])
def test_paged_attention_cell_shapes_compile_for_v5e(
        one_chip, no_persistent_cache, kv_format, regime):
    """The fused kernel at the benchmark cell's shapes (B 4, T 512, pages
    of 8, GQA 32/8 x 80): decode at the planned page blocks, and one
    slot's 32-token chunk over the same table."""
    from repro.kernels.paged_attention import (fused_chunk_attention,
                                               fused_paged_attention)
    fmt = quant.get_kv_format(kv_format)
    Hq, Hkv, D = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
    pool = _on(one_chip, jax.eval_shape(lambda: kvc.init_pool(
        1 + CELL_SLOTS * CELL_PAGES, PAGE_SIZE, Hkv, D, jnp.bfloat16,
        kv_format=kv_format)))
    B = CELL_SLOTS if regime == "decode" else 1
    tables = jax.ShapeDtypeStruct((B, CELL_PAGES), jnp.int32,
                                  sharding=one_chip)
    kw = dict(window=CFG.sliding_window, fmt=fmt, out_dtype=jnp.bfloat16,
              interpret=False)
    if regime == "decode":
        q = jax.ShapeDtypeStruct((B, Hq, D), jnp.bfloat16, sharding=one_chip)
        pos = jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip)
        _assert_kernel_compiles(
            lambda q, pool, tables, pos: fused_paged_attention(
                q, pool, tables, pos, **kw), q, pool, tables, pos)
        return
    q = jax.ShapeDtypeStruct((1, CHUNK, Hq, D), jnp.bfloat16,
                             sharding=one_chip)
    seg = jax.ShapeDtypeStruct((1, CHUNK, Hkv, D), jnp.bfloat16,
                               sharding=one_chip)
    positions = jax.ShapeDtypeStruct((1, CHUNK), jnp.int32,
                                     sharding=one_chip)
    _assert_kernel_compiles(
        lambda q, k, v, pool, tables, p: fused_chunk_attention(
            q, k, v, pool, tables, p, **kw),
        q, seg, seg, pool, tables, positions)


# custom calls of the 2-layer decode step that each kernel job's patterns
# assign (a scanned layer, so one of each per layer kind): counted on the
# tree before the step programs named their scopes and the paged-attention
# kernel took its name, and held here
JOB_CALLS = {"gemm_w4a16": 7, "paged_attention": 1}
STEP_SCOPES = ("attn_qkv", "kv_write", "attn_core", "attn_out", "mlp",
               "head", "sample")


def test_decode_step_scopes_and_kernel_jobs_for_v5e(
        one_chip, no_persistent_cache, monkeypatch):
    """The engine's danube-width decode step (2 of its 24 layers, fused
    attention, planned as on one chip) compiled for a v5e: every named
    scope reaches the ops' metadata, and the benchmark's kernel jobs
    assign the custom calls they assigned before there were scopes."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = dataclasses.replace(CFG, num_layers=2, w4a16_strategy="auto")
    params = jax.eval_shape(lambda: T.quantize_params(
        T.init_params(jax.random.PRNGKey(0), cfg), cfg))
    eng = ServingEngine(cfg, params, max_batch=SLOTS, max_prompt_len=300,
                        max_new_tokens=32, page_size=PAGE_SIZE,
                        prefill_chunk=CHUNK, attn_path="fused")
    text = eng._serve_step().lower(
        _on(one_chip, params),
        _on(one_chip, eng._serve_inputs_abstract())).compile().as_text()
    names = set(re.findall(r'op_name="[^"]*/(' + "|".join(STEP_SCOPES)
                           + r')/', text))
    assert names == set(STEP_SCOPES)

    bench = os.path.join(os.path.dirname(__file__), "..", "benchmarks",
                         "chip")
    sys.path.insert(0, bench)
    try:
        from chipbench import jobs
    finally:
        sys.path.remove(bench)
    calls = [line.strip().removeprefix("ROOT ")
             for line in text.splitlines() if " custom-call(" in line]
    for name, want in JOB_CALLS.items():
        job = jobs.load(bench, name)
        ops = [c for c in calls if job.PATTERN.search(c)
               and not (job.NOT and job.NOT.search(c))]
        assert len(ops) == want, (name, ops)
