"""Benchmark harness — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # all benches
    PYTHONPATH=src python -m benchmarks.run fig2 fig3  # a subset
    PYTHONPATH=src python -m benchmarks.run --quick    # CI perf snapshot ->
                                                       # BENCH_quickstart.json
                                                       # + BENCH_formats.json

Prints ``name,us_per_call,derived`` CSV rows per the repo convention.
Wall-clock rows are CPU interpret-mode trends (kernel-correctness-level
numbers); the calibrated Ascend model provides the paper-figure
reproduction, and the TPU roofline (benchmarks/roofline.py over the dry-run
records) provides the target-hardware numbers. ``--format`` runs the
kernel/quick benches under any registered QuantFormat.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.configs import PAPER_BATCH_SIZES, PAPER_GEMM_SHAPES
from repro.core import costmodel as cm
from repro.core import quant
from repro.core.quant import quantize
from repro.kernels import planning
from repro.kernels.gemm import gemm
from repro.launch import compile_cache

BENCH_FORMAT = quant.DEFAULT_FORMAT      # set by main() from --format


def _time(fn, *args, warmup=1, iters=3):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6      # µs


# ---------------------------------------------------------------------------
# Figure 2 — Split-K vs Data-Parallel across N×K and batch sizes
# ---------------------------------------------------------------------------

def bench_fig2_splitk_vs_dataparallel():
    """Execution time of INT4×FP16 for the paper's N×K grid (Ascend model),
    comparing Split-K against data-parallel — reproduces Fig. 2."""
    print("# fig2: name,us_per_call,derived(speedup_dp_over_splitk)")
    for (N, K) in PAPER_GEMM_SHAPES:
        for M in PAPER_BATCH_SIZES:
            t_dp = cm.w4a16_time_ascend(M, N, K, split_k=1) * 1e6
            s = cm.best_split_k_ascend(M, N, K)
            t_sk = cm.w4a16_time_ascend(M, N, K, split_k=s) * 1e6
            print(f"fig2/ascend_model/N{N}_K{K}_M{M}_S{s},"
                  f"{t_sk:.2f},{t_dp / t_sk:.3f}")


# ---------------------------------------------------------------------------
# Figure 3 — W4A16 speedup over native FP16
# ---------------------------------------------------------------------------

def bench_fig3_w4a16_vs_fp16():
    """Speedup of Split-K INT4×FP16 over FP16×FP16 (Ascend model) plus the
    TPU-v5e fused/decoupled comparison — reproduces Fig. 3 and the
    DESIGN.md adaptation claim."""
    print("# fig3: name,us_per_call,derived(speedup_over_fp16)")
    cap = 0.0
    for (N, K) in PAPER_GEMM_SHAPES:
        for M in PAPER_BATCH_SIZES:
            sp = cm.w4a16_speedup_ascend(M, N, K)
            cap = max(cap, sp)
            t = cm.w4a16_time_ascend(
                M, N, K, split_k=cm.best_split_k_ascend(M, N, K)) * 1e6
            print(f"fig3/ascend_model/N{N}_K{K}_M{M},{t:.2f},{sp:.3f}")
    print(f"fig3/ascend_model/max_speedup,0.0,{cap:.3f}  # paper: 1.48")
    for (N, K) in PAPER_GEMM_SHAPES[:4]:
        for M in (1, 16, 256):
            f = cm.fp16_time_tpu(M, N, K)
            fu = cm.w4a16_time_tpu_fused(M, N, K)
            de = cm.w4a16_time_tpu_decoupled(M, N, K, split_k=4)
            print(f"fig3/tpu_fused/N{N}_K{K}_M{M},{fu*1e6:.2f},{f/fu:.3f}")
            print(f"fig3/tpu_decoupled/N{N}_K{K}_M{M},{de*1e6:.2f},"
                  f"{f/de:.3f}")


# ---------------------------------------------------------------------------
# Kernel wall-time (CPU interpret — correctness-level trend only)
# ---------------------------------------------------------------------------

def bench_kernel_walltime():
    """Interpret-mode wall time of the actual kernels on scaled-down paper
    shapes: every strategy that supports the benched QuantFormat vs native
    bf16 GEMM, all through the planned execute path."""
    fmt = quant.get_format(BENCH_FORMAT)
    strategies = list(planning.strategies_for_format(fmt.name))
    baseline = "xla" if "xla" in strategies else strategies[0]
    print(f"# kernels: name,us_per_call,derived(ratio_vs_{baseline})  "
          f"[format={fmt.name}]")
    key = jax.random.PRNGKey(0)
    for (N, K) in [(512, 4096), (1024, 2048)]:
        for M in (1, 16):
            w = jax.random.normal(key, (K, N), jnp.float32)
            x = jax.random.normal(key, (M, K), jnp.bfloat16)
            qt = quantize(w, fmt, out_dtype=jnp.bfloat16)
            problem = planning.MatmulProblem.from_operands(x, qt)
            plans = {s: planning.plan_matmul(problem, strategy=s)
                     for s in strategies}
            t_base = _time(lambda: planning.execute(plans[baseline], x, qt))
            for strat in strategies:
                if strat == baseline:
                    continue
                t = _time(lambda s=strat: planning.execute(
                    plans[s], x, qt))
                print(f"kernels/{strat}/N{N}_K{K}_M{M},{t:.1f},"
                      f"{t / t_base:.2f}")
            wd = w.astype(jnp.bfloat16)
            t_g = _time(lambda: gemm(x, wd))
            print(f"kernels/gemm_bf16/N{N}_K{K}_M{M},{t_g:.1f},"
                  f"{t_g / t_base:.2f}")


# ---------------------------------------------------------------------------
# Planner decisions across the paper's GEMM grid
# ---------------------------------------------------------------------------

def bench_plans():
    """What the cost-model planner picks per paper (N, K, M) cell, with the
    predicted cost of every registered strategy next to the winner."""
    print("# plans: name,us_per_call,derived(strategy/split_k)")
    for (N, K) in PAPER_GEMM_SHAPES:
        for M in PAPER_BATCH_SIZES:
            problem = planning.MatmulProblem(
                M=M, N=N, K=K, group_size=128, act_dtype="bfloat16",
                out_dtype="bfloat16", backend="tpu")
            plan = planning.plan_matmul(problem, use_cache=False)
            # each strategy costed against ITS OWN plan (split_k etc.) —
            # the comparison the planner actually ran (format-eligible
            # strategies only; forcing a mismatched pair is refused)
            per = {s: planning.plan_matmul(problem, strategy=s)
                   for s in planning.strategies_for_format(problem.format)}
            costs = ";".join(
                f"{s}={planning.get_strategy(s).cost(problem, p) * 1e6:.1f}us"
                for s, p in per.items())
            t = planning.get_strategy(plan.strategy).cost(
                problem, per[plan.strategy])
            print(f"plans/N{N}_K{K}_M{M},{t*1e6:.2f},"
                  f"{plan.strategy}/S{plan.split_k}  # {costs}")


# ---------------------------------------------------------------------------
# Memory-capacity table (the paper's "fit larger models" conclusion)
# ---------------------------------------------------------------------------

def bench_capacity():
    """Weight bytes per arch: FP16 vs W4A16 (+scales) — the capacity win."""
    from repro import configs as C
    print("# capacity: name,us_per_call,derived(compression_ratio)")
    for arch in C.ARCHS:
        cfg = C.get_config(arch)
        n = cfg.param_count()
        fp16 = 2 * n
        w4 = 0.5 * n + 4 * n / cfg.group_size            # + fp32 scales
        print(f"capacity/{arch},0.0,{fp16 / w4:.3f}  "
              f"# {fp16/1e9:.1f}GB -> {w4/1e9:.1f}GB")


# ---------------------------------------------------------------------------
# Quick CI snapshot: shapes → ms + achieved GB/s, persisted as JSON so every
# CI run leaves a perf-trajectory artifact (BENCH_quickstart.json)
# ---------------------------------------------------------------------------

def bench_quick(out_path: str = "BENCH_quickstart.json") -> dict:
    """Planned execute on scaled-down paper shapes: wall-clock ms and
    achieved GB/s (quantized weight + activation + output bytes / time),
    written to ``out_path`` for the CI artifact upload."""
    print(f"# quick: name,us_per_call,derived(GB/s)  [format={BENCH_FORMAT}]")
    fmt = quant.get_format(BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    cells = []
    for (N, K) in [(512, 4096), (1024, 2048)]:
        for M in (1, 16):
            w = jax.random.normal(key, (K, N), jnp.float32)
            x = jax.random.normal(key, (M, K), jnp.bfloat16)
            qt = quantize(w, fmt, out_dtype=jnp.bfloat16)
            problem = planning.MatmulProblem.from_operands(x, qt)
            plan = planning.plan_matmul(problem)
            t_us = _time(lambda: planning.execute(plan, x, qt))
            moved = qt.nbytes_packed() + x.nbytes + M * N * 2
            gbps = moved / (t_us * 1e-6) / 1e9
            name = f"quick/{plan.strategy}/N{N}_K{K}_M{M}"
            print(f"{name},{t_us:.1f},{gbps:.2f}")
            cells.append({"name": name, "M": M, "N": N, "K": K,
                          "strategy": plan.strategy,
                          "ms": round(t_us / 1e3, 4),
                          "gbps": round(gbps, 3)})
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# quick: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Fused-format sweep: the three Pallas fused kernels (w4a16/w8a16/w4a8) on
# the same shapes, persisted as BENCH_formats.json so the CI perf
# trajectory covers every format kernel from day one
# ---------------------------------------------------------------------------

_FUSED_BY_FORMAT = {
    "w4a16_g128": "fused",
    "w8a16_channel": "w8a16_fused",
    "w4a8_g128": "w4a8_fused",
}


def bench_formats(out_path: str = "BENCH_formats.json") -> dict:
    """Wall-clock of each format's fused Pallas kernel (interpret mode off
    TPU) next to the planner's pick for that format, per shape cell."""
    print("# formats: name,us_per_call,derived(GB/s)")
    key = jax.random.PRNGKey(0)
    cells = []
    for fmt_name, fused_strategy in _FUSED_BY_FORMAT.items():
        fmt = quant.get_format(fmt_name)
        for (N, K) in [(512, 2048)]:
            w = jax.random.normal(key, (K, N), jnp.float32)
            qt = quantize(w, fmt, out_dtype=jnp.bfloat16)
            for M in (1, 16):
                x = jax.random.normal(key, (M, K), jnp.bfloat16)
                problem = planning.MatmulProblem.from_operands(x, qt)
                plan = planning.plan_matmul(problem, strategy=fused_strategy)
                t_us = _time(lambda: planning.execute(plan, x, qt))
                moved = qt.nbytes_packed() + x.nbytes + M * N * 2
                gbps = moved / (t_us * 1e-6) / 1e9
                picked = planning.plan_matmul(problem, use_cache=False)
                name = f"formats/{fmt_name}/{fused_strategy}/N{N}_K{K}_M{M}"
                print(f"{name},{t_us:.1f},{gbps:.2f}")
                cells.append({
                    "name": name, "format": fmt_name, "M": M, "N": N, "K": K,
                    "strategy": fused_strategy,
                    "planner_pick": picked.strategy,
                    "ms": round(t_us / 1e3, 4), "gbps": round(gbps, 3)})
    blob = {"backend": jax.default_backend(), "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# formats: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Serving sweep: the continuous-batching engine end to end — tokens/sec at
# several slot counts, persisted as BENCH_serving.json (CI artifact). This
# is the LiquidGEMM lesson: kernel wins only count when a batched serving
# loop drives them.
# ---------------------------------------------------------------------------

def bench_serving(out_path: str = "BENCH_serving.json") -> dict:
    """Engine decode throughput/latency per slot count on a reduced arch
    (CPU trend numbers; the shapes scale with batch, the regime does not)."""
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.runtime.engine import Request, ServingEngine

    print("# serving: name,us_per_call,derived(tok/s)")
    arch, P, G = "h2o-danube-1.8b", 8, 8
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="auto",
                              quant_format=BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
    cells = []
    for B in (1, 2, 4):
        engine = ServingEngine(cfg, params, max_batch=B, max_prompt_len=P,
                               max_new_tokens=G)
        tokens = jax.random.randint(key, (B, P), 0, cfg.vocab_size)
        reqs = [Request(rid=i, prompt=tokens[i], max_new_tokens=G)
                for i in range(B)]
        report = engine.run(reqs)
        ms_step = (report.decode_s / max(len(report.step_records), 1)) * 1e3
        name = f"serving/{arch}/B{B}_P{P}_G{G}"
        print(f"{name},{ms_step*1e3:.1f},{report.tokens_per_s:.2f}")
        cells.append({
            "name": name, "arch": arch, "batch": B, "prompt_len": P,
            "gen": G, "steps": report.steps,
            "decode_tokens": report.decode_tokens,
            "ms_per_step": round(ms_step, 3),
            "tok_per_s": round(report.tokens_per_s, 3),
            "prefill_ms": round(report.prefill_s * 1e3, 3),
            "cache_len": engine.cache_len,
        })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# serving: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Paged-KV sweep: ring vs paged engine at several prefix-share ratios —
# the KV cache is the other HBM-bound serving tensor (PAPER/LiquidGEMM);
# this persists throughput + peak pages as BENCH_paged_kv.json (CI artifact)
# ---------------------------------------------------------------------------

def bench_paged_kv(out_path: str = "BENCH_paged_kv.json") -> dict:
    """Ring vs paged engine decode at three prefix-share ratios (fraction
    of requests repeating one prompt): tokens/sec, peak live pages, and
    the zero-sharing worst case — the paged cache's capacity win."""
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.runtime.engine import Request, ServingEngine

    print("# paged_kv: name,us_per_call,derived(tok/s)")
    arch, P, G, B, R = "h2o-danube-1.8b", 8, 8, 4, 4
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="auto",
                              quant_format=BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
    tokens = jax.random.randint(key, (R, P), 0, cfg.vocab_size)

    def requests(share_ratio):
        # the first ceil(share_ratio * R) requests repeat prompt 0
        n_shared = int(round(share_ratio * R))
        return [Request(rid=i,
                        prompt=tokens[0] if i < n_shared else tokens[i],
                        max_new_tokens=G) for i in range(R)]

    cells = []
    for ratio in (0.0, 0.5, 1.0):
        for mode in ("ring", "paged"):
            engine = ServingEngine(
                cfg, params, max_batch=B, max_prompt_len=P,
                max_new_tokens=G, paged=(mode == "paged"), page_size=4,
                prefill_chunk=4 if mode == "paged" else None)
            report = engine.run(requests(ratio))
            ms_step = (report.decode_s
                       / max(len(report.step_records), 1)) * 1e3
            name = f"paged_kv/{arch}/{mode}/share{ratio:.1f}"
            print(f"{name},{ms_step*1e3:.1f},{report.tokens_per_s:.2f}")
            cells.append({
                "name": name, "arch": arch, "mode": mode,
                "share_ratio": ratio, "batch": B, "prompt_len": P,
                "gen": G, "tok_per_s": round(report.tokens_per_s, 3),
                "ms_per_step": round(ms_step, 3),
                "prefill_ms": round(report.prefill_s * 1e3, 3),
                "peak_pages": report.peak_pages,
                "worst_case_pages": (engine.pages_slot * B
                                     if engine.paged else None),
                "cache_len": engine.cache_len,
            })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# paged_kv: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Speculative-decoding sweep: ngram-proposed verify vs plain paged decode at
# several prompt-repetition ratios — accepted-tokens/s is the figure of
# merit, persisted as BENCH_speculative.json (CI artifact)
# ---------------------------------------------------------------------------

def bench_speculative(out_path: str = "BENCH_speculative.json") -> dict:
    """Ngram self-speculation vs the plain paged engine on a dense arch
    (no SWA wrap clamp) at three prompt-repetition ratios. Each config is
    run twice on the same engine and the warmed run is measured, so the
    speedup column compares steady-state decode, not compile time.
    tok/s counts ACCEPTED tokens only — the honest speculative metric."""
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.runtime.engine import Request, ServingEngine

    print("# speculative: name,us_per_call,derived(speedup_vs_baseline)")
    arch, P, G, B, K = "starcoder2-7b", 16, 48, 4, 4
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="xla",
                              quant_format=BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)

    def requests(reps):
        # reps=1: fully random per-request prompts (the ngram worst case);
        # reps=r: one P/r segment tiled r times, SHARED across the batch —
        # the prompt-lookup regime code serving actually sees (repetitive
        # prompts + prefix sharing between concurrent requests)
        seg = max(2, P // reps)
        toks = jax.random.randint(jax.random.fold_in(key, reps),
                                  (B, seg), 0, cfg.vocab_size)
        return [Request(rid=i,
                        prompt=jnp.tile(toks[0 if reps > 1 else i],
                                        -(-P // seg))[:P],
                        max_new_tokens=G) for i in range(B)]

    def run(speculate, reps):
        engine = ServingEngine(cfg, params, max_batch=B, max_prompt_len=P,
                               max_new_tokens=G, page_size=8,
                               prefill_chunk=8, speculate=speculate,
                               spec_k=K)
        engine.run(requests(reps))               # warm: compile + plans
        return engine.run(requests(reps))

    cells = []
    for reps in (1, 2, 4):
        base = run(None, reps)
        rep = run("ngram", reps)
        speedup = rep.tokens_per_s / max(base.tokens_per_s, 1e-9)
        ms_step = (rep.decode_s / max(len(rep.step_records), 1)) * 1e3
        name = f"speculative/{arch}/ngram_k{K}/reps{reps}"
        print(f"{name},{ms_step*1e3:.1f},{speedup:.3f}")
        cells.append({
            "name": name, "arch": arch, "proposer": "ngram", "spec_k": K,
            "batch": B, "prompt_len": P, "gen": G, "prompt_reps": reps,
            "proposed_tokens": rep.proposed_tokens,
            "accepted_tokens": rep.accepted_tokens,
            "acceptance_rate": round(rep.acceptance_rate, 4),
            "steps": rep.steps, "baseline_steps": base.steps,
            "tok_per_s": round(rep.tokens_per_s, 3),
            "baseline_tok_per_s": round(base.tokens_per_s, 3),
            "speedup_vs_baseline": round(speedup, 4),
            "ms_per_step": round(ms_step, 3),
        })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "spec_k": K, "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# speculative: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Paged-attention sweep: ring vs gather vs fused decode attention across
# context lengths and KV formats — bytes-moved (the paper's bottleneck
# metric) and tok/s per path, plus what the planner picks per backend;
# persisted as BENCH_paged_attn.json (CI artifact)
# ---------------------------------------------------------------------------

def bench_paged_attn(out_path: str = "BENCH_paged_attn.json") -> dict:
    """Op-level paged-attention sweep: the dense ring read, the XLA
    block-table gather (two passes over the KV window), and the fused
    Pallas kernel (one pass, in-VMEM dequant) on identical KV contents —
    at decode (q_len=1) plus the multi-query regimes (prefill chunks and
    k+1 speculative verify). Wall rows are CPU-trend numbers (the fused
    kernel runs in interpret mode off-TPU); the bytes/roofline columns
    are the decision metric — the gather's per-call HBM window
    materialization is what the fused path deletes."""
    import dataclasses

    from repro.core import quant as q
    from repro.kernels.paged_attention import fused_paged_attention
    from repro.models import attention
    from repro.runtime import kvcache as kvc

    print("# paged_attn: name,us_per_call,derived(tok/s)")
    B, Hq, Hkv, D, ps = 2, 4, 2, 64, 32
    key = jax.random.PRNGKey(0)

    def build(ctx, fmt_name):
        fmt = q.get_kv_format(fmt_name)
        T = ctx // ps
        nb = 1 + B * T
        kk, kv_ = jax.random.split(jax.random.fold_in(key, ctx))
        k = jax.random.normal(kk, (B, ctx, Hkv, D), jnp.float32)
        v = jax.random.normal(kv_, (B, ctx, Hkv, D), jnp.float32)
        tables = (1 + jnp.arange(B * T, dtype=jnp.int32)).reshape(B, T)
        positions = jnp.tile(jnp.arange(ctx, dtype=jnp.int32), (B, 1))
        pool = kvc.scatter_chunks(
            kvc.init_pool(nb, ps, Hkv, D, jnp.float32, kv_format=fmt_name),
            tables, k, v, positions, cache_len=ctx, fmt=fmt)
        ring = attention.KVCache(k=k, v=v, pos=positions)
        pos = jnp.full((B,), ctx - 1, jnp.int32)
        qv = jax.random.normal(jax.random.fold_in(key, 1),
                               (B, Hq, D), jnp.float32)
        return qv, pool, tables, pos, ring, fmt

    cells = []
    for fmt_name in ("kv_fp16", "kv8_channel"):
        quantized = q.get_kv_format(fmt_name).quantized
        for ctx in (128, 256, 512):
            qv, pool, tables, pos, ring, fmt = build(ctx, fmt_name)
            S = planning.choose_kv_partitions(B, Hkv, tables.shape[1])
            fns = {
                # ring stores raw cache-dtype rows — the same fp16 read
                # regardless of the pool's block format
                "ring": jax.jit(lambda qq, rr=ring, pp=pos:
                                attention.decode_attention(qq, rr, pp)),
                "gather": jax.jit(lambda qq, po=pool, tb=tables, pp=pos:
                                  kvc.paged_decode_attention(
                                      qq, po, tb, pp, fmt=fmt,
                                      out_dtype=jnp.float32)),
                "fused": jax.jit(lambda qq, po=pool, tb=tables, pp=pos:
                                 fused_paged_attention(
                                     qq, po, tb, pp, fmt=fmt,
                                     out_dtype=jnp.float32,
                                     kv_partitions=S)),
            }
            outs = {p: fn(qv) for p, fn in fns.items()}
            maxdiff = float(jnp.max(jnp.abs(outs["fused"] - outs["gather"])))
            problem = planning.AttentionProblem(
                B=B, Hq=Hq, Hkv=Hkv, D=D, cache_len=ctx, page_size=ps,
                kv_format=fmt_name, paged=True, act_bytes=4)
            picks = {
                be: planning.plan_attention(
                    dataclasses.replace(problem, backend=be)).path
                for be in ("cpu", "tpu")}
            for path, fn in fns.items():
                us = _time(fn, qv)
                gbytes = cm.paged_attn_bytes(
                    path, B, Hq, Hkv, D, ctx, act_bytes=4,
                    quantized=quantized and path != "ring",
                    kv_partitions=S if path == "fused" else 1)
                t_tpu = cm.attn_decode_time_tpu(
                    path, B, Hq, Hkv, D, ctx, act_bytes=4,
                    quantized=quantized and path != "ring",
                    kv_partitions=S if path == "fused" else 1)
                name = f"paged_attn/{fmt_name}/ctx{ctx}/{path}"
                print(f"{name},{us:.1f},{B / (us / 1e6):.1f}")
                cells.append({
                    "name": name, "path": path, "kv_format": fmt_name,
                    "ctx": ctx, "batch": B, "heads": Hq,
                    "kv_heads": Hkv, "head_dim": D, "page_size": ps,
                    "kv_partitions": S if path == "fused" else 1,
                    "q_len": 1,
                    "us_per_step": round(us, 2),
                    "tok_per_s": round(B / (us / 1e6), 2),
                    "bytes_moved": int(gbytes),
                    "roofline_tpu_us": round(t_tpu * 1e6, 3),
                    "planner_pick_cpu": picks["cpu"],
                    "planner_pick_tpu": picks["tpu"],
                    "fused_vs_gather_maxdiff": maxdiff,
                })

    # multi-query regimes over the same pools: chunked prefill (q_len =
    # the chunk, one slot per call) and speculative verify (q_len = k+1,
    # full batch) — gather still materializes the whole window per call,
    # so its bytes column is flat in q_len while the fused walk pays one
    # pass + O(q_len) partials
    from repro.kernels.paged_attention import fused_chunk_attention

    for fmt_name in ("kv_fp16", "kv8_channel"):
        quantized = q.get_kv_format(fmt_name).quantized
        for regime, Br, C in (("prefill_chunk", 1, 32), ("verify", B, 5)):
            for ctx in (128, 256, 512):
                _, pool, tables, _, _, fmt = build(ctx, fmt_name)
                tbl = tables[:Br]
                start = ctx - C
                positions = jnp.broadcast_to(
                    start + jnp.arange(C, dtype=jnp.int32), (Br, C))
                kk2 = jax.random.fold_in(key, 7 * ctx + C)
                qmq = jax.random.normal(kk2, (Br, C, Hq, D), jnp.float32)

                def rt(s, shape=(Br, C, Hkv, D)):
                    x = jax.random.normal(jax.random.fold_in(kk2, s),
                                          shape, jnp.float32)
                    return q.kv_dequantize(*q.kv_quantize(x, fmt), fmt=fmt,
                                           dtype=jnp.float32)

                kseg, vseg = rt(1), rt(2)
                problem = planning.AttentionProblem(
                    B=Br, Hq=Hq, Hkv=Hkv, D=D, cache_len=ctx, page_size=ps,
                    kv_format=fmt_name, paged=True, act_bytes=4, q_len=C)
                # the Split-K degree the planner would actually run
                # (occupancy-chosen, capped by the combine-traffic rule)
                S = planning.plan_attention(problem,
                                            path="fused").kv_partitions

                def gather_fn(qq, ks=kseg, vs=vseg, po=pool, tb=tbl,
                              pp=positions):
                    win = kvc.gather_window(po, tb, fmt=fmt,
                                            out_dtype=jnp.float32)
                    wpos = jnp.where(win.pos < pp[:, :1], win.pos, -1)
                    seq = attention.KVCache(
                        k=jnp.concatenate([win.k, ks], axis=1),
                        v=jnp.concatenate([win.v, vs], axis=1),
                        pos=jnp.concatenate([wpos, pp], axis=1))
                    return attention.prefix_chunk_attention(qq, seq, pp)

                def fused_fn(qq, ks=kseg, vs=vseg, po=pool, tb=tbl,
                             pp=positions, SS=S):
                    return fused_chunk_attention(
                        qq, ks, vs, po, tb, pp, fmt=fmt,
                        out_dtype=jnp.float32, kv_partitions=SS)

                fns = {"gather": jax.jit(gather_fn),
                       "fused": jax.jit(fused_fn)}
                outs = {p: fn(qmq) for p, fn in fns.items()}
                maxdiff = float(jnp.max(jnp.abs(outs["fused"]
                                                - outs["gather"])))
                picks = {
                    be: planning.plan_attention(
                        dataclasses.replace(problem, backend=be)).path
                    for be in ("cpu", "tpu")}
                for path, fn in fns.items():
                    us = _time(fn, qmq)
                    gbytes = cm.paged_attn_bytes(
                        path, Br, Hq, Hkv, D, ctx, act_bytes=4,
                        quantized=quantized,
                        kv_partitions=S if path == "fused" else 1,
                        q_len=C)
                    t_tpu = cm.attn_decode_time_tpu(
                        path, Br, Hq, Hkv, D, ctx, act_bytes=4,
                        quantized=quantized,
                        kv_partitions=S if path == "fused" else 1,
                        q_len=C)
                    name = (f"paged_attn/{fmt_name}/{regime}"
                            f"/ctx{ctx}/{path}")
                    tok_s = Br * C / (us / 1e6)
                    print(f"{name},{us:.1f},{tok_s:.1f}")
                    cells.append({
                        "name": name, "path": path, "kv_format": fmt_name,
                        "regime": regime, "ctx": ctx, "batch": Br,
                        "heads": Hq, "kv_heads": Hkv, "head_dim": D,
                        "page_size": ps,
                        "kv_partitions": S if path == "fused" else 1,
                        "q_len": C,
                        "us_per_step": round(us, 2),
                        "tok_per_s": round(tok_s, 2),
                        "bytes_moved": int(gbytes),
                        "roofline_tpu_us": round(t_tpu * 1e6, 3),
                        "planner_pick_cpu": picks["cpu"],
                        "planner_pick_tpu": picks["tpu"],
                        "fused_vs_gather_maxdiff": maxdiff,
                    })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# paged_attn: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Front-door sweep: the async HTTP serving path under rising arrival rates —
# real-socket SSE clients against the bounded admission queue; served ratio,
# TTFT/e2e quantiles and 429/408 shed counts land in BENCH_frontdoor.json
# ---------------------------------------------------------------------------

def bench_frontdoor(out_path: str = "BENCH_frontdoor.json") -> dict:
    """Arrival-rate sweep over the asyncio front door (reduced danube):
    R real HTTP clients spaced ``gap_ms`` apart stream SSE tokens through
    a small admission queue; faster arrivals shed load as 429 instead of
    queueing past the SLO. A plain ``engine.run`` pass warms compile
    caches first, so the sweep measures serving, not tracing."""
    import asyncio
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.runtime.engine import Request, ServingEngine
    from repro.runtime.frontdoor import (FrontDoor, QueueSettings,
                                         sse_decode_tokens)

    print("# frontdoor: name,us_per_call,derived(served/total)")
    arch, P, G, B, R, QD = "h2o-danube-1.8b", 8, 8, 2, 6, 3
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="xla",
                              quant_format=BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
    tokens = jax.random.randint(key, (R, P), 0, cfg.vocab_size)
    prompts = [[int(t) for t in tokens[i]] for i in range(R)]

    engine = ServingEngine(cfg, params, max_batch=B, max_prompt_len=P,
                           max_new_tokens=G, page_size=4, prefill_chunk=4,
                           admission="priority")
    engine.run([Request(rid=i, prompt=prompts[i], max_new_tokens=G)
                for i in range(B)])                # warm: compile + plans

    async def client(port, prompt, delay):
        await asyncio.sleep(delay)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps({"prompt": prompt, "max_new_tokens": G}).encode()
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        payload = await reader.read()
        writer.close()
        if b" 200 " not in payload.split(b"\r\n", 1)[0]:
            return None
        return sse_decode_tokens(payload)

    async def sweep(gap_s):
        fd = FrontDoor(engine,
                       settings=QueueSettings(queue_depth=QD))
        await fd.serve()
        t0 = time.perf_counter()
        outs = await asyncio.gather(*(
            client(fd.port, prompts[i], i * gap_s) for i in range(R)))
        report = await fd.shutdown()
        return outs, report, time.perf_counter() - t0

    cells = []
    for gap_ms in (0, 30, 120):
        outs, report, wall = asyncio.run(sweep(gap_ms / 1e3))
        served = sum(1 for o in outs if o is not None)
        ls, ts = report.latency_stats(), report.ttft_stats()
        name = f"frontdoor/{arch}/gap{gap_ms}ms"
        print(f"{name},{wall*1e6:.0f},{served}/{R}")
        cells.append({
            "name": name, "arch": arch, "gap_ms": gap_ms,
            "queue_depth": QD, "batch": B, "requests": R,
            "served": served, "rejected_429": report.rejected_429,
            "rejected_408": report.rejected_408,
            "peak_queue_depth": report.peak_queue_depth,
            "ttft_p50_ms": round(ts["p50"] * 1e3, 3),
            "ttft_p99_ms": round(ts["p99"] * 1e3, 3),
            "e2e_p50_ms": round(ls["p50"] * 1e3, 3),
            "e2e_p99_ms": round(ls["p99"] * 1e3, 3),
            "tok_per_s": round(report.tokens_per_s, 3),
            "wall_s": round(wall, 3),
        })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# frontdoor: wrote {len(cells)} cells -> {out_path}")
    return blob


# ---------------------------------------------------------------------------
# Warm-prefix-cache sweep: Zipf-distributed prompt reuse against the
# allocator's warm retention budget — warm hit rate and prefill steps saved
# per (skew, budget) cell, persisted as BENCH_prefix_cache.json (CI artifact)
# ---------------------------------------------------------------------------

def bench_prefix_cache(out_path: str = "BENCH_prefix_cache.json") -> dict:
    """Zipfian arrival-trace sweep over the warm prefix cache: R requests
    draw their prompt from a pool of U distinct page-aligned prompts with
    Zipf(skew) popularity, so hot prompts return after their slot has
    released its pages. Each skew level runs at three warm budgets (off /
    half the pool / the whole pool + slack); warm hit rate and
    prefill-steps-saved are the figures of merit — a full warm hit admits
    with zero prefill steps."""
    import dataclasses

    from repro import configs
    from repro.models import transformer as T
    from repro.runtime.engine import Request, ServingEngine

    print("# prefix_cache: name,us_per_call,derived(warm_hit_rate)")
    # dense arch: an SWA window would wrap decode over the prompt pages
    # and unpublish the very chains warm retention wants to keep
    arch, P, G, B, R, U = "starcoder2-7b", 16, 4, 2, 12, 6
    page = 4                                   # P/page = 4-page chains
    cfg = dataclasses.replace(configs.get_reduced(arch),
                              w4a16_strategy="xla",
                              quant_format=BENCH_FORMAT)
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
    pool = jax.random.randint(key, (U, P), 0, cfg.vocab_size)

    def trace(skew):
        # rank-r prompt drawn with probability ∝ 1/(r+1)^skew; B=2 slots
        # over R=12 arrivals means hot prompts keep returning after their
        # pages were released — exactly the regime warm retention targets
        w = jnp.arange(1, U + 1, dtype=jnp.float32) ** -skew
        picks = jax.random.choice(jax.random.fold_in(key, int(skew * 10)),
                                  U, (R,), p=w / w.sum())
        return [Request(rid=i, prompt=pool[int(picks[i])],
                        max_new_tokens=G) for i in range(R)]

    def engine_for(mb):
        return ServingEngine(cfg, params, max_batch=B, max_prompt_len=P,
                             max_new_tokens=G, page_size=page,
                             prefill_chunk=page, warm_cache_mb=mb)

    chain_mb = (engine_for(0.0).alloc.block_bytes
                * (P // page)) / (1 << 20)     # one full prompt chain
    cells = []
    for skew in (0.0, 1.0, 1.8):
        for budget_mb in (0.0, chain_mb * U / 2, chain_mb * (U + B)):
            engine = engine_for(budget_mb)
            engine.run(trace(skew))            # warm: compile + plans
            report = engine.run(trace(skew))
            admits = report.warm_hits + report.warm_misses
            hit_rate = report.warm_hits / max(admits, 1)
            name = (f"prefix_cache/{arch}/zipf{skew:.1f}/"
                    f"warm{budget_mb:.2f}MiB")
            print(f"{name},{report.decode_s*1e6:.0f},{hit_rate:.3f}")
            cells.append({
                "name": name, "arch": arch, "zipf_skew": skew,
                "warm_cache_mb": round(budget_mb, 4), "batch": B,
                "prompt_len": P, "gen": G, "requests": R,
                "distinct_prompts": U, "page_size": page,
                "warm_hits": report.warm_hits,
                "warm_misses": report.warm_misses,
                "warm_hit_rate": round(hit_rate, 4),
                "prefill_steps_saved": report.prefill_steps_saved,
                "steps": report.steps,
                "tok_per_s": round(report.tokens_per_s, 3),
            })
    blob = {"format": BENCH_FORMAT, "backend": jax.default_backend(),
            "cells": cells}
    with open(out_path, "w") as f:
        json.dump(blob, f, indent=1, sort_keys=True)
    print(f"# prefix_cache: wrote {len(cells)} cells -> {out_path}")
    return blob


BENCHES = {
    "fig2": bench_fig2_splitk_vs_dataparallel,
    "fig3": bench_fig3_w4a16_vs_fp16,
    "kernels": bench_kernel_walltime,
    "capacity": bench_capacity,
    "plans": bench_plans,
    "formats": bench_formats,
    "serving": bench_serving,
    "paged_kv": bench_paged_kv,
    "paged_attn": bench_paged_attn,
    "speculative": bench_speculative,
    "frontdoor": bench_frontdoor,
    "prefix_cache": bench_prefix_cache,
}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("benches", nargs="*", metavar="bench",
                    help=f"subset of {list(BENCHES)} (default: all)")
    ap.add_argument("--quick", action="store_true",
                    help="run the quick perf snapshot, the fused-format "
                         "sweep, the serving sweep, the ring-vs-paged KV "
                         "sweep, the paged-attention path sweep, the "
                         "speculative sweep, the front-door arrival "
                         "sweep and the warm-prefix-cache sweep, writing "
                         "BENCH_quickstart.json, BENCH_formats.json, "
                         "BENCH_serving.json, BENCH_paged_kv.json, "
                         "BENCH_paged_attn.json, BENCH_speculative.json, "
                         "BENCH_frontdoor.json and BENCH_prefix_cache.json "
                         "(the CI artifacts)")
    ap.add_argument("--format", default=quant.DEFAULT_FORMAT,
                    help="QuantFormat name for quantized benches "
                         "(w4a16_g128 | w8a16_channel | w4a8_g128 | ...)")
    ap.add_argument("--out", default="BENCH_quickstart.json",
                    help="--quick output path")
    args = ap.parse_args(argv)
    compile_cache.enable()

    global BENCH_FORMAT
    BENCH_FORMAT = quant.get_format(args.format).name
    if args.quick:
        bench_quick(args.out)
        bench_formats()
        bench_serving()
        bench_paged_kv()
        bench_paged_attn()
        bench_speculative()
        bench_frontdoor()
        bench_prefix_cache()
        return
    for name in args.benches or list(BENCHES):
        if name not in BENCHES:
            ap.error(f"unknown bench {name!r}; one of {list(BENCHES)}")
        BENCHES[name]()


if __name__ == "__main__":
    main()
