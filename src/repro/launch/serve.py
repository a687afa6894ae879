"""Serving driver: W4A16-quantized continuous-batching decode on a mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --reduced --batch 4 --prompt-len 32 --gen 16 --strategy fused

    # 8 fake CPU devices, 2-way data x 4-way tensor parallel:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch h2o-danube-1.8b \
        --reduced --mesh 2x4 --batch 4 --requests 8 --arrival-every 2

This is the paper's deployment scenario: weights quantized to INT4 at load
time, decode GEMMs run K≫N with small M — the Split-K regime. The
``runtime/engine.py`` scheduler admits/evicts requests per decode step
(continuous batching) and, on a mesh, plans every layer GEMM on its
shard-local shape (K/tp row-parallel, N/tp column-parallel).

Context lives in the paged, prefix-shared KV block pool by default
(``--ring`` restores per-slot ring caches): ``--page-size`` sets the
block granularity, ``--prefill-chunk`` interleaves long-prompt prefill
with decode, and ``--kv-format`` picks the KV block storage (``kv_fp16``
| ``kv8_channel`` per-head INT8) — validated against the registry up
front. See docs/serving.md.

``--http PORT`` swaps the in-process arrival loop for the asyncio front
door (``runtime/frontdoor.py``): real-socket clients POST /v1/generate
and stream tokens back as SSE, through a bounded admission queue
(``--queue-depth`` → 429 when full, ``--deadline-s`` → 408 once expired)
with ``GET /metrics`` live. See docs/serving.md §Front door.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import quant
from repro.kernels import planning
from repro.launch import compile_cache, mesh as launch_mesh
from repro.launch.presets import serve_settings_for
from repro.models import transformer as T
from repro.runtime import speculative
from repro.runtime.engine import Request, ServingEngine


def validate_kv_format(kv_format: str, weight_format: str, *,
                       paged: bool, attn_free: bool = False) -> str:
    """Resolve/validate the ``--kv-format`` × ``--format`` pair up front.

    Mirrors the planner's forced-pair refusal: a bad combination fails
    here with the registries' vocabulary instead of deep inside a trace.
    Both names must be registered, KV quantization requires the paged
    cache (the ring layout stores raw cache-dtype rows), and attention-free
    archs (rwkv) hold no KV cache for a format to apply to.
    """
    wf = quant.get_format(weight_format)          # raises w/ registry list
    kf = quant.get_kv_format(kv_format)           # raises w/ registry list
    if kf.quantized and attn_free:
        raise ValueError(
            f"--kv-format {kf.name!r} does not apply to attention-free "
            f"archs — there is no KV cache to quantize; use kv_fp16")
    if kf.quantized and not paged:
        raise ValueError(
            f"--kv-format {kf.name!r} quantizes KV blocks, which requires "
            f"the paged cache; drop --ring (or use --kv-format kv_fp16). "
            f"Registered KV formats: {quant.available_kv_formats()}")
    del wf  # every (weight, kv) registered pair is currently executable
    return kf.name


def parse_prompt_len(spec) -> "tuple[int, int]":
    """``N`` (fixed) or ``MIN:MAX`` (uniform variable length) → bounds."""
    s = str(spec)
    try:
        lo, hi = (int(x) for x in s.split(":", 1)) if ":" in s \
            else (int(s),) * 2
    except ValueError:
        raise ValueError(
            f"--prompt-len must be N or MIN:MAX, got {spec!r}") from None
    if not 0 < lo <= hi:
        raise ValueError(
            f"--prompt-len needs 0 < MIN <= MAX, got {spec!r}")
    return lo, hi


def _serve_http(engine, reqs, *, port, queue_depth, deadline_s,
                arrival_every):
    """Run the arrival simulation through the real front door: one
    real-socket HTTP client per request, tokens streamed back as SSE.
    The in-process simulation's step-count spacing maps to wall clock at
    10 ms per ``--arrival-every`` unit. Rejected requests (429/408)
    come back as ``None`` generations."""
    from repro.runtime.frontdoor import (FrontDoor, QueueSettings,
                                         sse_decode_tokens)

    async def client(port, req, delay):
        await asyncio.sleep(delay)
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        spec = {"prompt": [int(t) for t in req.prompt],
                "max_new_tokens": req.max_new_tokens,
                "priority": req.priority}
        if req.prefix_embeds is not None:
            spec["prefix_embeds"] = [[float(x) for x in row]
                                     for row in req.prefix_embeds]
        if req.audio_embeds is not None:
            spec["audio_embeds"] = [[float(x) for x in row]
                                    for row in req.audio_embeds]
        body = json.dumps(spec).encode()
        writer.write((f"POST /v1/generate HTTP/1.1\r\nHost: serve\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n").encode()
                     + body)
        await writer.drain()
        payload = await reader.read()
        writer.close()
        if b" 200 " not in payload.split(b"\r\n", 1)[0]:
            return None
        return sse_decode_tokens(payload)

    async def run():
        fd = FrontDoor(engine, settings=QueueSettings(
            queue_depth=queue_depth, default_deadline_s=deadline_s))
        await fd.serve(port=port)
        print(f"[serve] front door: http://{fd.host}:{fd.port} "
              f"(queue_depth {queue_depth}, deadline "
              f"{'none' if deadline_s is None else f'{deadline_s:g} s'})")
        t0 = time.time()
        got = await asyncio.gather(*(
            client(fd.port, r, i * arrival_every * 0.01)
            for i, r in enumerate(reqs)))
        report = await fd.shutdown()
        return got, report, time.time() - t0

    return asyncio.run(run())


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="engine slot count (max concurrent requests)")
    ap.add_argument("--max-batch", type=int, default=None,
                    help="alias for --batch (slot-pool size)")
    ap.add_argument("--prompt-len", default="32",
                    help="prompt tokens per request: fixed N, or MIN:MAX "
                         "for uniformly-distributed variable lengths")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--requests", type=int, default=None,
                    help="total simulated requests (default: the slot "
                         "count — one full static batch)")
    ap.add_argument("--arrival-every", type=int, default=0,
                    help="request-arrival simulation: one request every K "
                         "decode steps (0 = all arrive at step 0)")
    ap.add_argument("--mesh", default=None,
                    help="DATAxMODEL serving mesh (e.g. 2x4); requires "
                         "data*model visible devices — on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count. Default: "
                         "single device")
    ap.add_argument("--strategy", default="auto",
                    choices=["auto"] + list(planning.available_strategies()))
    ap.add_argument("--format", default=None,
                    help="quantization format name (see repro.core.quant."
                         "available_formats(): w4a16_g128 | w8a16_channel "
                         "| w4a8_g128 | any registered format); default: "
                         "the config's quant_format")
    ap.add_argument("--ring", action="store_true",
                    help="legacy per-slot ring KV caches instead of the "
                         "paged, prefix-shared block pool (the parity "
                         "reference; see docs/serving.md)")
    ap.add_argument("--page-size", type=int, default=None,
                    help="paged KV cache: tokens per physical block "
                         "(default: the arch's ServeSettings preset)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked prefill: max prompt tokens processed per "
                         "engine step, interleaved with decode — the single "
                         "prefill path for every family; 0 = the engine "
                         "default of 32 (default: the arch preset)")
    ap.add_argument("--warm-cache-mb", type=float, default=None,
                    help="warm prefix retention budget in MiB: released "
                         "page-aligned prefix chains stay adoptable and a "
                         "returning prompt skips its prefill; 0 = off "
                         "(default: the arch preset, usually 0)")
    ap.add_argument("--kv-format", default=None,
                    help="KV-cache block format (see repro.core.quant."
                         "available_kv_formats(): kv_fp16 | kv8_channel); "
                         "default: the arch preset")
    ap.add_argument("--attn-path", default=None,
                    choices=["auto", "gather", "fused"],
                    help="paged decode-attention path: gather (XLA window "
                         "reassembly) | fused (Pallas in-kernel block-table "
                         "walk) | auto (planner ranks them per backend; "
                         "default: the arch preset, usually auto)")
    ap.add_argument("--speculate", default=None,
                    help="speculative decoding proposer: off | ngram"
                         "[:max_n] | draft:layers=N (see repro.runtime."
                         "speculative.available_proposers(); default: the "
                         "arch preset, usually off)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="draft tokens scored per verify step "
                         "(default: the arch preset)")
    ap.add_argument("--plan-cache", default=None,
                    help="plan-cache JSON: loaded before serving if present, "
                         "saved (with any new decisions) afterwards")
    ap.add_argument("--refine-plans", action="store_true",
                    help="run the planner's tile-search refinement pass")
    ap.add_argument("--http", type=int, default=None, metavar="PORT",
                    help="serve through the async HTTP front door on PORT "
                         "(0 = ephemeral): real-socket POST /v1/generate "
                         "clients streaming SSE tokens, with GET /metrics "
                         "live; arrivals spaced --arrival-every x 10 ms")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="front-door admission queue bound before 429 "
                         "(--http only; default: the arch preset)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="default per-request SLO deadline in seconds, "
                         "408 once expired (--http only; 0 = none; "
                         "default: the arch preset)")
    ap.add_argument("--no-quant", action="store_true")
    ap.add_argument("--verbose", action="store_true",
                    help="per-step engine log lines")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.plan_cache and os.path.exists(args.plan_cache):
        n = planning.load_plan_cache(args.plan_cache, tolerant=True)
        if n >= 0:
            print(f"[serve] plan cache: loaded {n} plans "
                  f"from {args.plan_cache}")
        else:
            print(f"[serve] plan cache {args.plan_cache} unreadable; "
                  f"replanning from scratch")

    cfg = (configs.get_reduced if args.reduced else configs.get_config)(
        args.arch)
    sset = serve_settings_for(args.arch)
    paged = not args.ring
    page_size = args.page_size or sset.page_size
    prefill_chunk = sset.prefill_chunk if args.prefill_chunk is None \
        else (args.prefill_chunk or None)
    warm_cache_mb = sset.warm_cache_mb if args.warm_cache_mb is None \
        else args.warm_cache_mb
    fmt = quant.get_format(args.format or cfg.quant_format)
    kv_format = validate_kv_format(args.kv_format or sset.kv_format,
                                   fmt.name, paged=paged,
                                   attn_free=cfg.attn_free)
    speculate = sset.speculate if args.speculate is None \
        else (args.speculate if args.speculate != "off" else None)
    spec_k = sset.spec_k if args.spec_k is None else args.spec_k
    # refuse bad proposer/spec-k pairs up front with the registry's
    # vocabulary (same contract as --kv-format), not mid-serving-loop
    speculative.validate_speculate(speculate, spec_k, cfg=cfg, paged=paged)
    cfg = dataclasses.replace(cfg, w4a16_strategy=args.strategy,
                              quant_format=fmt.name)
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    if not args.no_quant:
        params = T.quantize_params(params, cfg, min_size=0)
        qbytes = sum(
            x.nbytes_packed() if hasattr(x, "nbytes_packed") else x.nbytes
            for x in jax.tree.leaves(
                params, is_leaf=lambda t: hasattr(t, "nbytes_packed")))
        print(f"[serve] {cfg.name} {fmt.name} ({args.strategy}); "
              f"weights {qbytes/1e6:.1f} MB on disk")

    mesh = launch_mesh.parse_mesh(args.mesh) if args.mesh else None
    if mesh is not None:
        print(f"[serve] mesh: "
              f"{dict(zip(mesh.axis_names, mesh.devices.shape))} "
              f"({mesh.devices.size} devices)")

    B = args.max_batch or args.batch
    pmin, pmax = parse_prompt_len(args.prompt_len)
    P, G = pmax, args.gen      # slots are sized for the longest prompt
    R = args.requests or B
    proposer = None
    if speculate is not None:
        proposer = speculative.make_proposer(speculate, target_cfg=cfg)
    attn_path = args.attn_path or sset.attn_path
    engine = ServingEngine(cfg, params, mesh=mesh, max_batch=B,
                           max_prompt_len=P, max_new_tokens=G,
                           refine_plans=args.refine_plans, paged=paged,
                           page_size=page_size, prefill_chunk=prefill_chunk,
                           warm_cache_mb=warm_cache_mb,
                           kv_format=kv_format, speculate=proposer,
                           spec_k=spec_k, attn_path=attn_path)
    print(f"[serve] engine: {B} slots, cache_len {engine.cache_len} "
          f"(prompt {P} + prefix {cfg.vision_prefix or 0} + gen {G})")
    if proposer is not None:
        print(f"[serve] speculative: proposer {proposer.name!r}, "
              f"k={spec_k} (verify scores {B}x{spec_k + 1} positions/step)")
    if engine.paged:
        print(f"[serve] paged KV: {engine.num_pages} blocks x "
              f"{engine.page_size} tokens ({engine.pages_slot}/slot), "
              f"kv_format {engine.kv_format}, prefill_chunk "
              f"{engine.prefill_chunk}"
              + (f", warm cache {warm_cache_mb:g} MiB"
                 if engine.alloc is not None and engine.alloc.warm_bytes
                 else ""))
        print(f"[serve] attn path: {engine.attn_path}"
              + (f" (kv_partitions={engine.kv_partitions}, "
                 f"pages_per_block={engine.pages_per_block})"
                 if engine.attn_path == "fused" else "")
              + ("" if args.attn_path else " [planned]"))
    for lk, plan in sorted(engine.plans.items()):
        print(f"[serve]   plan {lk}: {plan.strategy} "
              f"split_k={plan.split_k} "
              f"tiles=({plan.block_m},{plan.block_n},{plan.block_k})")

    # request-arrival simulation: R requests over the same random prompt
    # distribution, one every --arrival-every decode steps
    tokens = jax.random.randint(key, (R, P), 0, cfg.vocab_size)
    plens = [P] * R if pmin == pmax else [
        int(x) for x in jax.random.randint(
            jax.random.fold_in(key, 7), (R,), pmin, pmax + 1)]
    reqs = []
    for i in range(R):
        extras = {}
        if cfg.vision_prefix:
            extras["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.vision_prefix, cfg.d_model), cfg.dtype)
        if cfg.family == "encdec":
            extras["audio_embeds"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.encoder_seq, cfg.d_model), cfg.dtype)
        reqs.append(Request(rid=i, prompt=tokens[i][:plens[i]],
                            max_new_tokens=G,
                            arrival_step=i * args.arrival_every, **extras))
    if pmin != pmax:
        print(f"[serve] prompts: variable length {pmin}:{pmax} "
              f"(mean {sum(plens) / R:.1f})")

    if args.http is not None:
        got, report, wall = _serve_http(
            engine, reqs, port=args.http,
            queue_depth=args.queue_depth or sset.queue_depth,
            deadline_s=sset.deadline_s if args.deadline_s is None
            else (args.deadline_s or None),
            arrival_every=args.arrival_every)
    else:
        t0 = time.time()
        report = engine.run(reqs, verbose=args.verbose)
        wall = time.time() - t0
        got = [report.results[r.rid] for r in reqs]

    ls = report.latency_stats()
    print(f"[serve] {R} requests in {report.steps} steps / {wall:.2f} s "
          f"wall; prefill {report.prefill_s*1e3:.1f} ms total")
    print(f"[serve] decode: {report.decode_tokens} tokens in "
          f"{report.decode_s:.3f} s = {report.tokens_per_s:.1f} tok/s "
          f"({report.decode_s / max(len(report.step_records), 1) * 1e3:.2f} "
          f"ms/step); latency p50 {ls['p50']*1e3:.1f} / "
          f"p95 {ls['p95']*1e3:.1f} / p99 {ls['p99']*1e3:.1f} ms "
          f"max {ls['max']*1e3:.1f} ms")
    if args.http is not None:
        done = sum(1 for g in got if g is not None)
        ts = report.ttft_stats()
        print(f"[serve] front door: {done}/{R} served, "
              f"{report.rejected_429} x 429, {report.rejected_408} x 408; "
              f"peak queue {report.peak_queue_depth}; "
              f"ttft p50 {ts['p50']*1e3:.1f} ms p99 {ts['p99']*1e3:.1f} ms")
    if engine.paged:
        worst = engine.pages_slot * min(B, R)
        print(f"[serve] pages: peak {report.peak_pages} in use "
              f"(worst-case {worst} without sharing)")
    if proposer is not None:
        print(f"[serve] speculative: {report.accepted_tokens}/"
              f"{report.proposed_tokens} drafts accepted "
              f"({report.acceptance_rate:.0%}); tok/s above counts "
              f"accepted tokens only")
    print(f"[serve] sample generation (request 0): {got[0]}")
    if args.plan_cache:
        n = planning.save_plan_cache(args.plan_cache)
        c = planning.PLAN_CACHE
        print(f"[serve] plan cache: {n} plans -> {args.plan_cache} "
              f"({c.hits} hits / {c.misses} misses this run)")
    return jnp.asarray([g for g in got if g is not None], jnp.int32)


if __name__ == "__main__":
    main()
