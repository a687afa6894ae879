#!/usr/bin/env python3
"""Serve h2o-danube-1.8b at its published widths on TPU and check the output.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the TP x DP engine on four chips

One chip: the engine that ``python -m repro.launch.serve`` builds — random
weights from ``--seed`` quantized to w4a16_g128, the paged KV pool and the
arch's serving presets — serves 8 requests of 32 new tokens whose prompts
(64-300 tokens) run past the prefill chunk, so chunked prefill and decode
both run. Its plans must be Pallas kernels (fused or decoupled GEMMs, the
fused paged-attention kernel), and its logits must agree with the same
engine forced onto the path with no Pallas kernel (GEMM strategy "xla",
attention path "gather").

Four chips: only the engine on a 2x2 (data x model) mesh, against the
one-chip engine on device 0, with the same comparison.

Logits are compared at every prefill chunk, and at every decode step for
the slots whose inputs so far are the same in both engines: greedy tokens
from random weights may part at a near tie, and after that their logits
are no longer comparable. The bound is ``TOLERANCE``.

The last line of stdout is the result:
    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}
Without a TPU the script exits 2 before building anything and prints no
such line; a failed check raises, so the exit code is not 0.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro import configs  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch import mesh as launch_mesh  # noqa: E402
from repro.launch.presets import serve_settings_for  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.runtime.engine import Request, ServingEngine  # noqa: E402

ARCH = "h2o-danube-1.8b"

# Per-row relative L2 error ||test - ref|| / ||ref|| of the fp32 logits.
# Both paths hold activations in bf16 (unit roundoff 2**-8) and differ only
# in rounding and accumulation order, which 24 layers carry into the
# logits: on a CPU host at the reduced width and full depth the two paths
# part by up to 2.5e-2, while a kernel reading the wrong KV head parts by
# 1.4. The bound sits between the two.
TOLERANCE = 2.0 ** -3


@dataclasses.dataclass(frozen=True)
class Workload:
    slots: int = 8
    prompt_min: int = 64
    prompt_max: int = 300
    gen: int = 32


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def init_quantized(cfg, seed: int):
    """Random weights from ``seed``, quantized as ``launch/serve.py`` does."""
    params = T.init_params(jax.random.PRNGKey(seed), cfg)
    return T.quantize_params(params, cfg, min_size=0)


def make_requests(cfg, work: Workload, seed: int):
    rng = np.random.default_rng(seed)
    lens = rng.integers(work.prompt_min, work.prompt_max + 1, work.slots)
    return [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, n).astype(np.int32),
                    max_new_tokens=work.gen)
            for i, n in enumerate(lens)]


def build_engine(cfg, params, work: Workload, *, strategy: str,
                 attn_path: str, mesh=None) -> ServingEngine:
    """A ServingEngine with the arch's serving presets, as the serve
    launcher builds it."""
    sset = serve_settings_for(cfg.name)
    return ServingEngine(
        dataclasses.replace(cfg, w4a16_strategy=strategy), params,
        mesh=mesh, max_batch=work.slots, max_prompt_len=work.prompt_max,
        max_new_tokens=work.gen, page_size=sset.page_size,
        prefill_chunk=sset.prefill_chunk, warm_cache_mb=sset.warm_cache_mb,
        kv_format=sset.kv_format, speculate=sset.speculate,
        spec_k=sset.spec_k, attn_path=attn_path)


def served_paths(engine: ServingEngine):
    """(GEMM strategies, attention paths) the engine's steps run."""
    gemms = ({p.strategy for p in engine.plans.values()} if engine.plans
             else {engine.cfg.w4a16_strategy})
    return gemms, {engine.attn_path, engine.prefill_attn_path}


def describe(engine: ServingEngine, tag: str) -> None:
    log(f"{tag}: {engine.max_batch} slots, cache_len {engine.cache_len}, "
        f"{engine.num_pages} pages x {engine.page_size} tokens, "
        f"kv_format {engine.kv_format}, prefill_chunk {engine.prefill_chunk}")
    if engine.plans:
        for key, p in sorted(engine.plans.items()):
            log(f"{tag}: gemm {key}: {p.strategy} split_k={p.split_k} "
                f"tiles=({p.block_m},{p.block_n},{p.block_k})")
    else:
        log(f"{tag}: gemm *: {engine.cfg.w4a16_strategy} (forced)")
    log(f"{tag}: attention decode {engine.attn_path} "
        f"(kv_partitions={engine.kv_partitions}, "
        f"pages_per_block={engine.pages_per_block}), prefill "
        f"{engine.prefill_attn_path} "
        f"(kv_partitions={engine.prefill_kv_partitions}, "
        f"pages_per_block={engine.prefill_pages_per_block})")


def tap_logits(engine: ServingEngine):
    """Record the inputs and logits of every prefill-chunk and decode step
    the engine runs. The engine looks its compiled steps up per call, so
    wrapping the lookups sees every call."""
    taps = {"prefill": [], "decode": []}
    fields = {"prefill": ("slot", "positions"),
              "decode": ("tokens", "pos", "tables")}
    for attr, kind in (("_chunk_step", "prefill"), ("_serve_step", "decode")):
        def lookup(*key, _get=getattr(engine, attr), _kind=kind):
            step = _get(*key)

            def call(*args):
                out = step(*args)
                inputs = args[-1]      # copied: the engine reuses buffers
                taps[_kind].append(
                    ({f: np.array(inputs[f]) for f in fields[_kind]},
                     out["logits"]))
                return out
            return call
        setattr(engine, attr, lookup)
    return taps


def serve(engine: ServingEngine, requests, tag: str):
    taps = tap_logits(engine)
    t0 = time.perf_counter()
    report = engine.run(requests)
    wall = time.perf_counter() - t0
    log(f"{tag}: {len(requests)} requests, {report.steps} steps, "
        f"{report.decode_tokens} decode tokens in {wall:.3f} s wall "
        f"(one unwarmed run, compilation included; not a benchmark)")
    log(f"{tag}: sample tokens (request 0): {report.results[0]}")
    return report, taps


def _rows(taps, vocab: int):
    """Comparable logit rows of two tapped runs of one schedule:
    (ref rows, test rows), each (n, vocab) fp32."""
    ref, test = taps
    out_ref, out_test = [], []
    if len(ref["prefill"]) != len(test["prefill"]) \
            or len(ref["decode"]) != len(test["decode"]):
        raise AssertionError(
            f"schedules differ: prefill {len(ref['prefill'])} vs "
            f"{len(test['prefill'])} steps, decode {len(ref['decode'])} vs "
            f"{len(test['decode'])} steps")
    for (ia, la), (ib, lb) in zip(ref["prefill"], test["prefill"]):
        if not all(np.array_equal(ia[f], ib[f]) for f in ia):
            raise AssertionError("prefill chunks differ between engines")
        out_ref.append(np.asarray(la).reshape(-1, vocab))
        out_test.append(np.asarray(lb).reshape(-1, vocab))
    # a decode row is comparable while every input of its slot since the
    # slot went active has been the same in both engines
    same = prev = None
    for (ia, la), (ib, lb) in zip(ref["decode"], test["decode"]):
        active = (ia["tables"] >= 0).any(axis=1)
        if not np.array_equal(active, (ib["tables"] >= 0).any(axis=1)):
            raise AssertionError("active slots differ between engines")
        if same is None:
            same = np.zeros_like(active)
            prev = np.zeros_like(active)
        same |= active & ~prev
        same &= (ia["tokens"] == ib["tokens"]) & (ia["pos"] == ib["pos"])
        prev = active
        rows = active & same
        out_ref.append(np.asarray(la)[rows])
        out_test.append(np.asarray(lb)[rows])
    n_prefill = len(ref["prefill"])
    return (np.concatenate(out_ref).astype(np.float32),
            np.concatenate(out_test).astype(np.float32), n_prefill)


def compare_logits(ref_taps, test_taps, vocab: int, tag: str) -> float:
    """Check every comparable logit row of ``test`` against ``ref``;
    returns the largest relative L2 error."""
    a, b, n_prefill = _rows((ref_taps, test_taps), vocab)
    if not (np.isfinite(a).all() and np.isfinite(b).all()):
        raise AssertionError(f"{tag}: non-finite logits")
    rel = np.linalg.norm(b - a, axis=-1) / np.linalg.norm(a, axis=-1)
    agree = int((a.argmax(-1) == b.argmax(-1)).sum())
    log(f"{tag}: {len(a)} logit rows x {vocab} compared "
        f"({n_prefill} prefill, {len(a) - n_prefill} decode); relative L2 "
        f"error max {rel.max():.3e} mean {rel.mean():.3e} "
        f"(tolerance {TOLERANCE:.3e}); max |diff| "
        f"{np.abs(b - a).max():.3e} at max |logit| {np.abs(a).max():.3e}; "
        f"argmax agrees on {agree}/{len(a)}")
    if rel.max() > TOLERANCE:
        raise AssertionError(
            f"{tag}: logits differ by {rel.max():.3e} relative L2 "
            f"(tolerance {TOLERANCE:.3e})")
    if len(a) == n_prefill:
        raise AssertionError(f"{tag}: no decode row was comparable")
    return float(rel.max())


def token_agreement(ref, test) -> str:
    same = sum(ref.results[r] == test.results[r] for r in ref.results)
    return f"{same}/{len(ref.results)} requests token-identical"


def assert_pallas(engine: ServingEngine) -> None:
    gemms, attns = served_paths(engine)
    if not (gemms and gemms <= {"fused", "decoupled"}) \
            or attns != {"fused"}:
        raise AssertionError(
            f"expected Pallas plans (fused/decoupled GEMMs, fused "
            f"attention); the engine planned GEMMs {sorted(gemms)}, "
            f"attention {sorted(attns)}")


def one_chip(cfg, work: Workload, seed: int) -> None:
    params = init_quantized(cfg, seed)
    requests = make_requests(cfg, work, seed)
    log(f"{cfg.name}: {cfg.num_layers} layers, d_model {cfg.d_model}, "
        f"heads {cfg.num_heads}/{cfg.num_kv_heads} x {cfg.head_dim}, "
        f"d_ff {cfg.d_ff}, window {cfg.sliding_window}, vocab "
        f"{cfg.vocab_size}, {cfg.quant_format}; prompts "
        f"{[len(r.prompt) for r in requests]}")

    preset = serve_settings_for(cfg.name).attn_path
    pallas = build_engine(cfg, params, work, strategy="auto",
                          attn_path=preset)
    describe(pallas, "pallas")
    assert_pallas(pallas)
    rep, taps = serve(pallas, requests, "pallas")

    ref = build_engine(cfg, params, work, strategy="xla",
                       attn_path="gather")
    describe(ref, "xla")
    rep_ref, taps_ref = serve(ref, requests, "xla")
    compare_logits(taps_ref, taps, cfg.padded_vocab, "pallas vs xla")
    log(f"pallas vs xla: {token_agreement(rep_ref, rep)}")


def four_chips(cfg, work: Workload, seed: int) -> None:
    if jax.device_count() < 4:
        raise SystemExit(f"--chips 4 needs 4 devices, JAX sees "
                         f"{jax.device_count()}")
    params = init_quantized(cfg, seed)
    requests = make_requests(cfg, work, seed)
    mesh = launch_mesh.make_local_mesh(data=2, model=2)
    ids = sorted(d.id for d in mesh.devices.flat)
    log(f"mesh {dict(mesh.shape)} over devices {ids}")
    if len(set(ids)) != 4:
        raise AssertionError(f"mesh repeats a device: {ids}")

    preset = serve_settings_for(cfg.name).attn_path
    single = build_engine(cfg, params, work, strategy="auto",
                          attn_path=preset)
    describe(single, "one chip")
    rep1, taps1 = serve(single, requests, "one chip")

    multi = build_engine(cfg, params, work, strategy="auto",
                         attn_path=preset, mesh=mesh)
    describe(multi, "2x2 mesh")
    rep4, taps4 = serve(multi, requests, "2x2 mesh")
    for what, tree in (("params", multi.params),
                       ("decode state", multi.last_state)):
        leaves = jax.tree.leaves(tree)
        devs = {d.id for leaf in leaves for d in leaf.sharding.device_set}
        split = sum(not leaf.sharding.is_fully_replicated for leaf in leaves)
        log(f"2x2 mesh: {what} on devices {sorted(devs)}, {split} of "
            f"{len(leaves)} arrays sharded")
        if devs != set(ids) or not split:
            raise AssertionError(f"{what} not spread over the mesh")
    compare_logits(taps1, taps4, cfg.padded_vocab, "2x2 mesh vs one chip")
    log(f"2x2 mesh vs one chip: {token_agreement(rep1, rep4)}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the TP x DP engine on a 2x2 mesh against "
                         "the one-chip engine")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and prompts")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    compile_cache.enable()
    log(f"device {dev.device_kind!r} x {jax.device_count()}, "
        f"jax {jax.__version__}")
    phase = four_chips if args.chips == 4 else one_chip
    phase(configs.get_config(ARCH), Workload(), args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
