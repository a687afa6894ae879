#!/usr/bin/env python3
"""Chip benchmark of the W4A16 serving engine: one cell, one run.

    python3 benchmarks/chip/run.py --workload danube.long --seed 7 \
        --seconds 51 --trace 0

A cell (``BENCHMARK.json`` ``workloads``) is a model configuration
(``configs/<name>.json``) under a traffic mix (``traffic/<name>.json``).
The run makes the weights and the requests from ``--seed``, builds the
engine as ``launch/serve.py`` does, compiles every program the window will
run and builds every session's context (set-up), steps the engine for
``--seconds`` through its stepper API, checks what it served against the
plain float32 reference, and prints one JSON line last on stdout:

    {"correct", "attempted", "failed", "metrics", "device", "check"}

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` runs the
same window with the profiler on over a slice of it and reports the cell's
per-layer metrics, each read by ``metrics/<name>.py``. Without a TPU, or
with fewer chips than the cell asks for, it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Optional  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(BENCH_DIR))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import arch, check as check_mod  # noqa: E402
from chipbench import serving, tracing, traffic as traffic_mod  # noqa: E402
from chipbench import weights  # noqa: E402

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
TRACE_SLICE_S = 3.0
CONTAINER_OPS = ("while", "conditional", "call")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def enable_compile_cache() -> None:
    """Every program goes to the persistent cache at a fixed path inside the
    checkout, small ones included, so a second run compiles nothing."""
    os.makedirs(CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH_DIR, "chipbench", "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peak table "
                       f"chipbench/peaks.json ({sorted(table)})")
    return table[kind]


@dataclasses.dataclass
class Context:
    """What a metric reader sees of one run."""

    bench_dir: str
    cfgj: dict
    traffic: dict
    record: serving.Record
    setup_s: float
    peaks: dict
    trace: Optional[tracing.TraceSummary] = None
    traced_steps: list = dataclasses.field(default_factory=list)


def read_metric(ctx: Context, name: str):
    base = name.split(".")[0]
    return arch.load_file(os.path.join(BENCH_DIR, "metrics", base + ".py"),
                          f"metric_{base}").read(ctx)


def cell_metrics(bench: dict, cell: str, traced: bool) -> list:
    """The metrics this cell reports in this kind of run."""
    if not traced:
        return [m for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])]
    e2e = {m["name"] for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell] if m["moves"] in e2e
                             else [])]


def breakdown(summary: tracing.TraceSummary) -> dict:
    """The device ops that took most time and the device's idle time by
    what the host was doing (the harness span around it)."""
    by_op = {}
    for o in summary.ops:
        key = o.name.split(" =")[0].rstrip("0123456789").rstrip(".")
        if key.lstrip("%") in CONTAINER_OPS:
            continue            # its body's ops are listed themselves
        by_op[key] = by_op.get(key, 0.0) + o.dur
    idle = {}
    spans = summary.spans
    for a, b in summary.idle_gaps():
        mid = 0.5 * (a + b)
        host = "host: outside harness spans"
        for name, s0, s1 in spans:
            if s0 <= mid < s1:
                host = name
        idle[host] = idle.get(host, 0.0) + (b - a)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}


def run_cell(bench: dict, workload: str, seed: int, seconds: float,
             trace: bool, *, peaks: Optional[dict] = None,
             quant_format: str = "w4a16_g128", engine_hook=None,
             files: Optional[dict] = None, keep: Optional[dict] = None,
             t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``files`` (tests) replaces the cell's configuration, traffic and limits
    files with dicts, and its architecture module with the file at
    ``files["model"]``; ``peaks`` the peak-table row; ``quant_format`` the
    format the weights are handed to the program as; ``engine_hook`` is
    called with the engine before set-up; ``keep`` (a dict) receives the
    run's record, weights, sample and per-token gaps."""
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    files = files or {}
    cfgj = files.get("config") or load_json(ROOT, conf["file"])
    mix = files.get("traffic") or load_json(
        BENCH_DIR, "traffic", cell["traffic"] + ".json")
    limits = files.get("limits") or load_json(
        BENCH_DIR, "limits", workload + ".json")
    if "model" in files:
        arch.load(cfgj["model"], files["model"])
    model = arch.of(cfgj)
    dev = jax.devices()[0]
    if peaks is None:
        peaks = peaks_for(dev.device_kind)
    enable_compile_cache()
    counter = serving.compile_counter()

    raw = model.make_raw(cfgj, seed)
    log(f"{workload}: weights {weights.nbytes(raw) / 1e9:.3f} GB from seed "
        f"{seed}")
    params = model.program_params(raw, cfgj, quant_format)
    engine = serving.build_engine(cfgj, mix, params, quant_format)
    if engine_hook is not None:
        engine_hook(engine)
    reqs = traffic_mod.schedule(mix, seed, cfgj["vocab_size"])
    rec = serving.Record(seconds=seconds, max_batch=engine.max_batch)
    plans = sorted({(p.strategy, p.split_k) for p in engine.plans.values()})
    log(f"{workload}: {engine.max_batch} slots, cache_len "
        f"{engine.cache_len}, {engine.num_pages} pages x "
        f"{engine.page_size}; gemm plans {plans}; attention decode "
        f"{engine.attn_path} prefill {engine.prefill_attn_path}; "
        f"{len(reqs)} requests")
    serving.warm_up(engine)
    serving.build_sessions(engine, reqs, rec)
    tracer = None
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        tracer = tracing.Tracer(TRACE_DIR,
                                max(0.0, 0.5 * seconds - TRACE_SLICE_S / 2),
                                min(TRACE_SLICE_S, seconds))
    setup_s = serving.clock() - t_start
    serving.drive(engine, rec, counter=counter, tracer=tracer)
    log(f"{workload}: window {seconds} s: {len(rec.window_steps())} steps, "
        f"{sum(len(v) for v in rec.times.values())} tokens served in the "
        f"run, compilations inside the window {rec.compiles_in_window} "
        f"{sorted(set(rec.compiled_in_window))[:8]}")

    stats = dev.memory_stats() or {}
    peak_bytes = int(stats.get("peak_bytes_in_use", 0))
    engine._state = None
    engine.last_state = None
    del engine, params
    gc.collect()

    reference = check_mod.load_reference(BENCH_DIR, cfgj["reference"])
    served = {r: (rec.reqs[r].prompt, list(t))
              for r, t in rec.tokens.items() if t}
    picked = check_mod.sample(served, seed, limits["sample"]["min_tokens"],
                              limits["sample"]["max_positions"])
    t_ref = serving.clock()
    size = check_mod.padded_size([served[r] for r in picked])
    gaps = [check_mod.gaps_for(reference, raw, cfgj, *served[r], size=size)
            for r in picked]
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    verdict = check_mod.judge(gaps, limits)
    if keep is not None:
        keep.update(record=rec, raw=raw, cfgj=cfgj, served=served,
                    picked=picked, gaps=gaps, reference=reference)
    log(f"{workload}: reference over {len(picked)} requests "
        f"({sum(len(served[r][1]) for r in picked)} served tokens) in "
        f"{serving.clock() - t_ref:.1f} s")

    summary = None
    traced_steps = []
    if tracer is not None and tracer.off_at is not None:
        summary = tracing.reduce(tracer.path())
        traced_steps = [s for s in rec.steps
                        if s.t0 >= tracer.on_at and s.t1 <= tracer.off_at]
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    ctx = Context(BENCH_DIR, cfgj, mix, rec, setup_s, peaks, summary,
                  traced_steps)
    metrics = {}
    for m in cell_metrics(bench, workload, trace):
        value = read_metric(ctx, m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count(), "memory_peak_bytes": peak_bytes}
    result = {"correct": verdict.correct,
              "attempted": len(reqs),
              "failed": 0, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s()
        device["window_s"] = summary.window_s
        result["breakdown"] = breakdown(summary)
    result["compiles_in_window"] = rec.compiles_in_window
    result["check"] = verdict.numbers
    for line in verdict.lines():
        log(line)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        log(f"no workload {args.workload!r} in BENCHMARK.json")
        return 2
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        log(f"needs {cell['chips']} TPU chip(s); JAX found "
            f"{len(devs)} {devs[0].platform!r} device(s)")
        return 2
    result = run_cell(bench, args.workload, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
