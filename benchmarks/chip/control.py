#!/usr/bin/env python3
"""Readings the output check's limits are set from: the program on many
seeds, or its controls, for one cell, in one process.

    python3 benchmarks/chip/control.py --workload danube.long \
        --seeds 1,2,3 --seconds 40 [--format w4a8_g128] [--controls]

For each seed it runs the cell as the benchmark does (a window of
``--seconds`` at the cell's own load), with the weights handed to the
program as ``--format``, and prints one JSON line: the verdict and the
numbers of the cell's committed limits (``check``), and the per-token gap
statistics of what was served (``served``). ``--format w4a8_g128`` is the
program's own lower-precision path switched on (the same int4 weights,
activations quantized to int8 per token), the control of a W4A16 cell.

With ``--controls`` it also reads, on the same seed, ``fp8_reference``:
the float32 reference with every matmul input rounded to float8_e4m3fn put
in the program's place: at each position of the served prompts and tokens,
the float32 gap of the token the lower precision puts first.

The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import run  # noqa: E402
from chipbench import check, serving  # noqa: E402


def stats(gaps: np.ndarray) -> dict:
    g = np.asarray(gaps, np.float64)
    if g.size == 0:
        return {"tokens": 0}
    return {"tokens": int(g.size), "max": float(g.max()),
            "mean": float(g.mean()), "p99": float(np.quantile(g, 0.99)),
            "share_not_best": float((g > 0).mean())}


def readings(bench, workload, seed, seconds, controls,
             quant_format="w4a16_g128") -> dict:
    kept = {}
    res = run.run_cell(bench, workload, seed, seconds, False, keep=kept,
                       quant_format=quant_format, t_start=serving.clock())
    out = {"seed": seed, "format": quant_format, "correct": res["correct"],
           "check": res["check"], "served": stats(kept["gaps"])}
    if controls:
        ref, raw, cfgj = kept["reference"], kept["raw"], kept["cfgj"]
        pairs = [kept["served"][r] for r in kept["picked"]]
        size = check.padded_size(pairs)
        fp8 = [check.control_gaps(ref, raw, cfgj, *pair, jnp.float8_e4m3fn,
                                  size=size) for pair in pairs]
        out["fp8_reference"] = stats(np.concatenate(fp8) if fp8 else [])
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--format", default="w4a16_g128")
    ap.add_argument("--controls", action="store_true")
    args = ap.parse_args(argv)
    bench = run.load_json(run.ROOT, "BENCHMARK.json")
    for seed in (int(s) for s in args.seeds.split(",")):
        print(json.dumps(readings(bench, args.workload, seed, args.seconds,
                                  args.controls, args.format)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
