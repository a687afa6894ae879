"""Roofline analysis from dry-run records (peaks of the record's chip).

    compute term    = HLO_FLOPs / (chips × 197 TFLOP/s)
    memory term     = HLO_bytes / (chips × 819 GB/s)
    collective term = collective_bytes / (chips × 50 GB/s/link)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (per-device on
the partitioned module → multiply by chips for the global numbers; the
ratios below use per-device values against per-chip peaks, which is
equivalent). collective_bytes is the loop-aware per-device ICI traffic
parsed from the partitioned HLO by launch/dryrun.py.

MODEL_FLOPS uses 6·N·D (train) / 2·N·D (inference step) with N = active
params — the "useful fraction" column catches remat/redundancy waste.
"""
from __future__ import annotations

import json
from typing import Optional

from repro import configs
from repro.configs.shapes import SHAPES
from repro.core import costmodel


def model_flops(arch: str, shape_name: str) -> float:
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def roofline_row(rec: dict, chips: Optional[int] = None) -> Optional[dict]:
    if rec.get("status") != "OK":
        return None
    if chips is None:
        chips = 512 if rec.get("mesh") == "2x16x16" else 256
    flops_dev = rec["cost"].get("flops", 0.0)     # per-device, loop-aware
    bytes_dev = rec["cost"].get("bytes",
                                rec["cost"].get("bytes accessed", 0.0))
    coll_dev = rec["collectives"]["total"]
    # the dry run compiles for a production v5e mesh unless it says else
    spec = costmodel.tpu_spec(rec.get("device_kind", "TPU v5 lite"))
    t_compute = flops_dev / spec.flops
    t_memory = bytes_dev / spec.hbm_bw
    t_coll = coll_dev / spec.ici_bw
    dom = max(("compute", t_compute), ("memory", t_memory),
              ("collective", t_coll), key=lambda kv: kv[1])
    mf = model_flops(rec["arch"], rec["shape"])
    useful = mf / max(flops_dev * chips, 1.0)
    bound = max(t_compute, t_memory, t_coll)
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec.get("kind", "?"),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dom[0],
        "roofline_fraction": (t_compute / bound) if bound else 0.0,
        "model_flops": mf, "hlo_flops_global": flops_dev * chips,
        "useful_flop_fraction": useful,
        "peak_bytes_per_device": rec["bytes_per_device"]["peak_total"],
    }


def format_table(rows) -> str:
    hdr = (f"{'arch':18s} {'shape':12s} {'mesh':8s} {'comp(s)':>9s} "
           f"{'mem(s)':>9s} {'coll(s)':>9s} {'dominant':>10s} "
           f"{'roofl%':>7s} {'useful%':>8s} {'peakGB':>7s}")
    lines = [hdr, "-" * len(hdr)]
    for r in rows:
        if r is None:
            continue
        lines.append(
            f"{r['arch']:18s} {r['shape']:12s} {r['mesh']:8s} "
            f"{r['t_compute_s']:9.4f} {r['t_memory_s']:9.4f} "
            f"{r['t_collective_s']:9.4f} {r['dominant']:>10s} "
            f"{100*r['roofline_fraction']:6.1f}% "
            f"{100*min(r['useful_flop_fraction'],9.99):7.1f}% "
            f"{r['peak_bytes_per_device']/1e9:7.2f}")
    return "\n".join(lines)


def main(path: str = "dryrun_records.json"):
    with open(path) as f:
        records = json.load(f)
    rows = [roofline_row(r) for r in records if r.get("status") == "OK"]
    print(format_table(rows))
    skips = [r for r in records if r.get("status") == "SKIP"]
    for s in skips:
        print(f"SKIP  {s['arch']:18s} {s['shape']:12s} {s['mesh']:8s} "
              f"{s['skip_reason'][:60]}")


if __name__ == "__main__":
    import sys
    main(sys.argv[1] if len(sys.argv) > 1 else "dryrun_records.json")
