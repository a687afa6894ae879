import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first: jax locks the device count on first
init, and the production meshes need 512 placeholder host devices.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
    PYTHONPATH=src python -m repro.launch.dryrun --arch granite-20b \
        --shape train_4k --multi-pod
    PYTHONPATH=src python -m repro.launch.dryrun --json out.json

For every cell it records compiled.memory_analysis() (proves fit),
cost_analysis() FLOPs/bytes, and the per-device collective-operand bytes
parsed from the partitioned HLO — the inputs to EXPERIMENTS.md §Roofline.
"""
import argparse
import dataclasses
import json
import re
import sys
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.configs import SHAPES, input_specs, skip_reason, cache_len_for
from repro.launch.mesh import make_production_mesh
from repro.launch.presets import settings_for
from repro.models import transformer as T
from repro.runtime import steps as rsteps

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
}

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _bytes_of(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


_RESULT_RE = re.compile(r"=\s+(?:\()?(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_COMP_RE = re.compile(r"^(?:ENTRY )?%?([\w.\-]+)\s*\(.*\)\s*->.*\{", re.M)
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")


def _split_computations(hlo_text: str):
    """Map computation name → its body text."""
    comps = {}
    name, buf = None, []
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line)
        if m:
            if name:
                comps[name] = "\n".join(buf)
            name, buf = m.group(1), []
        elif name is not None:
            buf.append(line)
    if name:
        comps[name] = "\n".join(buf)
    return comps


_CONST_RE = re.compile(r"%([\w.\-]+) = s32\[\]\S* constant\((\d+)\)")
_CMP_RE = re.compile(
    r"compare\(%([\w.\-]+), %([\w.\-]+)\), direction=(LT|GT|LE|GE)")


def _trip_count(cond_text: str) -> int:
    """Loop bound from the while condition: the constant operand of the
    iteration-counter compare (NOT just any constant in the computation —
    vocab sizes etc. appear as constants too)."""
    consts = dict(_CONST_RE.findall(cond_text))
    bounds = []
    for a, b, d in _CMP_RE.findall(cond_text):
        for name in (a, b):
            if name in consts:
                c = int(consts[name])
                if c > 0:
                    bounds.append(c if d in ("LT", "GT") else c + 1)
    if bounds:
        return min(bounds)
    # compare may be fused away — conditions are tiny, so the smallest
    # positive s32[] scalar constant is the loop bound (min avoids picking
    # stray large constants)
    allc = [int(v) for v in consts.values() if int(v) > 0]
    return min(allc) if allc else 1


def _loop_multipliers(hlo_text: str) -> dict:
    """computation name → product of enclosing while trip counts."""
    comps = _split_computations(hlo_text)
    mult = {n: 1 for n in comps}
    call_re = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
    # iterate to fixpoint over nesting (few levels)
    for _ in range(6):
        for parent, body in comps.items():
            for m in _WHILE_RE.finditer(body):
                cond, wbody = m.group(1), m.group(2)
                trips = _trip_count(comps.get(cond, ""))
                want = mult.get(parent, 1) * max(trips, 1)
                if wbody in mult and mult[wbody] < want:
                    mult[wbody] = want
                if cond in mult:
                    mult[cond] = max(mult[cond], mult.get(parent, 1))
            # fusion/reduce interiors inherit the caller's multiplier
            for callee in call_re.findall(body):
                if callee in mult and mult[callee] < mult.get(parent, 1):
                    mult[callee] = mult[parent]
    return mult, comps


_INSTR_RE = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = ([a-z0-9]+)\[([\d,]*)\]\S* ([a-z0-9\-]+)\((.*)$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")

_SKIP_BYTES_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                   "bitcast", "while", "conditional", "call", "iota",
                   "after-all", "partition-id"}


def hlo_costs(hlo_text: str) -> dict:
    """Loop-aware FLOPs and HBM-byte estimates from partitioned HLO text.

    XLA's ``compiled.cost_analysis()`` counts each while body ONCE, so a
    126-layer scanned model is ~126× undercounted (verified on CPU). This
    walks every computation, multiplies by the enclosing while trip counts,
    and computes:
      * flops — 2 · |result| · |contracted dims| per dot op;
      * bytes — Σ (operand + result bytes) over top-level instructions
        (post-fusion HLO: fusion operands/results are the real HBM buffers).
    """
    mult, comps = _loop_multipliers(hlo_text)
    # computations invoked as fusions/reducers: their interiors live in
    # registers/VMEM, so bytes are attributed to the CALLING instruction
    fusion_called = set()
    while_bodies = set()
    for body in comps.values():
        fusion_called.update(re.findall(r"calls=%?([\w.\-]+)", body))
        fusion_called.update(re.findall(r"to_apply=%?([\w.\-]+)", body))
        for m in _WHILE_RE.finditer(body):
            while_bodies.add(m.group(2))
    flops = 0.0
    bytes_ = 0.0
    # Inside a while body the carry/working set is loop-resident (VMEM on
    # the target TPU) — HBM traffic there is the *stack* traffic: xs/ys
    # slice reads & writes, gathers/scatters, and collective results
    # (which land in HBM before the consuming op). Everything else in a
    # body is treated as on-chip reuse. Entry-level ops count in full.
    _BODY_BYTE_OPS = {"dynamic-slice", "dynamic-update-slice", "gather",
                      "scatter", "copy", "concatenate"}
    # stack accesses fused into kLoop fusions: pre-compute per-callee
    # slice-traffic so a fusion op inside a while body charges its inner
    # dynamic-(update-)slice bytes
    fusion_stack_bytes = {}
    for cname, body in comps.items():
        total = 0
        syms0 = {}
        for line in body.splitlines():
            m = _INSTR_RE.match(line)
            if m:
                syms0[m.group(1)] = (m.group(2), m.group(3))
        for line in body.splitlines():
            m = _INSTR_RE.match(line)
            if not m:
                continue
            _, dt0, dims0, op0, rest0 = m.groups()
            if op0 == "dynamic-slice":
                total += 2 * _bytes_of(dt0, dims0)
            elif op0 == "dynamic-update-slice":
                args0 = rest0.split("),")[0] if ")," in rest0 else rest0
                named = [o for o in _OPERAND_RE.findall(args0) if o in syms0]
                if len(named) >= 2:
                    total += 2 * _bytes_of(*syms0[named[1]])
        if total:
            fusion_stack_bytes[cname] = total

    _CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
    for cname, body in comps.items():
        k = mult.get(cname, 1)
        in_loop = cname in while_bodies or mult.get(cname, 1) > 1
        # symbol table: instruction name → (dtype, dims)
        syms = {}
        for line in body.splitlines():
            m = _INSTR_RE.match(line)
            if m:
                syms[m.group(1)] = (m.group(2), m.group(3))
        count_bytes = cname not in fusion_called
        for line in body.splitlines():
            m = _INSTR_RE.match(line)
            if not m:
                continue
            name, dt, dims, op, rest = m.groups()
            res_bytes = _bytes_of(dt, dims)
            if op == "dot":
                cd = _CDIMS_RE.search(line)
                lhs = _OPERAND_RE.search(rest)
                csize = 1
                if cd and lhs and lhs.group(1) in syms:
                    ldims = [int(x) for x in syms[lhs.group(1)][1].split(",")
                             if x]
                    for i in (int(x) for x in cd.group(1).split(",") if x):
                        if i < len(ldims):
                            csize *= ldims[i]
                n_res = 1
                for d in dims.split(","):
                    if d:
                        n_res *= int(d)
                flops += 2.0 * n_res * csize * k
            if count_bytes and op not in _SKIP_BYTES_OPS:
                if in_loop and op == "fusion":
                    cm = _CALLS_RE.search(line)
                    if cm and cm.group(1) in fusion_stack_bytes:
                        bytes_ += fusion_stack_bytes[cm.group(1)] * k
                    continue
                if in_loop and op not in _BODY_BYTE_OPS \
                        and op not in COLLECTIVES:
                    continue
                args = rest.split("),")[0] if ")," in rest else rest
                ops_named = [o for o in _OPERAND_RE.findall(args)
                             if o in syms]
                if op == "dynamic-update-slice" and len(ops_named) >= 2:
                    # in-place slice write: only the update region moves
                    total = 2 * _bytes_of(*syms[ops_named[1]])
                elif op == "dynamic-slice":
                    total = 2 * res_bytes
                elif op in COLLECTIVES:
                    total = 2 * res_bytes      # HBM write + consuming read
                else:
                    total = res_bytes + sum(
                        _bytes_of(*syms[o]) for o in ops_named)
                bytes_ += total * k
    return {"flops": flops, "bytes": bytes_}


def collective_bytes(hlo_text: str) -> dict:
    """Estimated per-device ICI traffic of every collective in the
    partitioned HLO, using ring-algorithm cost models on the RESULT shape:

      all-gather          R·(S-1)/S      (R = full gathered result)
      reduce-scatter      R·(S-1)        (R = scattered shard)
      all-reduce          2·R·(S-1)/S    (RS + AG)
      all-to-all          R·(S-1)/S
      collective-permute  R

    where S is the shard-group size parsed from replica_groups.
    Counted ONCE per static HLO op; ops inside while loops are multiplied
    by nothing (we report per-step traffic for a scanned layer stack via
    the loop trip count when present — see loop_multiplier note in
    EXPERIMENTS.md §Roofline).
    """
    out = {c: 0 for c in COLLECTIVES}
    counts = {c: 0 for c in COLLECTIVES}
    mult, comps = _loop_multipliers(hlo_text)
    for cname, body in comps.items():
        k = mult.get(cname, 1)
        for line in body.splitlines():
            for c in COLLECTIVES:
                if f" {c}(" in line or f" {c}-start(" in line:
                    m = _RESULT_RE.search(line)
                    if not m:
                        continue
                    r = _bytes_of(m.group(1), m.group(2))
                    g = _GROUPS_RE.search(line)
                    S = int(g.group(2)) if g else 2
                    if c == "all-gather":
                        b = r * (S - 1) // max(S, 1)
                    elif c == "reduce-scatter":
                        b = r * (S - 1)
                    elif c == "all-reduce":
                        b = 2 * r * (S - 1) // max(S, 1)
                    elif c == "all-to-all":
                        b = r * (S - 1) // max(S, 1)
                    else:
                        b = r
                    out[c] += b * k
                    counts[c] += k
                    break
    out["total"] = sum(out[c] for c in COLLECTIVES)
    out["op_counts"] = counts
    return out


def _abstract_opt_state(params_abs, opt_cfg):
    from repro.optim import adamw_init
    return jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_abs)


def _serve_cfg(cfg):
    """Serving config: W4A16 via the XLA-fusable dequant+dot formulation —
    GSPMD cannot partition a compiled Pallas kernel, so a step sharded over
    the production mesh runs the HLO formulation (``planning.pallas_lowers``
    makes the same choice for the serving engine on several chips)."""
    return dataclasses.replace(cfg, w4a16_strategy="xla",
                               moe_manual_dispatch=True)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               quantized_serve: bool = True):
    """Build + lower one cell; returns (lowered, meta) or ('skip', reason)."""
    cfg = configs.get_config(arch)
    shape = SHAPES[shape_name]
    skip = skip_reason(cfg, shape)
    if skip:
        return None, {"skipped": skip}
    mesh = make_production_mesh(multi_pod=multi_pod)
    settings = settings_for(arch)
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        # per-microbatch batch must stay DP-shardable: clamp microbatches
        # so (global_batch / micro) % dp_world == 0
        dpw = 1
        for a in ("pod", "data"):
            if a in mesh.axis_names:
                dpw *= mesh.shape[a]
        micro = settings.microbatches
        while micro > 1 and (shape.global_batch // micro) % dpw:
            micro //= 2
        if micro != settings.microbatches:
            settings = dataclasses.replace(settings, microbatches=micro)
        params_abs = T.abstract_params(cfg)
        from repro.optim import AdamWConfig
        opt_cfg = AdamWConfig(state_dtype=settings.opt_dtype)
        opt_abs = _abstract_opt_state(params_abs, opt_cfg)
        inputs_abs = {"batch": specs["batch"],
                      "step": jax.ShapeDtypeStruct((), jnp.int32)}
        with jax.set_mesh(mesh):
            fn = rsteps.jit_train_step(cfg, mesh, settings, params_abs,
                                       inputs_abs, opt_cfg)
            lowered = fn.lower(params_abs, opt_abs, inputs_abs)
        return lowered, {"mesh": mesh, "kind": "train"}

    scfg = _serve_cfg(cfg)
    params_abs = T.abstract_params(scfg)
    if quantized_serve and scfg.quantize_serve:
        params_abs = jax.eval_shape(
            lambda p: T.quantize_params(p, scfg), params_abs)

    if shape.kind == "prefill":
        with jax.set_mesh(mesh):
            fn = rsteps.jit_prefill_step(
                scfg, mesh, cache_len_for(scfg, shape), params_abs, specs,
                fsdp_serve=settings.fsdp_serve)
            lowered = fn.lower(params_abs, specs)
        return lowered, {"mesh": mesh, "kind": "prefill"}

    with jax.set_mesh(mesh):
        fn = rsteps.jit_serve_step(scfg, mesh, params_abs, specs,
                                   fsdp_serve=settings.fsdp_serve)
        lowered = fn.lower(params_abs, specs)
    return lowered, {"mesh": mesh, "kind": "decode"}


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True) -> dict:
    t0 = time.time()
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16"}
    try:
        lowered, meta = lower_cell(arch, shape_name, multi_pod=multi_pod)
    except Exception as e:  # a failure here is a bug in the system
        rec["status"] = "LOWER_FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
        return rec
    if lowered is None:
        rec["status"] = "SKIP"
        rec["skip_reason"] = meta["skipped"]
        return rec
    try:
        compiled = lowered.compile()
    except Exception as e:
        rec["status"] = "COMPILE_FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"[:500]
        return rec
    rec["status"] = "OK"
    rec["kind"] = meta["kind"]
    mem = compiled.memory_analysis()
    try:
        rec["bytes_per_device"] = {
            "argument": mem.argument_size_in_bytes,
            "output": mem.output_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "peak_total": (mem.argument_size_in_bytes
                           + mem.temp_size_in_bytes
                           + mem.output_size_in_bytes),
        }
    except AttributeError:
        rec["bytes_per_device"] = str(mem)
    cost = compiled.cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    rec["cost_xla_raw"] = {
        k: cost.get(k, 0.0) for k in ("flops", "bytes accessed")}
    hlo_text = compiled.as_text()
    rec["cost"] = hlo_costs(hlo_text)        # loop-aware (see hlo_costs)
    rec["collectives"] = collective_bytes(hlo_text)
    rec["seconds"] = round(time.time() - t0, 1)
    if verbose:
        print(json.dumps(rec, indent=None, default=str))
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--json", default=None, help="write records to this file")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(configs.ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    records = []
    fail = 0
    for mp in meshes:
        for a in archs:
            for s in shapes:
                rec = run_cell(a, s, multi_pod=mp)
                records.append(rec)
                if rec["status"] not in ("OK", "SKIP"):
                    fail += 1
    if args.json:
        with open(args.json, "w") as f:
            json.dump(records, f, indent=1, default=str)
    ok = sum(r["status"] == "OK" for r in records)
    sk = sum(r["status"] == "SKIP" for r in records)
    print(f"\n== dry-run: {ok} OK, {sk} skipped, {fail} FAILED "
          f"of {len(records)} cells ==")
    sys.exit(1 if fail else 0)


if __name__ == "__main__":
    main()
