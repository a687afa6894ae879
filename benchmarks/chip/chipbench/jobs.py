"""Kernel jobs, one file each under ``jobs/``: which device ops do the job
(``MATCH``, a pattern searched in an op's name and metadata, less those
matching ``EXCLUDE`` where a job has one) and the least time its work needs
(``least_time(cfgj, steps, peaks)``)."""
from __future__ import annotations

import os
import re

from chipbench import arch


def load(bench_dir: str, name: str):
    mod = arch.load_file(os.path.join(bench_dir, "jobs", name + ".py"),
                         f"job_{name}")
    mod.PATTERN = re.compile(mod.MATCH)
    mod.NOT = re.compile(mod.EXCLUDE) if getattr(mod, "EXCLUDE", None) \
        else None
    return mod


def assigned(job, op) -> bool:
    text = op.name + " " + op.meta
    return bool(job.PATTERN.search(text)) and not (
        job.NOT is not None and job.NOT.search(text))


def device_time(job, trace) -> float:
    """Summed device time of the ops the job's patterns assign to it."""
    return sum(o.dur for o in trace.ops if assigned(job, o))


def roofline_pct(ctx, job_name: str):
    """Least time of the job's work over the device time of its ops, in %;
    None where the trace assigns no op to the job."""
    if ctx.trace is None:
        return None
    job = load(ctx.bench_dir, job_name)
    t = device_time(job, ctx.trace)
    if t <= 0.0:
        return None
    return 100.0 * job.least_time(ctx.cfgj, ctx.traced_steps,
                                  ctx.peaks) / t
