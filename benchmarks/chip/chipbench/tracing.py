"""The profiler trace of a slice of the window, and its reduction.

A traced run switches JAX's profiler on between two engine steps in the
middle of the window and off again ``length`` seconds later, so the slice
holds whole steps only. :func:`reduce` reads the ``.xplane.pb`` it wrote
with nothing but JAX and returns, on the slice's own clock:

- device op intervals (TPU planes, line "XLA Ops"), each with its HLO op
  name, module and metadata (``tf_op`` op name and ``source`` where the
  profiler gives them), for busy time and kernel jobs;
- program executions (line "XLA Modules"), for per-program device time;
- the harness's host spans (``TraceAnnotation`` names), for attributing
  device idle gaps to what the host was doing.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

import jax

HARNESS_SPANS = ("engine.step",)


@dataclasses.dataclass
class Op:
    name: str
    module: str
    start: float             # seconds from the slice's first host span
    dur: float
    meta: str                # op name / source metadata, searchable
    device: str
    run: str = ""            # execution id, where the trace gives one


@dataclasses.dataclass
class TraceSummary:
    ops: List[Op]
    modules: List[Op]
    spans: List[Tuple[str, float, float]]     # host (name, start, end)
    window_s: float
    devices: List[str]

    def busy_s(self) -> float:
        """Union of device op intervals, averaged over the devices."""
        total = 0.0
        for dev in self.devices:
            iv = sorted((o.start, o.start + o.dur) for o in self.ops
                        if o.device == dev)
            total += _union(iv)
        return total / max(1, len(self.devices))

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Intervals inside the slice in which no op ran (first device)."""
        if not self.devices:
            return []
        dev = self.devices[0]
        iv = sorted((o.start, o.start + o.dur) for o in self.ops
                    if o.device == dev)
        gaps, t = [], 0.0
        for a, b in iv:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if self.window_s > t:
            gaps.append((t, self.window_s))
        return gaps

    def module_calls(self, pattern: str) -> List[Op]:
        rx = re.compile(pattern)
        return [m for m in self.modules if rx.search(m.name)]


def _union(iv) -> float:
    total, end = 0.0, float("-inf")
    for a, b in iv:
        if b <= end:
            continue
        total += b - max(a, end)
        end = b
    return total


class Tracer:
    """Switches the profiler on at the first step boundary at or after
    ``start_at`` (window clock) and off at the first one ``length`` seconds
    later; the trace goes to ``out_dir``."""

    def __init__(self, out_dir: str, start_at: float, length: float):
        self.out_dir = out_dir
        self.start_at = start_at
        self.length = length
        self.on_at: Optional[float] = None
        self.off_at: Optional[float] = None

    def between_steps(self, now: float) -> None:
        if self.on_at is None and now >= self.start_at \
                and now != float("inf"):
            jax.profiler.start_trace(self.out_dir)
            self.on_at = now
        elif self.on_at is not None and self.off_at is None \
                and now >= self.on_at + self.length:
            jax.profiler.stop_trace()
            self.off_at = now

    def path(self) -> str:
        found = glob.glob(os.path.join(self.out_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise FileNotFoundError(f"no trace under {self.out_dir}")
        return max(found, key=os.path.getmtime)


def _stats(ev) -> Dict[str, object]:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def _op(ev, device: str) -> Op:
    st = _stats(ev)
    meta = " ".join(str(st.get(k, "")) for k in
                    ("tf_op", "source", "long_name", "hlo_category"))
    return Op(ev.name, str(st.get("hlo_module", "")), ev.start_ns * 1e-9,
              ev.duration_ns * 1e-9, meta, device, str(st.get("run_id", "")))


def _modules_from_ops(ops: List[Op]) -> List[Op]:
    """Program executions rebuilt from op events where the trace has no
    module line: the ops of one module and execution id form one."""
    runs: Dict[tuple, Op] = {}
    for o in ops:
        key = (o.module, o.run)
        m = runs.get(key)
        if m is None:
            runs[key] = Op(o.module, o.module, o.start, o.dur, "", o.device,
                           o.run)
        else:
            end = max(m.start + m.dur, o.start + o.dur)
            m.start = min(m.start, o.start)
            m.dur = end - m.start
    return sorted(runs.values(), key=lambda m: m.start)


def reduce(path: str) -> TraceSummary:
    """Read one ``.xplane.pb`` into a :class:`TraceSummary`.

    Times are re-based on the first harness host span, so the slice runs
    from 0 to ``window_s`` (the end of the last harness span)."""
    pd = jax.profiler.ProfileData.from_file(path)
    ops, modules, spans, devices = [], [], [], []
    host_ops = []
    for plane in pd.planes:
        on_device = plane.name.startswith("/device:") and \
            "TPU" in plane.name and "NON_CORE" not in plane.name.upper()
        for line in plane.lines:
            if on_device and line.name in ("XLA Ops", "XLA Modules"):
                bucket = ops if line.name == "XLA Ops" else modules
                if plane.name not in devices:
                    devices.append(plane.name)
                for ev in line.events:
                    bucket.append(_op(ev, plane.name))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name in HARNESS_SPANS:
                        spans.append((ev.name, ev.start_ns * 1e-9,
                                      (ev.start_ns + ev.duration_ns) * 1e-9))
                    elif "hlo_op" in _stats(ev):
                        host_ops.append(_op(ev, plane.name))
    if not devices and host_ops:
        # a CPU backend runs its ops on host threads: one "device"
        ops = [dataclasses.replace(o, device="cpu") for o in host_ops]
        devices = ["cpu"]
        modules = _modules_from_ops(ops)
    if not spans:
        raise ValueError(f"{path}: no harness span in the trace")
    t0 = min(s[1] for s in spans)
    t1 = max(s[2] for s in spans)

    def rebase(items):
        for o in items:
            o.start -= t0
        return [o for o in items if o.start + o.dur > 0 and o.start < t1 - t0]

    return TraceSummary(
        ops=rebase(ops), modules=rebase(modules),
        spans=sorted((n, a - t0, b - t0) for n, a, b in spans),
        window_s=t1 - t0, devices=devices)

