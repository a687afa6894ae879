"""Build the serving engine as ``launch/serve.py`` does, warm it, and drive
it through its stepper API on a clock: ``start()``, ``submit(req)``,
``step()`` -> ``StepEvents``.

The driver records, on the host clock and relative to the window's start,
when each request's tokens came back, and per engine step the decode rows
it ran: each token after a request's first is one decode row. A sessions
mix runs every prefill chunk in set-up, so the window's steps run decode
rows only; ``StepEvents`` does not report chunks, and nothing here infers
them.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import arch, traffic as traffic_mod

clock = time.perf_counter


@dataclasses.dataclass
class StepRow:
    t0: float
    t1: float
    decode_pos: List[int]        # position each decode row wrote


@dataclasses.dataclass
class Record:
    """What one run served, on the window's clock (seconds from its start)."""

    seconds: float
    max_batch: int
    reqs: Dict[int, traffic_mod.Req] = dataclasses.field(default_factory=dict)
    tokens: Dict[int, List[int]] = dataclasses.field(default_factory=dict)
    times: Dict[int, List[float]] = dataclasses.field(default_factory=dict)
    steps: List[StepRow] = dataclasses.field(default_factory=list)
    compiles_in_window: int = 0
    compiled_in_window: List[str] = dataclasses.field(default_factory=list)

    def on_step(self, t0: float, t1: float, ev) -> None:
        decode_pos = []
        for rid, toks in ev.emitted.items():
            have = self.tokens.setdefault(rid, [])
            times = self.times.setdefault(rid, [])
            S = len(self.reqs[rid].prompt)
            for tok in toks:
                if have:
                    decode_pos.append(S + len(have) - 1)
                have.append(int(tok))
                times.append(t1)
        self.steps.append(StepRow(t0, t1, decode_pos))

    def window_steps(self) -> List[StepRow]:
        return [s for s in self.steps if s.t0 >= 0.0 and s.t1 <= self.seconds]


class CompileCounter:
    """Counts XLA executables built or loaded from the cache in the process
    (JAX's backend-compile event), with their names. One per process:
    :func:`compile_counter`."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names: List[str] = []
        from jax._src import monitoring
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


_COUNTER: List[CompileCounter] = []


def compile_counter() -> CompileCounter:
    if not _COUNTER:
        _COUNTER.append(CompileCounter())
    return _COUNTER[0]


def build_engine(cfgj: dict, traffic: dict, params, quant_format: str):
    """A ServingEngine with the configuration's serving settings, sized for
    the traffic's longest prompt and output."""
    from repro.launch.presets import serve_settings_for
    from repro.runtime.engine import ServingEngine

    cfg = arch.of(cfgj).program_config(cfgj, quant_format)
    sset = serve_settings_for(cfg.name)
    prompt_max, out_max = traffic_mod.limits(traffic)
    return ServingEngine(
        cfg, params, max_batch=int(traffic["slots"]),
        max_prompt_len=prompt_max, max_new_tokens=out_max,
        page_size=cfgj["page_size"], prefill_chunk=cfgj["prefill_chunk"],
        kv_format=cfgj["kv_cache"], warm_cache_mb=sset.warm_cache_mb,
        speculate=cfgj.get("speculate", sset.speculate),
        spec_k=sset.spec_k, attn_path=cfgj.get("attn_path", sset.attn_path))


def warm_up(engine) -> None:
    """Compile the step programs before set-up builds anything: a short
    real run through the stepper (one prefill chunk and a decode step), then
    the first-token argmax over the slots that finish prefill in one step,
    at 1 to ``max_batch`` rows, as ``runtime/engine.py``
    (``_flush_first_tokens``) runs it. Whatever this misses shows up in the
    run's count of compilations inside the window."""
    from repro.runtime.engine import Request

    C = engine.prefill_chunk
    engine.start()
    engine.submit(Request(rid=-1, prompt=np.zeros(C + 1, np.int32),
                          max_new_tokens=2))
    engine.drain()
    row = jnp.zeros((engine.cfg.padded_vocab,), jnp.float32)
    int(jnp.argmax(jnp.zeros((1, row.shape[0]), jnp.float32)[0]))
    for m in range(2, engine.max_batch + 1):
        np.asarray(jnp.argmax(jnp.stack([row] * m), axis=-1))
    engine.start()


def build_sessions(engine, reqs: List[traffic_mod.Req], rec: Record) -> None:
    """Set-up of a sessions mix: admit every session and step until each
    has finished its prefill and is decoding. These steps sit before the
    window on the record's clock."""
    from repro.runtime.engine import Request

    origin = clock()
    for r in reqs:
        rec.reqs[r.rid] = r
        engine.submit(Request(rid=r.rid, prompt=r.prompt,
                              max_new_tokens=r.max_new))
    while len(rec.tokens) < len(reqs) and engine.has_work():
        t0 = clock() - origin - 1e9
        ev = engine.step()
        rec.on_step(t0, clock() - origin - 1e9, ev)


def drive(engine, rec: Record, *, counter: Optional[CompileCounter] = None,
          tracer=None) -> None:
    """Step the engine from now until the window ends, ``[0, rec.seconds)``
    on a clock whose zero is this call's start. ``tracer`` (optional) is
    told the time before each step so that it can switch the profiler on
    and off between steps."""
    annotate = jax.profiler.TraceAnnotation
    origin = clock()
    n_compiled = len(counter.names) if counter is not None else 0
    while engine.has_work():
        now = clock() - origin
        if now >= rec.seconds:
            break
        if tracer is not None:
            tracer.between_steps(now)
        t0 = clock() - origin
        with annotate("engine.step"):
            ev = engine.step()
        rec.on_step(t0, clock() - origin, ev)
    if tracer is not None:
        tracer.between_steps(float("inf"))
    if counter is not None:
        names = counter.names[n_compiled:]
        rec.compiles_in_window = len(names)
        rec.compiled_in_window = names
