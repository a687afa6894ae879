"""Serving metrics plane: counters, gauges, histograms + percentiles, and
host spans.

One small registry shared by the serving stack: the engine samples it once
per :meth:`ServingEngine.step` (queue depth, active slots, pages in use,
TTFT, per-step decode time), the front door (``runtime/frontdoor.py``)
adds admission-side series (queue wait, 429/408 rejections, cancels), and
``GET /metrics`` renders the whole registry in Prometheus text exposition
format. The same nearest-rank percentile helpers back
:meth:`ServeReport.latency_stats`, so the CLI report, the final
``ServeReport`` and the ``/metrics`` endpoint can never disagree on what
"p99" means.

:class:`SpanRecorder` is the tracing beside the registry: a bounded
in-memory record of the engine's host spans (``serve.step`` and its
phases, see ``docs/serving.md``), each also entered as a
``jax.profiler.TraceAnnotation`` so that a profiled slice carries it on the
device trace's clock, plus the process's backend compiles and GC passes.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import itertools
import math
import threading
import time
from typing import Any, Deque, Dict, Iterable, List, Optional, Sequence

import jax

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "Span",
    "SpanRecorder", "nearest_rank", "summarize",
]

QUANTILES = (0.5, 0.95, 0.99)


def nearest_rank(values: Sequence[float], q: float) -> float:
    """Nearest-rank percentile: the smallest element with at least
    ``ceil(q * n)`` elements ≤ it. Exact (no interpolation), so two code
    paths computing "p99" over the same samples agree bit-for-bit."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile must be in (0, 1], got {q}")
    vs = sorted(values)
    if not vs:
        return 0.0
    return float(vs[max(1, math.ceil(q * len(vs))) - 1])


def summarize(values: Sequence[float]) -> Dict[str, float]:
    """p50/p95/p99 + mean/max summary of a latency sample set."""
    vs = list(values)
    out = {f"p{int(q * 100)}": nearest_rank(vs, q) for q in QUANTILES}
    out["max"] = float(max(vs)) if vs else 0.0
    out["mean"] = float(sum(vs) / len(vs)) if vs else 0.0
    out["count"] = float(len(vs))
    return out


class _Metric:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help

    def render(self) -> List[str]:
        raise NotImplementedError

    def _header(self) -> List[str]:
        lines = []
        if self.help:
            lines.append(f"# HELP {self.name} {self.help}")
        lines.append(f"# TYPE {self.name} {self.kind}")
        return lines


class Counter(_Metric):
    """Monotonic event count."""

    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self.value = 0

    def inc(self, n: int = 1) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease (n={n})")
        self.value += n

    def render(self) -> List[str]:
        return self._header() + [f"{self.name} {self.value}"]


class Gauge(_Metric):
    """Point-in-time value (queue depth, pages in use); tracks its peak."""

    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self.value = 0.0
        self.peak = 0.0

    def set(self, v: float) -> None:
        self.value = float(v)
        self.peak = max(self.peak, self.value)

    def render(self) -> List[str]:
        return self._header() + [f"{self.name} {_fmt(self.value)}"]


class Histogram(_Metric):
    """Sample store with exact nearest-rank quantiles.

    Serving runs here are bounded (one report per run), so every sample is
    kept and quantiles are exact — rendered as a Prometheus *summary*
    (which is what client-side exact quantiles are), not a bucketed
    histogram approximation.
    """

    kind = "summary"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self.values: List[float] = []
        self.sum = 0.0

    def observe(self, v: float) -> None:
        self.values.append(float(v))
        self.sum += float(v)

    @property
    def count(self) -> int:
        return len(self.values)

    def percentile(self, q: float) -> float:
        return nearest_rank(self.values, q)

    def summary(self) -> Dict[str, float]:
        return summarize(self.values)

    def render(self) -> List[str]:
        lines = self._header()
        for q in QUANTILES:
            lines.append(
                f'{self.name}{{quantile="{q}"}} {_fmt(self.percentile(q))}')
        lines.append(f"{self.name}_sum {_fmt(self.sum)}")
        lines.append(f"{self.name}_count {self.count}")
        return lines


def _fmt(v: float) -> str:
    return repr(int(v)) if float(v).is_integer() else repr(float(v))


class MetricsRegistry:
    """Get-or-create registry of named metrics, rendered as one page.

    The registry is touched from the asyncio event loop (front door) and
    from the engine-step executor thread; every mutation is a single
    attribute update on a metric object, but get-or-create itself is
    locked so two threads can't race a metric into existence twice.
    """

    def __init__(self):
        self._metrics: Dict[str, _Metric] = {}
        self._lock = threading.Lock()

    def _get(self, cls, name: str, help: str) -> _Metric:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls(name, help)
            elif not isinstance(m, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {m.kind}, "
                    f"not {cls.kind}")
            return m

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get(Gauge, name, help)

    def histogram(self, name: str, help: str = "") -> Histogram:
        return self._get(Histogram, name, help)

    def get(self, name: str) -> Optional[_Metric]:
        return self._metrics.get(name)

    def names(self) -> Iterable[str]:
        return self._metrics.keys()

    def snapshot(self) -> Dict[str, object]:
        """Plain-dict view (reports, tests, JSON artifacts)."""
        out: Dict[str, object] = {}
        for name, m in self._metrics.items():
            if isinstance(m, Histogram):
                out[name] = m.summary()
            elif isinstance(m, Gauge):
                out[name] = {"value": m.value, "peak": m.peak}
            else:
                out[name] = m.value
        return out

    def render(self) -> str:
        """Prometheus text exposition format (the ``GET /metrics`` body)."""
        lines: List[str] = []
        for name in sorted(self._metrics):
            lines.extend(self._metrics[name].render())
        return "\n".join(lines) + "\n"


@dataclasses.dataclass
class Span:
    """One host span, ``[start_ns, end_ns)`` on ``time.perf_counter_ns``.

    ``parent`` is the id of the span open around it on its thread (None at
    the top), ``step`` the engine step it belongs to, ``rid`` the request
    it serves where there is one; ``counts`` holds the work counted at that
    boundary (and, on ``serve.compile``, the compiled function's name)."""

    name: str
    start_ns: int
    end_ns: int
    id: int
    parent: Optional[int]
    step: Optional[int]
    rid: Optional[int] = None
    counts: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur_ns(self) -> int:
        return self.end_ns - self.start_ns


class SpanRecorder:
    """Bounded in-memory record of host spans: the newest ``capacity``
    closed spans, in the order they closed.

    :meth:`span` times a block and enters ``jax.profiler.TraceAnnotation``
    of the same name around it (with ``step`` and ``rid``), so a profiled
    slice carries the span on the device trace's clock. Beside the caller's
    spans the recorder adds, while it is open, one ``serve.compile`` per
    XLA backend compile (JAX's ``backend_compile_duration`` event, with
    ``fun_name``) and one ``serve.gc`` per Python GC pass (``gc.callbacks``,
    with ``generation``), each a child of the span open on its thread when
    it ended. :meth:`close` (or leaving the ``with`` block) removes those
    hooks."""

    COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self, capacity: int = 1 << 16):
        self.spans: Deque[Span] = collections.deque(maxlen=int(capacity))
        self.step: Optional[int] = None
        self._ids = itertools.count(1)
        self._local = threading.local()
        self._gc_start: Optional[int] = None
        gc.callbacks.append(self._on_gc)
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        self._hooked = True

    def close(self) -> None:
        if self._hooked:
            gc.callbacks.remove(self._on_gc)
            jax.monitoring.unregister_event_duration_listener(self._on_event)
            self._hooked = False

    def snapshot(self) -> List[Span]:
        """The recorded spans as a list. While the recorder is open a GC
        pass can append to ``spans`` at any allocation, so iterate over
        this copy, taken with the collector off, and not over ``spans``."""
        was = gc.isenabled()
        gc.disable()
        try:
            return list(self.spans)
        finally:
            if was:
                gc.enable()

    def __enter__(self) -> "SpanRecorder":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def _stack(self) -> List[Span]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    @contextlib.contextmanager
    def span(self, name: str, rid: Optional[int] = None,
             step: Optional[int] = None):
        """Record ``name`` around the block; yields the :class:`Span`, whose
        ``counts`` the block may fill. ``step`` sets the recorder's current
        step number, which this and every later span carries."""
        if step is not None:
            self.step = step
        stack = self._stack()
        s = Span(name, 0, 0, next(self._ids),
                 stack[-1].id if stack else None, self.step, rid)
        tags = {} if self.step is None else {"step": self.step}
        if rid is not None:
            tags["rid"] = rid
        stack.append(s)
        try:
            with jax.profiler.TraceAnnotation(name, **tags):
                s.start_ns = time.perf_counter_ns()
                yield s
        finally:
            s.end_ns = time.perf_counter_ns()
            stack.pop()
            self.spans.append(s)

    def _add(self, name: str, start_ns: int, end_ns: int, **counts) -> None:
        stack = self._stack()
        self.spans.append(Span(name, start_ns, end_ns, next(self._ids),
                               stack[-1].id if stack else None, self.step,
                               counts=counts))

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        if phase == "start":
            self._gc_start = time.perf_counter_ns()
        elif self._gc_start is not None:
            self._add("serve.gc", self._gc_start, time.perf_counter_ns(),
                      generation=info.get("generation"))
            self._gc_start = None

    def _on_event(self, event: str, duration: float, **kw) -> None:
        if event == self.COMPILE_EVENT:
            end = time.perf_counter_ns()
            self._add("serve.compile", end - int(duration * 1e9), end,
                      fun_name=str(kw.get("fun_name", "?")))
