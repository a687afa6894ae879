"""Host spans of the serving engine (``runtime/metrics.SpanRecorder``): a
toy paged engine with chunked prefill, stepped on the CPU, records
``serve.step`` and its phases with the work counted at each."""
import collections
import dataclasses
import gc

import jax
import pytest

from repro import configs
from repro.models import transformer as T
from repro.runtime import metrics as rmetrics
from repro.runtime.engine import Request, ServingEngine

KEY = jax.random.PRNGKey(3)
CHUNK = 4
PROMPTS = (9, 6, 11)          # 3, 2 and 3 chunks; two slots, so the third
GEN = 4                       # request is admitted when the first finishes


def _engine(**kw):
    cfg = dataclasses.replace(configs.get_reduced("h2o-danube-1.8b"),
                              w4a16_strategy="xla")
    params = T.quantize_params(T.init_params(KEY, cfg), cfg, min_size=0)
    return ServingEngine(cfg, params, max_batch=2, max_prompt_len=12,
                         max_new_tokens=GEN, page_size=4,
                         prefill_chunk=CHUNK, **kw)


def _requests(vocab):
    toks = jax.random.randint(KEY, (len(PROMPTS), max(PROMPTS)), 0, vocab)
    return [Request(rid=i, prompt=toks[i, :n], max_new_tokens=GEN)
            for i, n in enumerate(PROMPTS)]


@pytest.fixture(scope="module")
def engine():
    return _engine()


@pytest.fixture(scope="module")
def spec_engine():
    return _engine(speculate="ngram", spec_k=2)


def _serve(eng, rec=None):
    """Step ``eng`` to completion; returns (events, served tokens)."""
    eng.spans = rec
    eng.start()
    for r in _requests(eng.cfg.vocab_size):
        eng.submit(r)
    events = []
    while eng.has_work():
        events.append(eng.step())
    eng.spans = None
    return events, {r: list(t) for r, t in eng.report.results.items()}


def _children(spans):
    kids = collections.defaultdict(list)
    for s in spans:
        kids[s.parent].append(s)
    return kids


def test_spans_nest_inside_their_parents(engine):
    with rmetrics.SpanRecorder() as rec:
        _serve(engine, rec)
    spans = list(rec.spans)
    by_id = {s.id: s for s in spans}
    names = {s.name for s in spans}
    assert {"serve.step", "serve.admit", "serve.prefill_chunk",
            "serve.first_tokens", "serve.pages", "serve.inputs",
            "serve.dispatch", "serve.readback", "serve.collect"} <= names
    for s in spans:
        assert s.start_ns <= s.end_ns
        if s.name == "serve.step":
            assert s.parent is None
            continue
        if s.parent is None:         # a compile or GC pass between steps
            assert s.name in ("serve.compile", "serve.gc")
            continue
        p = by_id[s.parent]
        assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns
        assert s.step == p.step
    assert {s.rid for s in spans if s.name == "serve.admit"} == {0, 1, 2}


@pytest.mark.parametrize("which", ["engine", "spec_engine"])
def test_each_decoding_step_dispatches_and_reads_back_once(which, request):
    eng = request.getfixturevalue(which)
    with rmetrics.SpanRecorder() as rec:
        _serve(eng, rec)
    kids = _children(rec.spans)
    steps = [s for s in rec.spans if s.name == "serve.step"]
    assert steps
    decoding = 0
    for s in steps:
        n = collections.Counter(c.name for c in kids[s.id])
        want = 1 if s.counts["decode_rows"] else 0
        decoding += want
        assert n["serve.dispatch"] == want and n["serve.readback"] == want
        assert n["serve.collect"] == want
    assert decoding > 0


def test_step_counts_equal_the_work_done(engine):
    with rmetrics.SpanRecorder() as rec:
        events, served = _serve(engine, rec)
    steps = {s.step: s.counts for s in rec.spans if s.name == "serve.step"}
    assert sorted(steps) == [ev.step for ev in events]
    had = collections.Counter()
    for ev in events:
        rows = 0
        for rid, toks in ev.emitted.items():
            rows += len(toks) - (0 if had[rid] else 1)
            had[rid] += len(toks)
        c = steps[ev.step]
        assert c["decode_rows"] == rows
        assert c["admitted"] == len(ev.admitted)
        assert c["finished"] == len(ev.finished)
    total = collections.Counter()
    for c in steps.values():
        total.update(c)
    assert total["prefill_chunks"] == sum(-(-n // CHUNK) for n in PROMPTS)
    assert total["prefill_tokens"] == sum(PROMPTS)
    assert total["decode_rows"] == sum(len(t) - 1 for t in served.values())
    assert total["pages_allocated"] > 0
    chunks = [s for s in rec.spans if s.name == "serve.prefill_chunk"]
    assert sum(s.counts["tokens"] for s in chunks) == sum(PROMPTS)
    pages = [s for s in rec.spans if s.name == "serve.pages"]
    assert all(s.counts["allocated"] >= 0 for s in pages)


def test_a_recompile_is_a_compile_span_inside_dispatch(engine):
    _serve(engine)                              # every program compiled
    with rmetrics.SpanRecorder() as rec:
        engine.spans = rec
        engine.start()
        for r in _requests(engine.cfg.vocab_size):
            engine.submit(r)
        while engine.has_work():
            if any(s is not None and s.phase == "active"
                   for s in engine._slots):
                engine._serve_fns.clear()       # force the next decode
            engine.step()                       # step to compile again
        engine.spans = None
    by_id = {s.id: s for s in rec.spans}
    compiles = [s for s in rec.spans if s.name == "serve.compile"
                and "serve_step" in s.counts["fun_name"]]
    assert compiles
    for c in compiles:
        assert by_id[c.parent].name == "serve.dispatch"


def test_a_gc_pass_inside_a_step_is_recorded(engine, monkeypatch):
    real = engine._flush_first_tokens

    def flush(pending):
        gc.collect()
        return real(pending)

    monkeypatch.setattr(engine, "_flush_first_tokens", flush)
    with rmetrics.SpanRecorder() as rec:
        _serve(engine, rec)
    by_id = {s.id: s for s in rec.spans}
    full = [s for s in rec.spans if s.name == "serve.gc"
            and s.counts["generation"] == 2 and s.parent is not None
            and by_id[s.parent].name == "serve.first_tokens"]
    assert full


def test_recorder_keeps_only_the_newest_spans(engine):
    with rmetrics.SpanRecorder(capacity=16) as rec:
        _serve(engine, rec)
    spans = list(rec.spans)
    assert len(spans) == 16
    assert spans[-1].name == "serve.step"
    assert spans[-1].step == engine.report.steps - 1


def test_snapshot_can_be_walked_while_gc_passes_are_recorded():
    with rmetrics.SpanRecorder() as rec:
        gc.collect()
        gc.collect()
        seen = 0
        for _ in rec.snapshot():
            gc.collect()                # each pass appends a serve.gc span
            seen += 1
        assert seen >= 2 and len(rec.spans) >= 2 * seen
        with pytest.raises(RuntimeError, match="mutated"):
            for _ in rec.spans:
                gc.collect()


def test_close_removes_the_process_hooks():
    rec = rmetrics.SpanRecorder()
    rec.close()
    rec.close()
    gc.collect()
    jax.jit(lambda x: x * 7 + 1)(3.0).block_until_ready()
    assert not rec.spans


def test_tracing_off_enters_no_annotation_and_serves_the_same(
        engine, monkeypatch):
    entered = []

    class Counting:
        def __init__(self, name, **kw):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    _, off = _serve(engine)
    assert entered == []
    with rmetrics.SpanRecorder() as rec:
        _, on = _serve(engine, rec)
    assert off == on and sorted(on) == [0, 1, 2]
    spans = [s.name for s in rec.spans
             if s.name not in ("serve.compile", "serve.gc")]
    assert sorted(entered) == sorted(spans)
