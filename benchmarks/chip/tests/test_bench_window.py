"""Window arithmetic: rates are taken over the whole window; the inter-token
tail is taken over every gap inside it."""
import types

import numpy as np
import pytest

import benchpath  # noqa: F401
from chipbench import serving, traffic, window


def record(seconds=10.0):
    return serving.Record(seconds=seconds, max_batch=4)


def req(rid, n=6, out=3):
    return traffic.Req(rid, np.zeros(n, np.int32), out)


def test_rate_is_over_the_whole_window():
    rec = record(10.0)
    rec.times = {0: [0.1, 0.2, 0.3], 1: [-0.5, 9.9, 10.0]}
    assert window.tokens_in_window(rec) == 4
    assert window.tokens_in_window(rec) / rec.seconds == pytest.approx(0.4)


def test_inter_token_samples_span_a_quarter_second():
    """Every gap between successive tokens inside the window is a sample,
    however short, so one stalled step shows in the tail undiluted."""
    rec = record(10.0)
    steady = list(np.arange(0.0, 2.0, 0.01))
    rec.times = {0: steady, 1: [-0.2, 0.5, 0.7, 9.95, 10.3]}
    s = window.itl_samples(rec)
    assert len(s) == 199 + 2 and max(s) == pytest.approx(9.25)
    assert sorted(s)[:199] == pytest.approx([0.01] * 199)
    stall = [t if t < 1.0 else t + 0.2 for t in steady]
    rec.times = {0: stall}
    s = window.itl_samples(rec)
    assert max(s) == pytest.approx(0.21)
    assert window.nearest_rank(s, 99.5) == pytest.approx(0.21)
    assert window.nearest_rank([1, 2, 3, 4], 50) == 2
    assert window.nearest_rank(list(range(1, 101)), 95) == 95


def test_step_record_works_out_chunks_and_decode_rows():
    """Each token after a request's first is one decode row, at the
    position it wrote; the steps of its prefill hold none."""
    rec = record()
    rec.reqs = {0: req(0, n=6, out=3)}

    def ev(admitted=(), emitted=None, finished=()):
        return types.SimpleNamespace(admitted=list(admitted),
                                     emitted=emitted or {},
                                     finished=list(finished))
    rec.on_step(0.0, 0.1, ev(admitted=[0]))
    rec.on_step(0.1, 0.2, ev(emitted={0: [5, 6]}))
    rec.on_step(0.2, 0.3, ev(emitted={0: [7]}, finished=[0]))
    assert [s.decode_pos for s in rec.steps] == [[], [6], [7]]
    assert rec.tokens[0] == [5, 6, 7] and rec.times[0] == [0.2, 0.2, 0.3]
