"""Architecture modules: ``dense_decoder`` gives the numbers the harness
counted before it moved there, bit for bit; a module of the tests' own is
taken through a whole run by the harness's loaders and counts; a
configuration that names no module is refused."""
import hashlib
import os

import numpy as np
import pytest

import benchpath  # noqa: F401
from chipbench import arch, work
from test_bench_check import (PEAKS, SEEDS, _isolated, bench,  # noqa: F401
                              files)

run = benchpath.load_run()

TWO_KINDS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "arch_two_kinds.py")
POSITIONS = [3000, 4500, 5000, 7000]
# (flops, bytes) at h2o-danube-1.8b's published widths, and the sha256 of
# make_raw at its reduced sizes and seed 2**31 + 5, from the harness as it
# counted before the architecture moved into models/dense_decoder.py
BEFORE = {
    "gemm_call_1": (3334471680, 887881728),
    "gemm_call_4": (13337886720, 894369792),
    "attn_decode": (3757424640, 940339200),
    "model_flops": 17750671360,
}
RAW_SHA256 = "5d428367445e0256888ed573c0750206c544b94996d1115232f6c9cb2e945c4e"


def danube():
    return run.load_json(run.BENCH_DIR, "configs", "h2o-danube-1.8b.json")


@pytest.mark.parametrize("name", sorted(BEFORE))
def test_dense_decoder_counts_as_before(name):
    cfg = danube()
    got = {"gemm_call_1": lambda: work.gemm_call(cfg, 1),
           "gemm_call_4": lambda: work.gemm_call(cfg, 4),
           "attn_decode": lambda: work.attn_decode(cfg, POSITIONS),
           "model_flops": lambda: work.model_flops(cfg, 4, POSITIONS)}[name]()
    assert got == BEFORE[name]


def test_dense_decoder_weights_as_before():
    cfg = arch.of(danube()).reduced(danube())
    raw = arch.of(cfg).make_raw(cfg, 2 ** 31 + 5)
    h = hashlib.sha256()
    for k in sorted(raw):
        a = np.asarray(raw[k])
        for part in (k, str(a.dtype), str(a.shape)):
            h.update(part.encode())
        h.update(a.tobytes())
    assert h.hexdigest() == RAW_SHA256


def two_kinds_files():
    f = files("danube.long")
    f["config"] = arch.load("two_kinds", TWO_KINDS).reduced(
        dict(f["config"], model="two_kinds"))
    f["model"] = TWO_KINDS
    return f


def test_work_counts_each_layer_at_its_own_window():
    cfg = two_kinds_files()["config"]
    positions = [3, 40, 100]

    def layer(w):
        return sum(np.array(work.attn_row(4, 2, 32,
                                          min(p + 1, w) if w else p + 1))
                   for p in positions)

    sliding, full = layer(16), layer(0)
    assert work.attn_decode(cfg, positions) == tuple(sliding + full)
    dense = dict(cfg, model="dense_decoder")
    assert work.attn_decode(dense, positions) == tuple(2 * sliding)
    assert work.gemm_call(cfg, 3) == work.gemm_call(dense, 3)
    assert (work.model_flops(cfg, 3, positions)
            - work.model_flops(dense, 3, positions) == full[0] - sliding[0])


def test_second_architecture_end_to_end():
    """A module under tests/ goes through run_cell: weights, pytree and
    ModelConfig come from it, and the whole step's share of peak counts
    its work."""
    f = two_kinds_files()
    kept = {}
    res = run.run_cell(bench(), "danube.long", SEEDS[0], 1.5, True,
                       peaks=PEAKS, files=f, keep=kept,
                       t_start=run.serving.clock())
    mod = arch.of(f["config"])
    assert mod.CALLS == ["make_raw", "program_params", "program_config"]
    assert res["correct"], res["check"]
    steps = kept["record"].window_steps()
    wall = sum(s.t1 - s.t0 for s in steps)
    flops = sum(mod.model_flops(f["config"], len(s.decode_pos), s.decode_pos)
                for s in steps)
    dense = sum(work.model_flops(dict(f["config"], model="dense_decoder"),
                                 len(s.decode_pos), s.decode_pos)
                for s in steps)
    assert flops > dense
    assert res["metrics"]["step_mfu.tput"]["value"] == pytest.approx(
        100.0 * flops / (wall * PEAKS["bf16_flops_per_s"]))


@pytest.mark.parametrize("via", ["run_cell", "work"])
def test_config_without_model_is_refused(via):
    f = files("danube.long")
    del f["config"]["model"]
    with pytest.raises(ValueError, match='"model"'):
        if via == "run_cell":
            run.run_cell(bench(), "danube.long", 1, 1.0, False, peaks=PEAKS,
                         files=f)
        else:
            work.gemm_call(f["config"], 1)
