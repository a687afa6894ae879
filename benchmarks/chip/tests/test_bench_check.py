"""The output check, driven through a whole run on the CPU at reduced
sizes (the harness's look for a chip skipped): it passes the program on
large seeds, traced and untraced, and fails the lower-precision controls
and a broken timed path.

Every cell is served at the sizes its architecture module's ``reduced()``
gives, under the ``reduced`` block of its traffic file: for ``danube.long``
2 layers, width 128, 4/2 heads of 32, vocabulary 512, window 16, pages of
8, chunks of 8; four sessions of 20-70 tokens decoding 160 more, so every
session's ring wraps. The limits here sit between the program's readings on
these sizes over the seeds below and the controls' (see LIMITS).
"""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

import benchpath  # noqa: F401
from chipbench import arch, check

run = benchpath.load_run()

SEEDS = (1319105951, 2 ** 31 + 5, 3000000019)
LIMITS = {"sample": {"min_tokens": 120, "max_positions": 4000},
          "max_logit_gap": {"limit": 0.1},
          "mean_logit_gap": {"limit": 0.01},
          "tokens_compared": {"limit": 60}}
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


class StepClock:
    """A clock that advances a fixed 2 ms per reading, so that a window
    holds the same engine steps however loaded the test machine is."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 0.002
        return self.t


@pytest.fixture(autouse=True)
def _isolated(monkeypatch, tmp_path):
    monkeypatch.setattr(run, "enable_compile_cache", lambda: None)
    monkeypatch.setattr(run, "TRACE_DIR", str(tmp_path / "trace"))
    monkeypatch.setattr(run.serving, "clock", StepClock())


def bench():
    return run.load_json(run.ROOT, "BENCHMARK.json")


def files(workload):
    b = bench()
    cell = next(w for w in b["workloads"] if w["name"] == workload)
    conf = next(c for c in b["configs"] if c["name"] == cell["config"])
    cfg = run.load_json(run.ROOT, conf["file"])
    mix = run.load_json(run.BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return {"config": arch.of(cfg).reduced(cfg), "traffic": mix["reduced"],
            "limits": LIMITS}


def serve(workload, seed, trace=False, hook=None, keep=None, seconds=1.5,
          quant_format="w4a16_g128"):
    return run.run_cell(bench(), workload, seed, seconds, trace, peaks=PEAKS,
                        files=files(workload), engine_hook=hook, keep=keep,
                        quant_format=quant_format,
                        t_start=run.serving.clock())


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_passes_on_large_seeds(seed, trace):
    res = serve("danube.long", seed, trace)
    assert res["correct"], res["check"]
    assert list(res)[-1] == "check"
    assert res["check"]["tokens_compared"]["value"] >= 60
    names = set(res["metrics"])
    if trace:
        assert res["device"]["busy_s"] > 0.0
        assert {"decode_step_ms", "device_idle_share.tput"} <= names
    else:
        assert {"itl_p95_ms", "output_tokens_per_s", "setup_s"} <= names
    json.dumps(res)


@pytest.mark.parametrize("workload", [w["name"] for w in bench()["workloads"]])
def test_other_cells_pass(workload):
    """Every cell passes, compiles nothing inside its window, and runs only
    decode rows there (each session's context was built in set-up)."""
    kept = {}
    res = serve(workload, SEEDS[0], keep=kept)
    assert res["correct"], res["check"]
    assert res["compiles_in_window"] == 0
    steps = kept["record"].window_steps()
    assert steps and all(s.decode_pos for s in steps)


def test_float8_control_fails():
    kept = {}
    res = serve("danube.long", SEEDS[1], keep=kept)
    assert res["correct"]
    gaps = np.concatenate([
        check.control_gaps(kept["reference"], kept["raw"], kept["cfgj"],
                           *kept["served"][r], jnp.float8_e4m3fn)
        for r in kept["picked"]])
    verdict = check.judge(gaps, LIMITS)
    assert not verdict.correct
    assert verdict.numbers["max_logit_gap"]["value"] > 0.1


def _wrap_decode(engine, change):
    get = engine._serve_step
    calls = [0]

    def lookup(*key):
        step = get(*key)

        def call(params, inputs):
            calls[0] += 1
            return change(step(params, inputs), inputs, calls[0])
        return call
    engine._serve_step = lookup


def test_token_altered_where_produced_fails():
    def alter(out, inputs, n):
        if n % 4:
            return out
        return dict(out, next=(out["next"] + 1) % 512)
    res = serve("danube.long", SEEDS[2],
                hook=lambda e: _wrap_decode(e, alter))
    assert not res["correct"]


def test_decode_returning_its_state_unchanged_fails():
    def keep_state(out, inputs, n):
        return dict(out, state=inputs["state"])
    res = serve("danube.long", SEEDS[2],
                hook=lambda e: _wrap_decode(e, keep_state))
    assert not res["correct"]


def test_without_a_chip_it_exits_2_and_prints_nothing(capsys):
    assert run.main(["--workload", "danube.long", "--seed", "1",
                     "--seconds", "1"]) == 2
    assert capsys.readouterr().out == ""


def test_benchmark_file_names_its_files():
    """Every cell names a configuration whose file, architecture module and
    reference exist, a traffic file with a ``reduced`` block, and limits;
    every metric has its reader."""
    b = run.load_json(run.ROOT, "BENCHMARK.json")
    for w in b["workloads"]:
        conf = next(c for c in b["configs"] if c["name"] == w["config"])
        cfg = run.load_json(run.ROOT, conf["file"])
        for sub, name, ext in (("models", cfg["model"], ".py"),
                               ("configs", cfg["reference"], ".py"),
                               ("limits", w["name"], ".json")):
            assert os.path.exists(os.path.join(run.BENCH_DIR, sub,
                                               name + ext)), (sub, name)
        mix = run.load_json(run.BENCH_DIR, "traffic", w["traffic"] + ".json")
        assert "reduced" in mix, w["traffic"]
        assert run.cell_metrics(b, w["name"], False)
        assert run.cell_metrics(b, w["name"], True)
    for m in b["end_to_end"] + b["per_layer"]:
        base = m["name"].split(".")[0]
        assert os.path.exists(os.path.join(run.BENCH_DIR, "metrics",
                                           base + ".py"))
