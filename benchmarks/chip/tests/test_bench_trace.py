"""Trace reduction on a small trace recorded here on the CPU: busy time is
the union of op intervals, program executions are counted by name, idle
time goes to the host span that was open, and the readers use them."""
import time
import types

import jax
import jax.numpy as jnp
import pytest

import benchpath  # noqa: F401
from chipbench import jobs, tracing

run = benchpath.load_run()


@pytest.fixture(scope="module")
def summary(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def serve_step(x):
        return jnp.tanh(x @ x).sum()

    @jax.jit
    def chunk_step(x):
        return jnp.sin(x @ x.T).sum()

    x = jnp.ones((384, 384))
    serve_step(x).block_until_ready()
    chunk_step(x).block_until_ready()
    jax.profiler.start_trace(out)
    for _ in range(3):
        with jax.profiler.TraceAnnotation("engine.step"):
            chunk_step(x).block_until_ready()
            serve_step(x).block_until_ready()
            time.sleep(0.05)        # host work inside the step
    jax.profiler.stop_trace()
    tracer = tracing.Tracer(out, 0.0, 1.0)
    return tracing.reduce(tracer.path())


def test_busy_and_idle(summary):
    assert summary.ops and summary.devices
    busy = summary.busy_s()
    assert 0.0 < busy < summary.window_s
    assert summary.window_s >= 0.15
    idle = dict(run.breakdown(summary)["idle_gaps"])
    assert idle["engine.step"] >= 0.12
    assert sum(idle.values()) == pytest.approx(summary.window_s - busy,
                                               abs=1e-6)


def test_program_executions(summary):
    assert len(summary.module_calls("serve_step")) == 3
    assert len(summary.module_calls("chunk_step")) == 3
    ctx = types.SimpleNamespace(trace=summary)
    assert run.read_metric(ctx, "decode_step_ms") > 0.0
    idle = run.read_metric(ctx, "device_idle_share.tput")
    assert idle == pytest.approx(100.0 * (1 - summary.busy_s()
                                          / summary.window_s))
    b = run.breakdown(summary)
    assert 0 < len(b["device_ops"]) <= 10


def test_union_counts_overlap_once():
    assert tracing._union([(0, 2), (1, 3), (5, 6)]) == 4
    assert tracing._union([(0, 4), (1, 2)]) == 4


def test_roofline_assigns_ops_by_pattern():
    op = tracing.Op
    summary = tracing.TraceSummary(
        ops=[op("%w4a16_fused.57 = bf16[32,2560] custom-call(%a, %b), "
                'custom_call_target="tpu_custom_call"', "m", 0.0, 0.002,
                "", "d"),
             op("%paged_attention.3 = (f32[32,8,1,1,4,80]) custom-call(%q), "
                'custom_call_target="tpu_custom_call"', "m", 0.002, 0.0005,
                "", "d"),
             op("%fusion.2 = bf16[32,2560] fusion(%x)", "m", 0.0025,
                0.001, "", "d")],
        modules=[], spans=[("engine.step", 0.0, 0.004)], window_s=0.004,
        devices=["d"])
    job = jobs.load(benchpath.BENCH_DIR, "gemm_w4a16")
    assert jobs.device_time(job, summary) == pytest.approx(0.002)
    attn = jobs.load(benchpath.BENCH_DIR, "paged_attention")
    assert jobs.device_time(attn, summary) == pytest.approx(0.0005)
    cfgj = {"model": "dense_decoder", "num_hidden_layers": 1,
            "hidden_size": 128, "intermediate_size": 256,
            "num_attention_heads": 4, "num_key_value_heads": 2,
            "head_dim": 32, "mlp": "gated"}
    step = types.SimpleNamespace(decode_pos=[3, 4])
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    ctx = types.SimpleNamespace(trace=summary, bench_dir=benchpath.BENCH_DIR,
                                cfgj=cfgj, traced_steps=[step], peaks=peaks)
    share = jobs.roofline_pct(ctx, "gemm_w4a16")
    least = job.least_time(cfgj, [step], peaks)
    assert share == pytest.approx(100.0 * least / 0.002)
    empty = tracing.TraceSummary([], [], [], 1.0, ["d"])
    ctx.trace = empty
    assert jobs.roofline_pct(ctx, "gemm_w4a16") is None


@pytest.mark.parametrize("name, job", [
    ("%paged_attention.3", "paged_attention"),
    ("%w4a16_fused.57", "gemm_w4a16"),
    ("%w4a16_grouped.1", None),
    ("%closed_call.13", None),
])
def test_jobs_match_kernels_by_name(name, job):
    """A custom call goes to the job that names its kernel, and one of any
    other name to no job."""
    op = tracing.Op(f"{name} = bf16[4,2560] custom-call(%a), "
                    'custom_call_target="tpu_custom_call"', "m", 0.0, 0.001,
                    "", "d")
    for other in ("gemm_w4a16", "paged_attention"):
        assert jobs.assigned(jobs.load(benchpath.BENCH_DIR, other), op) \
            == (other == job)
