"""Serving-engine tests: slot scheduler + continuous batching, prefix-aware
KV-cache sizing (the PR-4 regression), shard-local planning, and the
8-fake-device parity suite (sharded engine decode token-identical to
single-device, plans keyed on per-rank shapes)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.configs.shapes import serve_cache_len
from repro.kernels import planning
from repro.models import attention
from repro.models import transformer as T
from repro.runtime import steps as rsteps
from repro.runtime.engine import (
    Request, ServingEngine, insert_slot, reset_slot,
)

ROOT = os.path.join(os.path.dirname(__file__), "..")
KEY = jax.random.PRNGKey(0)


class FakeMesh:
    """Spec-level mesh stand-in (shape/axis_names only)."""

    def __init__(self, sizes):
        self.shape = dict(sizes)
        self.axis_names = tuple(sizes)


def _params(cfg, quantized=True):
    p = T.init_params(KEY, cfg)
    return T.quantize_params(p, cfg, min_size=0) if quantized else p


def _requests(cfg, n, P, G, *, arrival_every=0):
    toks = jax.random.randint(KEY, (n, P), 0, cfg.vocab_size)
    reqs = []
    for i in range(n):
        kw = {}
        if cfg.vision_prefix:
            kw["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(KEY, i),
                (cfg.vision_prefix, cfg.d_model), cfg.dtype)
        if cfg.family == "encdec":
            kw["audio_embeds"] = jax.random.normal(
                jax.random.fold_in(KEY, i),
                (cfg.encoder_seq, cfg.d_model), cfg.dtype)
        reqs.append(Request(rid=i, prompt=toks[i], max_new_tokens=G,
                            arrival_step=i * arrival_every, **kw))
    return reqs


# ---------------------------------------------------------------------------
# prefix-aware cache sizing (satellite bugfix)
# ---------------------------------------------------------------------------

def test_serve_cache_len_prefix_aware():
    vlm = configs.get_reduced("internvl2-1b")           # vision_prefix=8
    assert serve_cache_len(vlm, 8, 4) == 8 + 8 + 4
    # encoder-decoder: audio frames live in enc_kv, NOT the decoder ring
    encdec = configs.get_reduced("whisper-small")
    assert serve_cache_len(encdec, 8, 3) == 8 + 3
    # sliding-window archs stay bounded by the window
    swa = configs.get_reduced("h2o-danube-1.8b")        # window=16
    assert serve_cache_len(swa, 30, 10) == 16


def test_engine_vision_prefix_ring_regression():
    """Prefill writes P + vision_prefix entries and decode advances from
    pos0 = P + prefix: with the old P+G sizing the pos-tagged ring silently
    overwrote the earliest context. The fixed ring retains position 0
    through the last decode step. (Explicitly the legacy ring engine —
    the paged parity suite lives in tests/test_kvcache.py.)"""
    cfg = dataclasses.replace(configs.get_reduced("internvl2-1b"),
                              w4a16_strategy="xla")
    P, G = 8, 6
    prefix = cfg.vision_prefix
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=1, max_prompt_len=P,
                        max_new_tokens=G, paged=False)
    assert eng.cache_len == P + prefix + G

    req = _requests(cfg, 1, P, G)[0]
    inputs = eng._prefill_inputs(req)
    logits, rstate = eng._prefill_fn(inputs)(eng.params, inputs)
    state = insert_slot(
        T.init_decode_state(cfg, 1, eng.cache_len), rstate, 0)
    valid = np.asarray(state["cache"]["kv"].pos[0, 0])
    assert sorted(valid[valid >= 0]) == list(range(P + prefix))

    serve = eng._serve_step()
    tok = jnp.argmax(logits[0])[None].astype(jnp.int32)
    for i in range(G - 1):
        pos = jnp.full((1,), P + prefix + i, jnp.int32)
        res = serve(eng.params, {"state": state, "tokens": tok, "pos": pos})
        tok, state = res["next"], res["state"]
    valid = np.asarray(state["cache"]["kv"].pos[0, 0])
    # every position 0 .. pos0+G-2 still present: nothing was overwritten
    assert sorted(valid[valid >= 0]) == list(range(P + prefix + G - 1))


def test_cache_reset_slots():
    cache = attention.init_cache(2, 4, 1, 8, jnp.float32)
    cache = attention.cache_insert(
        cache, jnp.ones((2, 1, 8)), jnp.ones((2, 1, 8)),
        jnp.zeros((2,), jnp.int32))
    out = attention.cache_reset_slots(cache, 1)
    assert int(out.pos[0, 0]) == 0                 # slot 0 untouched
    assert np.all(np.asarray(out.pos[1]) == -1)    # slot 1 wiped
    # layer-stacked form: batch is still the second-to-last pos dim
    stacked = attention.KVCache(
        k=jnp.zeros((3, 2, 4, 1, 8)), v=jnp.zeros((3, 2, 4, 1, 8)),
        pos=jnp.zeros((3, 2, 4), jnp.int32))
    out = attention.cache_reset_slots(stacked, 0)
    assert np.all(np.asarray(out.pos[:, 0]) == -1)
    assert np.all(np.asarray(out.pos[:, 1]) == 0)


# ---------------------------------------------------------------------------
# shard-local planning
# ---------------------------------------------------------------------------

def test_shard_problem_local_shapes():
    p = planning.MatmulProblem(M=4, N=256, K=512, group_size=128)
    mesh = FakeMesh({"data": 2, "model": 4})
    row = planning.shard_problem(p, mesh, "row")
    assert (row.M, row.N, row.K) == (2, 256, 128)      # K/tp, M/dp
    col = planning.shard_problem(p, mesh, "col")
    assert (col.M, col.N, col.K) == (2, 64, 512)       # N/tp, M/dp
    rep = planning.shard_problem(p, mesh, "rep")
    assert (rep.M, rep.N, rep.K) == (2, 256, 512)      # M/dp only
    # non-divisible dims stay global (mirror runtime/sharding.py rules)
    odd = planning.MatmulProblem(M=3, N=100, K=130, group_size=0)
    local = planning.shard_problem(odd, mesh, "row")
    assert (local.M, local.N, local.K) == (3, 100, 130)
    assert planning.shard_problem(p, None, "row") == p
    # batch divides GREEDILY per DP axis, exactly like batch_spec: M=4 on a
    # (pod=2, data=4) mesh shards over pod alone -> each rank runs M=2
    pod_mesh = FakeMesh({"pod": 2, "data": 4, "model": 1})
    local = planning.shard_problem(p, pod_mesh, "rep")
    assert local.M == 2


def test_plan_for_params_drops_ambiguous_square_keys(monkeypatch):
    """wq (col) and wo (row) of a square attention projection share the
    global layer_key: when their shard-local plans disagree the key must be
    dropped (global-planner fallback) — never hand one layer the other's
    wrong-shape plan. Local plans differ only in the Split-K degree of a
    Pallas kernel, so the kernels are taken to run per shard on a chip
    with cores to fill (a GSPMD step on TPU chips plans XLA GEMMs, and a
    v5e has one TensorCore: neither ever disagrees)."""
    from repro.core.quant import quantize

    monkeypatch.setattr(planning, "pallas_lowers", lambda backend, spmd: True)
    monkeypatch.setattr(planning, "num_cores", lambda: 8)
    w = jax.random.normal(KEY, (1024, 1024), jnp.float32)
    qt = quantize(w, group_size=64)
    params = {"wq": {"kernel": qt}, "wo": {"kernel": qt}}
    mesh = FakeMesh({"data": 1, "model": 4})
    planning.PLAN_CACHE.clear()
    plans = planning.plan_for_params(params, M=1, mesh=mesh, backend="tpu")
    col = planning.plan_matmul(
        planning.shard_problem(
            planning.MatmulProblem(M=1, N=1024, K=1024, group_size=64,
                                   backend="tpu"), mesh, "col"),
        use_cache=False)
    row = planning.plan_matmul(
        planning.shard_problem(
            planning.MatmulProblem(M=1, N=1024, K=1024, group_size=64,
                                   backend="tpu"), mesh, "row"),
        use_cache=False)
    assert col != row, "test premise: local plans must actually disagree"
    assert "1024x1024" not in plans
    # non-ambiguous keys are unaffected
    rect = {"wq": {"kernel": quantize(
        jax.random.normal(KEY, (1024, 512), jnp.float32), group_size=64)}}
    plans = planning.plan_for_params(rect, M=1, mesh=mesh, backend="tpu")
    assert "1024x512" in plans
    planning.PLAN_CACHE.clear()


def test_plan_for_params_mesh_goes_shard_local():
    cfg = configs.get_reduced("h2o-danube-1.8b")
    params = _params(cfg)
    mesh = FakeMesh({"data": 2, "model": 4})
    planning.PLAN_CACHE.clear()
    plans = planning.plan_for_params(params, M=2, mesh=mesh)
    # returned dict keyed by GLOBAL layer shapes (what trace-time sees) ...
    assert "256x128" in plans and "128x256" in plans
    # ... while the plan-cache keys carry the per-rank LOCAL shapes
    keys = list(planning.PLAN_CACHE._plans)
    assert any(p.K == 64 and p.N == 128 and p.M == 1 for p in keys), \
        "row-parallel w_down (256x128 global) should cache as K/tp=64"
    assert any(p.K == 128 and p.N == 64 and p.M == 1 for p in keys), \
        "column-parallel w_up (128x256 global) should cache as N/tp=64"
    assert not any(p.K == 256 or p.N == 256 for p in keys), \
        "no global-shape problem should be costed under a TP mesh"
    planning.PLAN_CACHE.clear()


# ---------------------------------------------------------------------------
# scheduler / continuous batching
# ---------------------------------------------------------------------------

def test_engine_matches_manual_decode_loop():
    """Engine output (pooled slots, batched decode) is token-identical to a
    hand-rolled per-request prefill + decode loop — the pre-engine serve
    semantics."""
    # full expert capacity: MoE dropping is computed over the routing
    # batch, so the engine's padded chunk T would drop different tokens
    # than the T=P manual prefill (see prefill_chunk_step's MoE note)
    cfg = dataclasses.replace(configs.get_reduced("olmoe-1b-7b"),
                              w4a16_strategy="xla",
                              moe_capacity_factor=64.0)
    P, G, n = 8, 4, 2
    params = _params(cfg)
    reqs = _requests(cfg, n, P, G)
    eng = ServingEngine(cfg, params, max_batch=n, max_prompt_len=P,
                        max_new_tokens=G)
    report = eng.run(reqs)

    cache_len = serve_cache_len(cfg, P, G)
    prefill = jax.jit(rsteps.make_prefill_step(cfg, cache_len))
    serve = jax.jit(rsteps.make_serve_step(cfg))
    for req in reqs:
        inputs = {"tokens": jnp.asarray(req.prompt)[None]}
        logits, state = prefill(params, inputs)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        want = [int(tok[0])]
        for i in range(G - 1):
            pos = jnp.full((1,), P + i, jnp.int32)
            res = serve(params, {"state": state, "tokens": tok, "pos": pos})
            tok, state = res["next"], res["state"]
            want.append(int(tok[0]))
        assert report.results[req.rid] == want


def test_engine_continuous_batching_reuses_slots():
    cfg = dataclasses.replace(configs.get_reduced("olmoe-1b-7b"),
                              w4a16_strategy="xla")
    P, G, n = 8, 3, 5
    params = _params(cfg)
    eng = ServingEngine(cfg, params, max_batch=2, max_prompt_len=P,
                        max_new_tokens=G)
    report = eng.run(_requests(cfg, n, P, G, arrival_every=1))
    assert sorted(report.results) == list(range(n))
    assert all(len(toks) == G for toks in report.results.values())
    assert len(report.latencies) == n
    # never more than the slot pool in flight; late arrivals admitted into
    # freed slots (continuous batching, not a static batch)
    assert max(r["active"] for r in report.step_records) <= 2
    assert any(r["admitted"] > 0 and r["step"] > 0
               for r in report.step_records)
    assert report.decode_tokens == sum(
        r["active"] for r in report.step_records)


def test_engine_rejects_oversized_requests():
    cfg = dataclasses.replace(configs.get_reduced("olmoe-1b-7b"),
                              w4a16_strategy="xla")
    eng = ServingEngine(cfg, _params(cfg), max_batch=1, max_prompt_len=4,
                        max_new_tokens=2)
    toolong = Request(rid=0, prompt=jnp.zeros((8,), jnp.int32),
                      max_new_tokens=2)
    with pytest.raises(ValueError, match="prompt length"):
        eng.run([toolong])
    greedy = Request(rid=0, prompt=jnp.zeros((4,), jnp.int32),
                     max_new_tokens=9)
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.run([greedy])


# ---------------------------------------------------------------------------
# multi-device parity (subprocess with 8 fake CPU devices)
# ---------------------------------------------------------------------------

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax
import jax.numpy as jnp

from repro import configs
from repro.kernels import planning
from repro.launch.mesh import make_local_mesh
from repro.models import transformer as T
from repro.runtime.engine import Request, ServingEngine

out = {}
P, G, R, SLOTS = 8, 5, 3, 2


def build_requests(cfg, key):
    toks = jax.random.randint(key, (R, P), 0, cfg.vocab_size)
    reqs = []
    for i in range(R):
        kw = {}
        if cfg.vision_prefix:
            kw["prefix_embeds"] = jax.random.normal(
                jax.random.fold_in(key, i),
                (cfg.vision_prefix, cfg.d_model), cfg.dtype)
        reqs.append(Request(rid=i, prompt=toks[i], max_new_tokens=G,
                            arrival_step=i, **kw))
    return reqs


def run_engine(cfg, params, mesh, reqs):
    eng = ServingEngine(cfg, params, mesh=mesh, max_batch=SLOTS,
                        max_prompt_len=P, max_new_tokens=G)
    rep = eng.run(reqs)
    return {str(k): v for k, v in sorted(rep.results.items())}, eng


for arch, meshes in [("h2o-danube-1.8b", [(2, 2), (1, 4)]),
                     ("internvl2-1b", [(2, 2)])]:
    cfg = configs.get_reduced(arch)          # w4a16_strategy="auto"
    key = jax.random.PRNGKey(0)
    params = T.quantize_params(T.init_params(key, cfg), cfg, min_size=0)
    reqs = build_requests(cfg, key)
    planning.PLAN_CACHE.clear()
    single, _ = run_engine(cfg, params, None, reqs)
    for dp, tp in meshes:
        planning.PLAN_CACHE.clear()
        mesh = make_local_mesh(data=dp, model=tp)
        sharded, eng = run_engine(cfg, params, mesh, reqs)
        tag = f"{arch}/{dp}x{tp}"
        out[tag + "/match"] = sharded == single
        # plan-cache keys must carry the per-rank local shapes:
        # w_down is (K=256, N=128) globally -> K/tp; w_up (128, 256) -> N/tp
        keys = list(planning.PLAN_CACHE._plans)
        out[tag + "/cache_local_row"] = any(
            p.K == 256 // tp and p.N == 128 for p in keys)
        out[tag + "/cache_local_col"] = any(
            p.K == 128 and p.N == 256 // tp for p in keys)
        out[tag + "/cache_no_global_K"] = not any(p.K == 256 for p in keys)
        out[tag + "/plans_keyed_global"] = (
            "256x128" in eng.plans and "128x256" in eng.plans)
print("RESULT " + json.dumps(out))
"""


@pytest.mark.slow
def test_sharded_engine_parity_and_local_plans():
    """TP=2/4 x DP engine decode is token-identical to single-device on two
    reduced archs (one vision-prefix), with plans keyed on shard-local
    shapes — the PR-4 acceptance demo."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    env["JAX_PLATFORMS"] = "cpu"
    res = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=560)
    assert res.returncode == 0, res.stderr[-3000:]
    line = [l for l in res.stdout.splitlines() if l.startswith("RESULT ")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out and all(out.values()), {k: v for k, v in out.items() if not v}
