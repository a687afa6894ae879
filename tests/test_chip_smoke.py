"""chip_smoke.py's checks, rehearsed on CPU at the reduced danube width.

The script needs a TPU; here it must refuse the CPU without printing a
result, and its comparison machinery runs on the reduced config: the
engine forced onto the Pallas kernels (interpret mode) against the
XLA/gather engine, a 2x2 mesh of virtual devices against one device, and
perturbed logits that must fail the check.
"""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import pytest

from repro import configs

ROOT = os.path.join(os.path.dirname(__file__), "..")
_spec = importlib.util.spec_from_file_location(
    "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(cs)

WORK = cs.Workload(slots=2, prompt_min=20, prompt_max=70, gen=6)


def _cfg():
    # a window longer than the prompts, as at the published width
    return dataclasses.replace(configs.get_reduced(cs.ARCH),
                               sliding_window=4096)


def test_chip_smoke_refuses_cpu(capsys):
    assert cs.main([]) == 2
    out = capsys.readouterr()
    assert '"ok"' not in out.out and "needs a TPU" in out.err


def test_chip_smoke_pallas_vs_xla_comparison():
    cfg = _cfg()
    params = cs.init_quantized(cfg, 0)
    requests = cs.make_requests(cfg, WORK, 0)
    assert all(WORK.prompt_min <= len(r.prompt) <= WORK.prompt_max
               for r in requests)
    pallas = cs.build_engine(cfg, params, WORK, strategy="fused",
                             attn_path="fused")
    cs.assert_pallas(pallas)
    _, taps = cs.serve(pallas, requests, "pallas")
    ref = cs.build_engine(cfg, params, WORK, strategy="xla",
                          attn_path="gather")
    with pytest.raises(AssertionError, match="expected Pallas plans"):
        cs.assert_pallas(ref)
    _, taps_ref = cs.serve(ref, requests, "xla")
    # prompts run past the prefill chunk: more chunks than requests
    assert len(taps["prefill"]) > WORK.slots and taps["decode"]
    assert cs.compare_logits(taps_ref, taps, cfg.padded_vocab, "t") < 1e-4

    bad = {"prefill": taps["prefill"],
           "decode": [(i, l * 1.5) for i, l in taps["decode"]]}
    with pytest.raises(AssertionError, match="relative L2"):
        cs.compare_logits(taps_ref, bad, cfg.padded_vocab, "t")


FOUR = r"""
import dataclasses, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import importlib.util
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
cs = sys.modules["chip_smoke"] = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cs)
from repro import configs
cfg = dataclasses.replace(configs.get_reduced(cs.ARCH), sliding_window=4096)
cs.four_chips(cfg, cs.Workload(slots=4, prompt_min=20, prompt_max=70,
                               gen=6), 0)
print("DONE")
"""


def test_chip_smoke_four_device_phase_on_virtual_cpus():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"),
               JAX_PLATFORMS="cpu")
    res = subprocess.run(
        [sys.executable, "-c", FOUR, os.path.join(ROOT, "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    out = res.stdout
    assert "over devices [0, 1, 2, 3]" in out and "DONE" in out
    assert "params on devices [0, 1, 2, 3]" in out
    assert "4/4 requests token-identical" in out
