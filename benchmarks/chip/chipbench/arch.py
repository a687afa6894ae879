"""Architecture modules: all that the harness knows of a model's layers.

A configuration file names its module under the key ``model``; the module
is ``models/<model>.py``, loaded by path once per process. It defines:

- ``make_raw(cfgj, seed)``: the benchmark's own raw weights;
- ``program_params(raw, cfgj, quant_format)`` and
  ``program_config(cfgj, quant_format)``: the hand-over to the program;
- ``gemm_call(cfgj, rows)``, ``attn_decode(cfgj, positions)`` and
  ``model_flops(cfgj, rows, positions)``: work counted from shapes, each
  ``(flops, bytes)`` but the last (:mod:`chipbench.work`);
- ``reduced(cfgj)``: the configuration at the sizes the CPU tests serve.

It is the only part of the benchmark that imports the program's
configurations and pytree.
"""
from __future__ import annotations

import importlib.util
import os
import sys
from typing import Optional

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_file(path: str, key: str):
    """The Python file at ``path`` as a module named ``key``."""
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(name: str, path: Optional[str] = None):
    """The architecture module ``name``: ``models/<name>.py``, or the file
    at ``path`` (a test's own), which then stands for ``name`` in this
    process."""
    key = f"bench_model_{name}"
    if path is None and key in sys.modules:
        return sys.modules[key]
    mod = load_file(path or os.path.join(BENCH_DIR, "models", name + ".py"),
                    key)
    sys.modules[key] = mod
    return mod


def of(cfgj: dict):
    """The architecture module the configuration names."""
    if "model" not in cfgj:
        raise ValueError(
            f"configuration {cfgj.get('name', '?')!r} has no \"model\" key: "
            "it names the architecture module models/<model>.py")
    return load(cfgj["model"])
