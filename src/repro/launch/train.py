"""Training driver.

    PYTHONPATH=src python -m repro.launch.train --arch hymba-1.5b --reduced \
        --steps 20 --batch 8 --seq 64

Full configs target the production mesh (see dryrun.py); ``--reduced`` runs
the same code path end-to-end on local devices.
"""
from __future__ import annotations

import argparse
import os
import tempfile
import time

import jax
import jax.numpy as jnp

from repro import configs
from repro.data import SyntheticTokenStream
from repro.kernels import planning
from repro.launch import compile_cache
from repro.launch.presets import settings_for
from repro.models import transformer as T
from repro.optim import AdamWConfig, adamw_init
from repro.runtime import steps as rsteps
from repro.runtime.resilient import RunnerConfig, run_training


def extra_inputs(cfg, batch_size, rng):
    ex = {}
    if cfg.vision_prefix:
        ex["vision_embeds"] = jax.random.normal(
            rng, (batch_size, cfg.vision_prefix, cfg.d_model), cfg.dtype)
    if cfg.family == "encdec":
        ex["audio_embeds"] = jax.random.normal(
            rng, (batch_size, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    return ex


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCHS))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--ckpt-dir",
                    default=os.path.join(tempfile.gettempdir(), "repro_ckpt"))
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--plan-cache", default=None,
                    help="plan-cache JSON: pre-plan this model's quantized "
                         "serving GEMMs after training and persist them, so "
                         "the serve launcher starts with warm plans")
    ap.add_argument("--format", default=None,
                    help="quantization format for the post-training "
                         "serving-GEMM planning pass (any registered "
                         "QuantFormat name; default: config quant_format)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    if args.plan_cache and os.path.exists(args.plan_cache):
        if planning.load_plan_cache(args.plan_cache, tolerant=True) < 0:
            print(f"[train] plan cache {args.plan_cache} unreadable; "
                  f"replanning from scratch")

    cfg = (configs.get_reduced if args.reduced else configs.get_config)(
        args.arch)
    settings = rsteps.TrainSettings(microbatches=args.microbatches)
    opt_cfg = AdamWConfig(lr=1e-3)

    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    opt_state = adamw_init(params, opt_cfg)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"[train] {cfg.name} ({cfg.family}) params={n_params/1e6:.2f}M "
          f"devices={jax.device_count()}")

    step_fn = jax.jit(rsteps.make_train_step(cfg, opt_cfg, settings))
    stream = SyntheticTokenStream(
        vocab_size=cfg.vocab_size, seq_len=args.seq, batch_size=args.batch)
    ex = extra_inputs(cfg, args.batch, key)

    def batches(step):
        b = stream.batch_at(step)
        return {"batch": {**b, **ex}, "step": jnp.asarray(step, jnp.int32)}

    losses = []

    def on_metrics(step, m):
        losses.append(m["loss"])
        if step % 5 == 0:
            print(f"  step {step:4d} loss {m['loss']:.4f} "
                  f"gnorm {m['grad_norm']:.3f}")

    t0 = time.time()
    params, opt_state, history = run_training(
        cfg=RunnerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every),
        train_step=step_fn, params=params, opt_state=opt_state,
        batches=batches, num_steps=args.steps, on_metrics=on_metrics)
    dt = time.time() - t0
    print(f"[train] done {args.steps} steps in {dt:.1f}s; "
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}; "
          f"events: {[h[0] for h in history]}")
    if args.plan_cache:
        # quantize a throwaway copy of the trained tree to enumerate the
        # serving GEMMs, plan them at decode batch M, and persist — the
        # train→quantize→serve pipeline starts serving with warm plans
        qparams = T.quantize_params(params, cfg, format=args.format,
                                    min_size=0)
        plans = planning.plan_for_params(qparams, M=args.batch)
        n = planning.save_plan_cache(args.plan_cache)
        print(f"[train] plan cache: {len(plans)} layer GEMMs planned, "
              f"{n} plans -> {args.plan_cache}")
    return losses


if __name__ == "__main__":
    main()
