"""jit-able train / prefill / serve steps with explicit shardings.

``make_train_step`` supports microbatch gradient accumulation (scan) — with
per-layer remat this is what bounds activation memory for the 405B cell —
and bf16 gradient all-reduce (compression) with fp32 update math.

All step functions take ``(params, [opt_state,] inputs: dict)`` so one
sharding pytree covers the whole input bundle uniformly.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import AdamWConfig, adamw_update, cosine_schedule
from repro.runtime import sharding as shd


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    microbatches: int = 1
    fsdp: bool = False
    fsdp_serve: bool = False
    opt_dtype: Any = jnp.float32
    grad_dtype: Any = jnp.bfloat16      # gradient compression for the
                                        # cross-pod all-reduce
    zero2: bool = False                 # gather FSDP weights ONCE per step
                                        # (not per microbatch): 8-16× less
                                        # all-gather traffic, costs one
                                        # model-sharded weight copy in HBM.
                                        # Off for 405B-class (copy too big).


def _split_micro(batch, n):
    def f(x):
        B = x.shape[0]
        return x.reshape(n, B // n, *x.shape[1:])
    return jax.tree.map(f, batch)


def make_train_step(cfg: ModelConfig, opt_cfg: AdamWConfig,
                    settings: TrainSettings, *,
                    gathered_shardings=None, fsdp_shardings=None):
    """train_step(params, opt_state, inputs) → (params, opt_state, metrics).

    inputs = {"batch": {tokens, labels, [embeds]}, "step": scalar}

    With ``settings.zero2`` and the two sharding pytrees provided, weights
    are all-gathered from their FSDP shards ONCE per step (constrained to
    ``gathered_shardings``), reused across every microbatch, and gradients
    are reduce-scattered back to ``fsdp_shardings`` before the optimizer —
    ZeRO-2 semantics instead of ZeRO-3's per-microbatch regather.
    """

    def loss_of(params, mb):
        return T.loss_fn(params, cfg, mb)

    def train_step(params, opt_state, inputs):
        batch, step = inputs["batch"], inputs["step"]
        n = settings.microbatches
        opt_params = params
        if settings.zero2 and gathered_shardings is not None:
            params = jax.lax.with_sharding_constraint(
                params, gathered_shardings)
        if n > 1:
            micro = _split_micro(batch, n)

            def acc_fn(carry, mb):
                l, g = jax.value_and_grad(loss_of)(params, mb)
                g = jax.tree.map(lambda a: a.astype(settings.grad_dtype), g)
                if fsdp_shardings is not None:
                    # reduce-scatter each microbatch's gradients onto the
                    # ZeRO shards immediately: the accumulator stays sharded
                    # (vs. an all-reduce leaving grads replicated over data)
                    g = jax.lax.with_sharding_constraint(g, fsdp_shardings)
                carry_l, carry_g = carry
                return (carry_l + l,
                        jax.tree.map(jnp.add, carry_g, g)), None

            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, settings.grad_dtype), params)
            if fsdp_shardings is not None:
                g0 = jax.lax.with_sharding_constraint(g0, fsdp_shardings)
            (loss, grads), _ = jax.lax.scan(
                acc_fn, (jnp.zeros((), jnp.float32), g0), micro)
            loss = loss / n
            grads = jax.tree.map(lambda g: g / n, grads)
        else:
            loss, grads = jax.value_and_grad(loss_of)(params, batch)
            grads = jax.tree.map(
                lambda a: a.astype(settings.grad_dtype), grads)

        if settings.zero2 and fsdp_shardings is not None:
            # reduce-scatter gradients back onto the ZeRO shards
            grads = jax.lax.with_sharding_constraint(grads, fsdp_shardings)
        lr_scale = cosine_schedule(step)
        new_params, opt_state, om = adamw_update(
            grads, opt_state, opt_params, opt_cfg, lr_scale)
        metrics = {"loss": loss, **om}
        return new_params, opt_state, metrics

    return train_step


def make_prefill_step(cfg: ModelConfig, cache_len: int):
    """prefill_step(params, inputs={tokens, [prefix_embeds], [audio_embeds]})."""
    def prefill_step(params, inputs):
        return T.prefill(params, cfg, inputs["tokens"], cache_len=cache_len,
                         prefix_embeds=inputs.get("prefix_embeds"),
                         audio_embeds=inputs.get("audio_embeds"))
    return prefill_step


def make_serve_step(cfg: ModelConfig, *, cache_len: int = 0,
                    kv_format: str = "kv_fp16",
                    attn_path: str = "gather", kv_partitions=None,
                    live_pages=None):
    """serve_step(params, inputs={state, tokens, pos, [tables], [active]})
    — one decode step. When ``inputs`` carries per-slot block ``tables``
    the KV state is the paged pool, ``cache_len``/``kv_format`` select the
    slot-window length and KV storage format, and ``attn_path`` /
    ``kv_partitions`` the planned decode-attention path and Split-K
    degree (see runtime/kvcache.py). ``live_pages`` (static) clamps the
    gather path to the batch's live-page high-water mark — the engine
    compiles one variant per power-of-2 bucket. ``active`` (B,) bool
    masks recurrent-carry writes for rows that are mid chunked prefill
    (carry families on the chunked engine only)."""
    def serve_step(params, inputs):
        logits, state = T.decode_step(
            params, cfg, inputs["state"], inputs["tokens"], inputs["pos"],
            tables=inputs.get("tables"), active=inputs.get("active"),
            cache_len=cache_len, kv_format=kv_format, attn_path=attn_path,
            kv_partitions=kv_partitions, live_pages=live_pages)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return {"next": next_tok, "logits": logits, "state": state}
    return serve_step


def make_prefill_chunk_step(cfg: ModelConfig, cache_len: int, *,
                            kv_format: str = "kv_fp16",
                            attn_path: str = "gather", kv_partitions=None,
                            live_pages=None):
    """chunk_step(params, state, inputs={h, positions, slot, [table]}) —
    one chunked-prefill step for one slot (see T.prefill_chunk_step):
    attends the slot's pooled window on ``attn_path`` (gather, clamped to
    ``live_pages``, or the fused multi-query kernel with ``kv_partitions``
    page-axis splits), scatters the chunk's K/V into the slot's pooled
    pages (attention families — ``table`` absent for attention-free
    rwkv), threads the slot's recurrent carries / cross-KV through by the
    ``slot`` row index, and returns the updated state plus
    last-valid-position logits (used when the final chunk completes the
    prompt). ``state`` is its own argument so the block pool — the
    largest serving tensor — can be donated without dragging the small
    non-donatable chunk inputs along."""
    def chunk_step(params, state, inputs):
        logits, state = T.prefill_chunk_step(
            params, cfg, state, inputs["h"], inputs["positions"],
            inputs.get("table"), inputs["slot"],
            cache_len=cache_len, kv_format=kv_format, attn_path=attn_path,
            kv_partitions=kv_partitions, live_pages=live_pages)
        return {"logits": logits, "state": state}
    return chunk_step


def make_verify_step(cfg: ModelConfig, cache_len: int, *,
                     kv_format: str = "kv_fp16",
                     attn_path: str = "gather", kv_partitions=None,
                     live_pages=None):
    """verify(params, state, inputs={tokens, positions, [tables]}) — one
    batched speculative-verify step (see T.verify_step): scores the last
    emitted token plus up to C-1 draft tokens for every slot in one
    forward pass and returns the per-position greedy choice. ``next`` is
    the device-side argmax over *all* (slot, position) cells, so the host
    syncs one (B, C) int array per step regardless of batch or draft
    length. The (B, k+1) window attends its pooled context on
    ``attn_path`` exactly like a prefill chunk (``"fused"`` = one
    multi-query kernel pass, ``"gather"`` clamped to ``live_pages``).
    ``state`` is its own (donatable) argument, as in the chunked
    prefill step. Carry families additionally return ``carries`` — the
    per-position carry checkpoints the engine selects the accepted
    frontier from (see T.verify_step)."""
    def verify(params, state, inputs):
        logits, state, carries = T.verify_step(
            params, cfg, state, inputs["tokens"], inputs["positions"],
            inputs.get("tables"), cache_len=cache_len, kv_format=kv_format,
            attn_path=attn_path, kv_partitions=kv_partitions,
            live_pages=live_pages)
        with jax.named_scope("sample"):
            next_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = {"next": next_tok, "logits": logits, "state": state}
        if carries is not None:
            out["carries"] = carries
        return out
    return verify


# ---------------------------------------------------------------------------
# sharding builders for the input bundles
# ---------------------------------------------------------------------------

def train_input_shardings(inputs_abstract, mesh):
    rep = NamedSharding(mesh, P())
    return {
        "batch": shd.data_shardings(inputs_abstract["batch"], mesh),
        "step": rep,
    }


def prefill_input_shardings(inputs_abstract, mesh):
    return shd.data_shardings(inputs_abstract, mesh)


def serve_input_shardings(inputs_abstract, cfg, mesh):
    out = {
        "state": shd.decode_state_shardings(inputs_abstract["state"], cfg, mesh),
        "tokens": shd.data_shardings(inputs_abstract["tokens"], mesh),
        "pos": shd.data_shardings(inputs_abstract["pos"], mesh),
    }
    if "tables" in inputs_abstract:       # paged: (B, pages_per_slot)
        out["tables"] = shd.data_shardings(inputs_abstract["tables"], mesh)
    if "active" in inputs_abstract:       # carry families, chunked engine
        out["active"] = shd.data_shardings(inputs_abstract["active"], mesh)
    return out


# ---------------------------------------------------------------------------
# sharded jit wrappers (what dryrun.py lowers)
# ---------------------------------------------------------------------------

def jit_train_step(cfg, mesh, settings: TrainSettings, params_abstract,
                   inputs_abstract, opt_cfg: Optional[AdamWConfig] = None):
    opt_cfg = opt_cfg or AdamWConfig(state_dtype=settings.opt_dtype)
    pshard = shd.param_shardings(params_abstract, mesh, fsdp=settings.fsdp)
    gathered = None
    if settings.zero2 and settings.fsdp:
        gathered = shd.param_shardings(params_abstract, mesh, fsdp=False)
    step_fn = make_train_step(
        cfg, opt_cfg, settings,
        gathered_shardings=gathered,
        fsdp_shardings=pshard if settings.fsdp else None)
    rep = NamedSharding(mesh, P())
    oshard = {"m": pshard, "v": pshard, "count": rep}
    ishard = train_input_shardings(inputs_abstract, mesh)
    mshard = {"loss": rep, "grad_norm": rep}
    return jax.jit(
        step_fn,
        in_shardings=(pshard, oshard, ishard),
        out_shardings=(pshard, oshard, mshard),
        donate_argnums=(0, 1),
    )


def jit_prefill_step(cfg, mesh, cache_len: int, params_abstract,
                     inputs_abstract, *, fsdp_serve=False):
    fn = make_prefill_step(cfg, cache_len)
    pshard = shd.param_shardings(params_abstract, mesh, fsdp=fsdp_serve)
    ishard = prefill_input_shardings(inputs_abstract, mesh)
    # constrain the RETURNED decode state too — without this the prefilled
    # KV cache materializes replicated (catastrophic at 32k×405B)
    _, state_abs = jax.eval_shape(fn, params_abstract, inputs_abstract)
    sshard = shd.decode_state_shardings(state_abs, cfg, mesh)
    B = inputs_abstract["tokens"].shape[0]
    # same normalized entry as the input shardings (shd.batch_axis_entry) —
    # a raw bspec[0] here could disagree with data_shardings on older jax
    baxis = shd.batch_axis_entry(B, mesh)
    return jax.jit(
        fn,
        in_shardings=(pshard, ishard),
        out_shardings=(NamedSharding(mesh, P(baxis, None)), sshard),
    )


def jit_serve_step(cfg, mesh, params_abstract, inputs_abstract, *,
                   fsdp_serve=False, cache_len: int = 0,
                   kv_format: str = "kv_fp16", attn_path: str = "gather",
                   kv_partitions=None, live_pages=None):
    fn = make_serve_step(cfg, cache_len=cache_len, kv_format=kv_format,
                         attn_path=attn_path, kv_partitions=kv_partitions,
                         live_pages=live_pages)
    pshard = shd.param_shardings(params_abstract, mesh, fsdp=fsdp_serve)
    ishard = serve_input_shardings(inputs_abstract, cfg, mesh)
    B = inputs_abstract["tokens"].shape[0]
    baxis = shd.batch_axis_entry(B, mesh)
    return jax.jit(
        fn,
        in_shardings=(pshard, ishard),
        out_shardings={
            "next": NamedSharding(mesh, P(baxis)),
            "logits": NamedSharding(mesh, P(baxis, None)),
            "state": ishard["state"],
        },
        donate_argnums=(1,),
    )


def jit_prefill_chunk_step(cfg, mesh, cache_len, params_abstract,
                           inputs_abstract, *, kv_format: str = "kv_fp16",
                           attn_path: str = "gather", kv_partitions=None,
                           live_pages=None, fsdp_serve=False):
    """Sharded chunked-prefill step: state in/out on the decode-state
    shardings (the pool replicates pages over DP, shards heads over TP);
    the B=1 chunk inputs replicate."""
    fn = make_prefill_chunk_step(cfg, cache_len, kv_format=kv_format,
                                 attn_path=attn_path,
                                 kv_partitions=kv_partitions,
                                 live_pages=live_pages)
    pshard = shd.param_shardings(params_abstract, mesh, fsdp=fsdp_serve)
    sshard = shd.decode_state_shardings(inputs_abstract["state"], cfg, mesh)
    ishard = {k: shd.data_shardings(v, mesh)
              for k, v in inputs_abstract.items() if k != "state"}
    return jax.jit(
        fn,
        in_shardings=(pshard, sshard, ishard),
        out_shardings={
            "logits": NamedSharding(mesh, P(None, None)),
            "state": sshard,
        },
        # donate the state: the block pool is the largest serving tensor
        # and would otherwise be copied whole on every prefill chunk
        donate_argnums=(1,),
    )


def jit_verify_step(cfg, mesh, cache_len, params_abstract,
                    inputs_abstract, *, kv_format: str = "kv_fp16",
                    attn_path: str = "gather", kv_partitions=None,
                    live_pages=None, fsdp_serve=False):
    """Sharded speculative-verify step: state in/out on the decode-state
    shardings (donated, like the chunk step); tokens/positions/tables are
    batch-sharded over data, and the (B, C) next/logits outputs come back
    batch-sharded too."""
    fn = make_verify_step(cfg, cache_len, kv_format=kv_format,
                          attn_path=attn_path, kv_partitions=kv_partitions,
                          live_pages=live_pages)
    pshard = shd.param_shardings(params_abstract, mesh, fsdp=fsdp_serve)
    sshard = shd.decode_state_shardings(inputs_abstract["state"], cfg, mesh)
    ishard = {k: shd.data_shardings(v, mesh)
              for k, v in inputs_abstract.items() if k != "state"}
    B = inputs_abstract["tokens"].shape[0]
    baxis = shd.batch_axis_entry(B, mesh)
    oshard = {
        "next": NamedSharding(mesh, P(baxis, None)),
        "logits": NamedSharding(mesh, P(baxis, None, None)),
        "state": sshard,
    }
    if cfg.family in T.CARRY_FAMILIES:
        # carries are (L, B, C+1, ...) checkpoint stacks — batch on axis 1
        out_abs = jax.eval_shape(
            fn, params_abstract, inputs_abstract["state"], ishard_inputs(
                inputs_abstract))

        def cshard(leaf):
            spec = [None] * leaf.ndim
            spec[1] = baxis
            return NamedSharding(mesh, P(*spec))

        oshard["carries"] = jax.tree.map(cshard, out_abs["carries"])
    return jax.jit(
        fn,
        in_shardings=(pshard, sshard, ishard),
        out_shardings=oshard,
        donate_argnums=(1,),
    )


def ishard_inputs(inputs_abstract):
    """The non-state portion of a (params, state, inputs) step's bundle."""
    return {k: v for k, v in inputs_abstract.items() if k != "state"}
