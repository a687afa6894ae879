"""Top-k token-choice MoE with capacity-based dispatch (Switch/Mixtral style).

Dispatch is the sort-free cumsum-rank formulation: every (token, k) pair gets
a rank within its chosen expert; pairs beyond the expert capacity are
dropped (standard capacity-factor semantics). Expert FFNs run as batched
(E, Cap, d)×(E, d, ff) matmuls, which shard cleanly: expert weights are
tensor-parallel on the ff axis by default (no all-to-all — robust at 512
devices), with expert-parallel sharding available as a config knob.

Scalability: routing/dispatch is *shard-local by construction* — tokens are
reshaped to (dp_shards, T_local, d) using the ambient mesh and the whole
dispatch/combine is vmapped over the shard dim, so every gather/scatter has
batched (local) indices and GSPMD never materializes the global token
array. Capacity is therefore per data shard, which matches how capacity
factors are used in practice (per-device buffers). Without this, a 32k
MoE prefill all-gathers 8.6 GB of tokens per layer.

Expert kernels are 3-D (E, K, N) and quantize per-expert via
``layers.quantize_tree`` — W4A16's biggest capacity win in the paper's terms,
since expert weights dominate MoE model bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import planning
from repro.models import layers


def init_moe(key, d_model: int, d_ff: int, num_experts: int, dtype):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    E = num_experts
    return {
        "router": layers.init_linear(k1, d_model, E, dtype),
        "w_gate": {"kernel": (jax.random.normal(k2, (E, d_model, d_ff), jnp.float32) * s_in).astype(dtype)},
        "w_up": {"kernel": (jax.random.normal(k3, (E, d_model, d_ff), jnp.float32) * s_in).astype(dtype)},
        "w_down": {"kernel": (jax.random.normal(k4, (E, d_ff, d_model), jnp.float32) * s_out).astype(dtype)},
    }


def _expert_matmul(w, x, cfg):
    """x: (E, Cap, K) · w: (E, K, N) — dense or per-expert W4A16."""
    kern = w["kernel"]
    if isinstance(kern, layers.QuantizedTensor):
        # one plan for the whole expert stack (all E GEMMs share shapes),
        # then vmap the planned execute over experts
        problem = planning.MatmulProblem(
            M=int(x.shape[1]), N=int(kern.packed.shape[-1]),
            K=int(x.shape[-1]), group_size=kern.group_size,
            act_dtype=str(jnp.dtype(x.dtype)),
            out_dtype=str(jnp.dtype(x.dtype)),
            has_zeros=kern.zeros is not None,
            backend=jax.default_backend(), batch=int(x.shape[0]),
            format=kern.format.name)
        plan = planning.resolve_plan(problem, cfg)
        return jax.vmap(lambda xe, qe: planning.execute(plan, xe, qe))(x, kern)
    return jnp.einsum("ecd,edf->ecf", x, kern.astype(x.dtype),
                      preferred_element_type=jnp.float32).astype(x.dtype)


def _dp_axes(T: int):
    """DP axes of the ambient mesh that divide T (empty outside set_mesh)."""
    mesh = jax.sharding.get_abstract_mesh()
    if not mesh.axis_names:
        return (), None
    axes = []
    n = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and T % (n * mesh.shape[a]) == 0:
            axes.append(a)
            n *= mesh.shape[a]
    return tuple(axes), mesh


def _dispatch_ffn(p, xt, *, num_experts, top_k, capacity_factor, cfg):
    """Route/dispatch/combine for one token shard. xt: (T, d)."""
    T, d = xt.shape
    E = num_experts

    logits = layers.linear(p["router"], xt.astype(jnp.float32), cfg)  # (T, E)
    gates = jax.nn.softmax(logits, axis=-1)
    weights, sel = jax.lax.top_k(gates, top_k)                    # (T, k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    # aux loss (Switch): E * sum_e f_e * p_e
    me = jnp.mean(gates, axis=0)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(sel, E, dtype=jnp.float32), axis=1), axis=0)
    aux = E * jnp.sum(me * ce)

    cap = int(max(top_k, round(T * top_k / E * capacity_factor)))
    cap = min(cap, T * top_k)

    flat_e = sel.reshape(-1)                                      # (T*k,)
    onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)           # (T*k, E)
    rank = (jnp.cumsum(onehot, axis=0) - onehot)[
        jnp.arange(T * top_k), flat_e]                            # pos within expert
    keep = rank < cap
    slot = jnp.where(keep, flat_e * cap + rank, E * cap)          # overflow bin

    token_id = jnp.repeat(jnp.arange(T), top_k)
    src = jnp.zeros((E * cap + 1,), jnp.int32).at[slot].set(
        token_id + 1, mode="drop")                                # 0 = empty
    src = src[: E * cap]
    gathered = jnp.where(
        (src > 0)[:, None],
        jnp.take(xt, jnp.maximum(src - 1, 0), axis=0),
        0.0,
    ).reshape(E, cap, d)

    h_gate = _expert_matmul(p["w_gate"], gathered, cfg)
    h_up = _expert_matmul(p["w_up"], gathered, cfg)
    h = jax.nn.silu(h_gate.astype(jnp.float32)).astype(xt.dtype) * h_up
    out_e = _expert_matmul(p["w_down"], h, cfg).reshape(E * cap, d)

    # combine: scatter expert outputs back to (token, k) then weighted sum
    pair_out = jnp.where(
        keep[:, None],
        jnp.take(out_e, jnp.minimum(slot, E * cap - 1), axis=0),
        0.0,
    ).reshape(T, top_k, d)
    yt = jnp.sum(pair_out * weights[..., None].astype(xt.dtype), axis=1)
    return yt, aux


def moe_ffn(p, x: jax.Array, *, num_experts: int, top_k: int,
            capacity_factor: float = 1.25, cfg=None):
    """x: (..., d) → (..., d) plus aux load-balancing loss."""
    lead = x.shape[:-1]
    d = x.shape[-1]
    xt = x.reshape(-1, d)                            # (T, d)
    T = xt.shape[0]

    dp, mesh = _dp_axes(T)
    manual = dp and (cfg is None or getattr(cfg, "moe_manual_dispatch", False))
    if manual:
        # dispatch is manual over the DP axes (each rank routes only its
        # local tokens — per-shard capacity, no global token gather); the
        # "model" axis stays auto so TP expert weights partition as usual.
        # Inference-only: XLA crashes on shard_map(partial-auto) under
        # AD+remat, so training uses the vmapped formulation below.
        from jax.sharding import PartitionSpec as P

        def local(pp, xl):
            y, a = _dispatch_ffn(
                pp, xl, num_experts=num_experts, top_k=top_k,
                capacity_factor=capacity_factor, cfg=cfg)
            return y, jax.lax.pmean(a, dp)

        yt, aux = jax.shard_map(
            local, mesh=mesh, axis_names=set(dp),
            in_specs=(P(), P(dp, None)),
            out_specs=(P(dp, None), P()),
            check_vma=False,
        )(p, xt)
    elif dp:
        # AD-safe DP-sharded dispatch: vmap over the shard dim so every
        # gather/scatter is batch-local; GSPMD keeps buffers shard-local
        shards = 1
        for a in dp:
            shards *= mesh.shape[a]
        xs = layers.shard_hint(xt.reshape(shards, T // shards, d), "bsd")
        yt, aux = jax.vmap(
            lambda xl: _dispatch_ffn(
                p, xl, num_experts=num_experts, top_k=top_k,
                capacity_factor=capacity_factor, cfg=cfg))(xs)
        yt = layers.shard_hint(yt, "bsd").reshape(T, d)
        aux = jnp.mean(aux)
    else:
        yt, aux = _dispatch_ffn(
            p, xt, num_experts=num_experts, top_k=top_k,
            capacity_factor=capacity_factor, cfg=cfg)
    return yt.reshape(*lead, d), aux
