"""Mixture-of-Experts FFN with capacity-based sparse dispatch (GShard-style).

FLOPs scale with *active* experts (top-k + shared), not total experts: tokens
are routed to per-expert buffers of capacity C = ceil(tokens * k / E) *
capacity_factor via a cumsum position assignment, then each expert runs a
dense SwiGLU over its buffer.  With experts sharded over the 'model' mesh
axis this lowers to the canonical all-to-all dispatch pattern under GSPMD.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import init_dense


def init_moe(key, d_model, d_ff, n_experts, n_shared, dtype=jnp.float32):
    ks = jax.random.split(key, 5)
    p = {
        "router": init_dense(ks[0], (d_model, n_experts), dtype=dtype),
        "w_gate": init_dense(ks[1], (n_experts, d_model, d_ff), dtype=dtype),
        "w_up": init_dense(ks[2], (n_experts, d_model, d_ff), dtype=dtype),
        "w_down": init_dense(ks[3], (n_experts, d_ff, d_model), dtype=dtype),
    }
    if n_shared:
        kg, ku, kd = jax.random.split(ks[4], 3)
        p["shared"] = {
            "w_gate": init_dense(kg, (d_model, d_ff * n_shared), dtype=dtype),
            "w_up": init_dense(ku, (d_model, d_ff * n_shared), dtype=dtype),
            "w_down": init_dense(kd, (d_ff * n_shared, d_model), dtype=dtype),
        }
    return p


def moe_ffn(params, x, *, top_k: int, capacity_factor: float = 1.25,
            shard=lambda x, *axes: x, dispatch_groups: int = 1):
    """x: (B, T, d) -> (B, T, d) plus aux load-balancing loss.

    dispatch_groups=1 is the classic GShard dispatch: one global cumsum over
    all (token, slot) pairs — simple, but on a sharded token axis the prefix
    sum and the (N*k, E) routing tensors generate enormous collectives.

    dispatch_groups=G (perf path, §Perf cell A) reshapes the token axis into
    (G, N/G) with G aligned to the mesh so every group's cumsum, capacity
    bucket and scatter stay *device-local*; only the expert all-to-all
    remains.  Any within-capacity position assignment is valid, so this is
    semantics-preserving (same token->expert routing, different slots).
    """
    B, T, d = x.shape
    E = params["router"].shape[-1]
    n_tok = B * T
    G = dispatch_groups
    assert n_tok % G == 0, (n_tok, G)
    tpg = n_tok // G                                  # tokens per group
    tokens = x.reshape(G, tpg, d)
    tokens = shard(tokens, "moe_groups", None, None)

    logits = (tokens @ params["router"]).astype(jnp.float32)  # (G, tpg, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)       # (G, tpg, k)
    gate_vals = gate_vals / jnp.maximum(
        gate_vals.sum(-1, keepdims=True), 1e-9)

    # Aux load-balance loss (Switch): E * sum_e f_e * P_e
    onehot = jax.nn.one_hot(expert_idx, E, dtype=jnp.float32)  # (G,tpg,k,E)
    f = onehot.sum((0, 1, 2)) / (n_tok)
    aux = E * jnp.sum(f * probs.mean((0, 1)))

    capacity = int(max(1, np.ceil(tpg * top_k / E * capacity_factor)))

    # Per-group positions: cumsum along the *unsharded* (tpg*k) dim.
    flat_choice = onehot.reshape(G, tpg * top_k, E)
    pos_in_expert = jnp.cumsum(flat_choice, axis=1) - 1.0
    pos = (pos_in_expert * flat_choice).sum(-1)                # (G, tpg*k)
    keep = pos < capacity
    eidx = expert_idx.reshape(G, tpg * top_k)
    gval = (gate_vals.reshape(G, tpg * top_k) * keep).astype(x.dtype)

    # Scatter into per-group (E, C, d) buffers.  GSPMD's scatter partitioner
    # replicates fancy-indexed scatters across the mesh (observed: 240 GB
    # all-gathers per MoE layer on kimi-k2); when the group axis is aligned
    # to the mesh we instead pin the scatter/gather group-local with
    # shard_map (§Perf cell A iteration 2).
    tok_rep = jnp.repeat(tokens, top_k, axis=1)                # (G,tpg*k,d)
    pos_c = jnp.clip(pos.astype(jnp.int32), 0, capacity - 1)
    upd = jnp.where(keep[..., None], tok_rep, 0)

    def scatter_local(e, c, u):
        def one(ee, cc, uu):
            z = jnp.zeros((E, capacity, d), x.dtype)
            return z.at[ee, cc].add(uu)
        return jax.vmap(one)(e, c, u)

    def gather_local(ob, e, c):
        return jax.vmap(lambda o, ee, cc: o[ee, cc])(ob, e, c)

    mesh = getattr(shard, "mesh", None)
    rules = getattr(shard, "rules", None)
    g_axes = rules.mesh_axes("moe_groups") if rules is not None else None
    if G > 1 and mesh is not None and g_axes is not None:
        from jax.sharding import PartitionSpec as P
        gspec = P(g_axes)
        scatter_fn = jax.shard_map(
            scatter_local, mesh=mesh,
            in_specs=(P(g_axes), P(g_axes), P(g_axes)),
            out_specs=P(g_axes), check_vma=False)
        gather_fn = jax.shard_map(
            gather_local, mesh=mesh,
            in_specs=(P(g_axes), P(g_axes), P(g_axes)),
            out_specs=P(g_axes), check_vma=False)
    else:
        scatter_fn, gather_fn = scatter_local, gather_local

    buf = scatter_fn(eidx, pos_c, upd)                          # (G,E,C,d)
    # the expert all-to-all: reshard from dispatch layout (groups over the
    # whole mesh) to compute layout (groups over data, experts over model)
    buf = shard(buf, "moe_groups_ep", "expert", "expert_cap", None)

    # Expert computation: (G, E, C, d) x (E, d, f)
    h = jnp.einsum("gecd,edf->gecf", buf, params["w_gate"])
    u = jnp.einsum("gecd,edf->gecf", buf, params["w_up"])
    out_buf = jnp.einsum("gecf,efd->gecd", jax.nn.silu(h) * u,
                         params["w_down"])
    out_buf = shard(out_buf, "moe_groups_ep", "expert", "expert_cap", None)

    # Gather back and combine with gate values (group-local).
    gathered = gather_fn(out_buf, eidx, pos_c)                 # (G,tpg*k,d)
    combined = (gathered * gval[..., None]).reshape(
        G, tpg, top_k, d).sum(2)

    if "shared" in params:
        s = params["shared"]
        t2 = tokens.reshape(n_tok, d)
        combined = combined.reshape(n_tok, d) + \
            (jax.nn.silu(t2 @ s["w_gate"]) * (t2 @ s["w_up"])) @ s["w_down"]
    return combined.reshape(B, T, d), aux
