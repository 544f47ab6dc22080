"""Mixture-of-Experts FFN with capacity-based dispatch and expert parallelism.

Experts are sharded over the "model" mesh axis (EP); the per-expert token
buffer ("expert_capacity" logical axis) is sharded over "data" so the
dispatched activation tensor (E, C, d_model) stays bounded per device.  XLA
SPMD inserts the all-to-all-equivalent collectives at the gather/scatter
boundaries — the JAX-native mapping of the Megatron/DeepSpeed EP pattern.

Routing is top-k softmax gating with a capacity factor (Switch-style token
dropping); shared experts (DeepSeek-V2 / Kimi-K2) run densely for all
tokens.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed.context import constrain
from repro.models.common import ModelConfig, MoEConfig, ParamSpec
from repro.models import layers


def moe_specs(cfg: ModelConfig, moe: Optional[MoEConfig] = None) -> Dict[str, Any]:
    m = moe or cfg.moe
    d, dt = cfg.d_model, cfg.param_dtype
    specs: Dict[str, Any] = {
        "router": ParamSpec((d, m.num_experts), ("embed", None), jnp.float32, "scaled"),
        "wi_gate": ParamSpec((m.num_experts, d, m.d_ff), ("expert", "embed", None), dt, "scaled"),
        "wi_up": ParamSpec((m.num_experts, d, m.d_ff), ("expert", "embed", None), dt, "scaled"),
        "wo": ParamSpec((m.num_experts, m.d_ff, d), ("expert", None, "embed"), dt, "scaled"),
    }
    if m.num_shared_experts:
        shared_ff = m.shared_d_ff or m.num_shared_experts * m.d_ff
        specs["shared"] = layers.mlp_specs(d, shared_ff, dt)
    return specs


def _capacity(num_tokens: int, m: MoEConfig) -> int:
    cap = int(num_tokens * m.top_k * m.capacity_factor / m.num_experts)
    return max(8, (cap + 7) // 8 * 8)  # 8-aligned, non-degenerate


def route(
    router_w: jax.Array, x: jax.Array, m: MoEConfig
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (expert_idx (T,k), gates (T,k), aux_loss scalar)."""
    logits = jnp.einsum("td,de->te", x.astype(m.router_dtype), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    # load-balancing auxiliary loss (Switch/GShard form)
    density = jnp.mean(
        jax.nn.one_hot(idx[:, 0], m.num_experts, dtype=jnp.float32), axis=0
    )
    density_proxy = jnp.mean(probs, axis=0)
    aux = jnp.sum(density * density_proxy) * m.num_experts
    return idx, gates.astype(x.dtype), aux


def moe_forward(
    params: Dict[str, Any],
    x: jax.Array,              # (B, S, d_model)
    cfg: ModelConfig,
    moe: Optional[MoEConfig] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Returns (output (B,S,d), aux_loss).

    Under an active sharding context whose mesh has a >1 "model" axis, the
    expert-parallel shard_map path is used (see ``moe_forward_ep``); the
    gather-based global dispatch below is the portable single-device path.
    """
    from repro.distributed.context import current_context

    ctx = current_context()
    if ctx is not None:
        mesh, _ = ctx
        if "model" in mesh.axis_names and mesh.shape["model"] > 1:
            m_ = moe or cfg.moe
            if m_.num_experts % mesh.shape["model"] == 0:
                return moe_forward_ep(params, x, cfg, mesh, m_)
    m = moe or cfg.moe
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    idx, gates, aux = route(params["router"], xf, m)     # (T,k)
    cap = _capacity(t, m)

    # position of each (token, k) within its expert via a segmented cumsum
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.int32)   # (T,k,E)
    flat = onehot.reshape(t * m.top_k, m.num_experts)
    pos_in_expert = (jnp.cumsum(flat, axis=0) - flat).reshape(t, m.top_k, m.num_experts)
    pos = jnp.sum(pos_in_expert * onehot, axis=-1)                  # (T,k)
    keep = pos < cap

    # scatter token ids into the (E, C) dispatch table
    token_ids = jnp.broadcast_to(jnp.arange(t)[:, None], (t, m.top_k))
    safe_e = jnp.where(keep, idx, 0)
    safe_p = jnp.where(keep, pos, cap)  # dropped slots land in a spill column
    table = jnp.full((m.num_experts, cap + 1), t, jnp.int32)
    table = table.at[safe_e.reshape(-1), safe_p.reshape(-1)].set(
        jnp.where(keep, token_ids, t).reshape(-1), mode="drop"
    )
    slot_token = table[:, :cap]                                     # (E, C)

    # gather tokens (pad row t = zeros), run experts, scatter back
    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[slot_token]                                           # (E, C, d)
    xe = constrain(xe, ("expert", "expert_capacity", None))
    gate_lin = jnp.einsum("ecd,edf->ecf", xe, params["wi_gate"])
    up = jnp.einsum("ecd,edf->ecf", xe, params["wi_up"])
    hidden = jax.nn.silu(gate_lin.astype(jnp.float32)).astype(xe.dtype) * up
    hidden = constrain(hidden, ("expert", "expert_capacity", None))
    ye = jnp.einsum("ecf,efd->ecd", hidden, params["wo"])           # (E, C, d)
    ye = constrain(ye, ("expert", "expert_capacity", None))

    # combine: for each (token, k), read back its expert slot
    ypad = jnp.concatenate([ye.reshape(-1, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    slot_flat = jnp.where(keep, safe_e * cap + safe_p, ye.shape[0] * ye.shape[1])
    yk = ypad[slot_flat]                                            # (T,k,d)
    y = jnp.sum(yk * gates[..., None], axis=1)

    if m.num_shared_experts:
        y = y + layers.mlp(params["shared"], xf)
    return y.reshape(b, s, d), aux


# ---------------------------------------------------------------------------
# Expert-parallel shard_map path (§Perf hillclimb H2)
# ---------------------------------------------------------------------------
#
# The gather-based dispatch above indexes the GLOBAL token buffer with
# arbitrary indices, which the SPMD partitioner can only realize by
# all-gathering every token to every shard (measured: 12-21 TB/device/step
# on deepseek/kimi/jamba train_4k).  Here tokens stay sharded over "data",
# every "model" rank routes its local tokens to ITS OWN expert slice only,
# and partial expert outputs are combined with a single psum over "model" —
# the DeepSpeed/Megatron EP pattern expressed with shard_map.


def _local_dispatch_compute(xf, router_w, wi_gate, wi_up, wo, m: MoEConfig,
                            e_start: jax.Array, e_local: int, cap: int):
    """Route local tokens; compute only experts [e_start, e_start+e_local)."""
    t, d = xf.shape
    logits = jnp.einsum("td,de->te", xf.astype(m.router_dtype), router_w)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, m.top_k)
    gates = gates / jnp.maximum(jnp.sum(gates, axis=-1, keepdims=True), 1e-9)
    gates = gates.astype(xf.dtype)

    density = jnp.mean(jax.nn.one_hot(idx[:, 0], m.num_experts, dtype=jnp.float32), axis=0)
    aux = jnp.sum(density * jnp.mean(probs, axis=0)) * m.num_experts

    # per-(token,k) position within its expert (global expert ids)
    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.int32)
    flat = onehot.reshape(t * m.top_k, m.num_experts)
    pos = jnp.sum(
        (jnp.cumsum(flat, axis=0) - flat).reshape(t, m.top_k, m.num_experts) * onehot,
        axis=-1,
    )
    local_e = idx - e_start
    mine = (local_e >= 0) & (local_e < e_local) & (pos < cap)

    token_ids = jnp.broadcast_to(jnp.arange(t)[:, None], (t, m.top_k))
    safe_e = jnp.where(mine, local_e, 0)
    safe_p = jnp.where(mine, pos, cap)
    table = jnp.full((e_local, cap + 1), t, jnp.int32)
    table = table.at[safe_e.reshape(-1), safe_p.reshape(-1)].set(
        jnp.where(mine, token_ids, t).reshape(-1), mode="drop"
    )
    slot_token = table[:, :cap]

    xpad = jnp.concatenate([xf, jnp.zeros((1, d), xf.dtype)], axis=0)
    xe = xpad[slot_token]                                       # (E_loc, C, d)
    hidden = jax.nn.silu(
        jnp.einsum("ecd,edf->ecf", xe, wi_gate).astype(jnp.float32)
    ).astype(xe.dtype) * jnp.einsum("ecd,edf->ecf", xe, wi_up)
    ye = jnp.einsum("ecf,efd->ecd", hidden, wo)                 # (E_loc, C, d)

    ypad = jnp.concatenate([ye.reshape(-1, d), jnp.zeros((1, d), ye.dtype)], axis=0)
    slot_flat = jnp.where(mine, safe_e * cap + safe_p, e_local * cap)
    yk = ypad[slot_flat]                                        # (t, k, d)
    y_partial = jnp.sum(yk * gates[..., None], axis=1)          # local-expert share
    return y_partial, aux


def moe_forward_ep(
    params: Dict[str, Any],
    x: jax.Array,
    cfg: ModelConfig,
    mesh,
    m: MoEConfig,
) -> Tuple[jax.Array, jax.Array]:
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    n_model = mesh.shape["model"]
    data_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_data = 1
    for a in data_axes:
        n_data *= mesh.shape[a]
    batch_axes = data_axes if (data_axes and b % n_data == 0) else None
    n_shards = n_data if batch_axes else 1
    e_local = m.num_experts // n_model
    t_local = (b // n_shards) * s
    cap = _capacity(t_local, m)

    def body(xb, router_w, wi_gate, wi_up, wo):
        xf = xb.reshape(-1, d)
        rank = jax.lax.axis_index("model")
        y_partial, aux = _local_dispatch_compute(
            xf, router_w, wi_gate, wi_up, wo, m, rank * e_local, e_local, cap
        )
        y = jax.lax.psum(y_partial, "model")
        aux = jax.lax.pmean(aux, "model")
        return y.reshape(xb.shape), aux

    y, aux = jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(
            P(batch_axes, None, None),
            P(None, None),                 # router replicated
            P("model", None, None),
            P("model", None, None),
            P("model", None, None),
        ),
        out_specs=(P(batch_axes, None, None), P()),
        check_vma=False,
    )(x, params["router"], params["wi_gate"], params["wi_up"], params["wo"])

    if m.num_shared_experts:
        y = y + layers.mlp(params["shared"], x.reshape(-1, d)).reshape(b, s, d)
    return y, aux
