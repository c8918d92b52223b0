"""Mixture-of-Experts layer: top-k routing, capacity dispatch, EP sharding.

GShard-style capacity dispatch implemented with scatter/gather (memory
O(tokens * top_k), never the [tokens, E, C] one-hot cube):

  1. router logits -> top-k (prob, expert id) per token,
  2. position-in-expert via a cumulative sum over the flattened
     (token, k) slots; slots past the expert capacity C are dropped,
  3. scatter tokens into the [E, C, d] dispatch buffer, run all experts
     as one stacked einsum, gather back weighted by router probs.

Sharding (applied by the planner): dispatch buffer [G, E, C, d] with the
group axis G on 'data' and experts E on 'model' — dispatch/combine then
induce exactly one model-axis collective each (the MoE all-to-all
analogue), matching the paper's "stream tokens, reuse (expert) kernels"
flow.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.distributed.ctx import constrain
from repro.models import layers as L

Array = jax.Array


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int                 # per-expert hidden
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25


def init(key, cfg: MoEConfig, dtype=jnp.float32) -> dict:
    ks = jax.random.split(key, 4)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": L.dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   * d ** -0.5).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 * d ** -0.5).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   * f ** -0.5).astype(dtype),
    }


def capacity(cfg: MoEConfig, tokens_per_group: int) -> int:
    c = int(tokens_per_group * cfg.top_k * cfg.capacity_factor
            / cfg.n_experts)
    return max(4, c)


def forward(params: dict, cfg: MoEConfig, x: Array
            ) -> tuple[Array, dict]:
    """x: [G, S, d] (G = routing groups, sharded on 'data').

    Returns (y [G, S, d], aux) with aux = load-balance loss terms.
    """
    g, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    c = capacity(cfg, s)

    logits = x.astype(jnp.float32) @ params["router"]           # [G,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                      # [G,S,K]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # position of each (token, k) slot within its expert, per group
    flat_i = top_i.reshape(g, s * k)                            # [G,SK]
    onehot = jax.nn.one_hot(flat_i, e, dtype=jnp.int32)         # [G,SK,E]
    pos = jnp.cumsum(onehot, axis=1) - 1                        # [G,SK,E]
    pos_in_e = jnp.take_along_axis(
        pos, flat_i[..., None], axis=-1)[..., 0]                # [G,SK]
    keep = pos_in_e < c
    # dropped slots are masked to zero and clamped onto slot 0 (inert:
    # zero contribution) so the buffer shape is exactly [G, E*C, d] —
    # an OOB dump row would make E*C+1 unshardable over the expert axis
    # and forces XLA's scatter fallback (all-reduce of the whole buffer)
    slot = jnp.where(keep, flat_i * c + pos_in_e, 0)

    # scatter tokens into the dispatch buffer [G, E*C, d]
    xk = jnp.repeat(x, k, axis=1) * keep[..., None].astype(x.dtype)
    buf = jnp.zeros((g, e * c, d), x.dtype)
    buf = buf.at[jnp.arange(g)[:, None], slot].add(xk)
    h = constrain(buf.reshape(g, e, c, d), "moe_dispatch")

    # stacked expert FFN (SwiGLU)
    gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", h,
                                  params["w_gate"].astype(x.dtype)))
    up = jnp.einsum("gecd,edf->gecf", h, params["w_up"].astype(x.dtype))
    out = jnp.einsum("gecf,efd->gecd", gate * up,
                     params["w_down"].astype(x.dtype))          # [G,E,C,d]

    # combine: gather each slot's expert output, weight by router prob
    # (dropped slots read slot 0 but their weight is masked to zero)
    out_flat = out.reshape(g, e * c, d)
    yk = out_flat[jnp.arange(g)[:, None], slot]                 # [G,SK,d]
    w = (top_p.reshape(g, s * k) * keep).astype(x.dtype)
    y = constrain((yk * w[..., None]).reshape(g, s, k, d).sum(axis=2),
                  "moe_combine")

    # Switch-style load-balance aux loss
    me = probs.mean(axis=(0, 1))                                # [E]
    ce = (onehot.sum(axis=(0, 1)) / (g * s * k)).astype(jnp.float32)
    aux = {
        "lb_loss": e * jnp.sum(me * ce),
        "dropped_frac": 1.0 - keep.mean(),
    }
    return y, aux


# ---------------------------------------------------------------------------
# Explicit expert-parallel path (shard_map)
# ---------------------------------------------------------------------------

def _local_expert_ffn(params, cfg: MoEConfig, x: Array, e_lo: int,
                      e_loc: int, w_gate, w_up, w_down) -> Array:
    """Per-device body: dispatch local tokens to the E_loc experts this
    model shard owns (capacity buffers are device-LOCAL, so the scatter
    never crosses shards — the fix for the SPMD scatter fallback), run
    the local expert einsums, combine, and leave the cross-shard sum to
    one psum over 'model' (TP-like: a single [G,S,d] all-reduce/layer).
    """
    g, s, d = x.shape
    k = cfg.top_k
    c = capacity(cfg, s)

    logits = x.astype(jnp.float32) @ params["router"]           # [G,S,E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    flat_i = top_i.reshape(g, s * k)
    local = (flat_i >= e_lo) & (flat_i < e_lo + e_loc)
    loc_i = jnp.where(local, flat_i - e_lo, 0)
    onehot = jax.nn.one_hot(loc_i, e_loc, dtype=jnp.int32) \
        * local[..., None].astype(jnp.int32)
    pos = jnp.cumsum(onehot, axis=1) - 1
    pos_in_e = jnp.take_along_axis(pos, loc_i[..., None], -1)[..., 0]
    keep = local & (pos_in_e < c)
    slot = jnp.where(keep, loc_i * c + pos_in_e, 0)

    xk = jnp.repeat(x, k, axis=1) * keep[..., None].astype(x.dtype)
    buf = jnp.zeros((g, e_loc * c, d), x.dtype)
    buf = buf.at[jnp.arange(g)[:, None], slot].add(xk)
    h = buf.reshape(g, e_loc, c, d)

    gate = jax.nn.silu(jnp.einsum("gecd,edf->gecf", h,
                                  w_gate.astype(x.dtype)))
    up = jnp.einsum("gecd,edf->gecf", h, w_up.astype(x.dtype))
    out = jnp.einsum("gecf,efd->gecd", gate * up,
                     w_down.astype(x.dtype)).reshape(g, e_loc * c, d)

    yk = out[jnp.arange(g)[:, None], slot]
    w = (top_p.reshape(g, s * k) * keep).astype(x.dtype)
    return (yk * w[..., None]).reshape(g, s, k, d).sum(axis=2)


def forward_ep(params: dict, cfg: MoEConfig, x: Array, *, mesh,
               data_axes: tuple[str, ...], model_axis: str = "model",
               fsdp_axes: tuple[str, ...] = ()) -> tuple[Array, dict]:
    """shard_map expert parallelism: experts sharded over ``model_axis``,
    tokens over ``data_axes``; each shard serves only its experts and one
    psum('model') per layer combines — total cross-shard traffic is one
    [G, S, d] all-reduce instead of buffer-wide scatter collectives."""
    from jax.sharding import PartitionSpec as P

    e = cfg.n_experts
    m_ways = 1
    for ax in ([model_axis] if isinstance(model_axis, str) else model_axis):
        m_ways *= dict(zip(mesh.axis_names, mesh.devices.shape)).get(ax, 1)
    e_loc = e // m_ways

    def device_fn(router, w_gate, w_up, w_down, x_loc):
        if fsdp_axes:
            for ax in fsdp_axes:
                w_gate = jax.lax.all_gather(w_gate, ax, axis=1,
                                            tiled=True)
                w_up = jax.lax.all_gather(w_up, ax, axis=1, tiled=True)
                w_down = jax.lax.all_gather(w_down, ax, axis=2,
                                            tiled=True)
        m_idx = jax.lax.axis_index(model_axis)
        p = {"router": router}
        y_part = _local_expert_ffn(p, cfg, x_loc, m_idx * e_loc, e_loc,
                                   w_gate, w_up, w_down)
        return jax.lax.psum(y_part, model_axis)

    wa = tuple(fsdp_axes) if fsdp_axes else None
    fn = jax.shard_map(
        device_fn, mesh=mesh,
        in_specs=(P(), P(model_axis, wa, None), P(model_axis, wa, None),
                  P(model_axis, None, wa), P(data_axes, None, None)),
        out_specs=P(data_axes, None, None),
        check_vma=False)
    y = fn(params["router"], params["w_gate"], params["w_up"],
           params["w_down"], x)
    return y, {"lb_loss": jnp.zeros(()), "dropped_frac": jnp.zeros(())}
