"""Pallas TPU kernel: blocked online-softmax (flash) attention.

LM-pillar hot spot for the prefill shapes (32 k tokens).  Standard
single-pass streaming softmax: grid (batch*heads, q_blocks, kv_blocks)
with the kv loop innermost; running (max, denom, acc) state in VMEM
scratch; causal and sliding-window masks applied from global indices.

GQA is handled in the BlockSpec index maps — the K/V block index maps
divide the head index by the group size, so no materialized KV repeat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array

NEG_INF = -1e30


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                 scale: float, causal: bool, window: int | None,
                 block_q: int, block_k: int, n_kv_blocks: int):
    gq = pl.program_id(1)
    gk = pl.program_id(2)

    @pl.when(gk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [bq, d]
    k = k_ref[0].astype(jnp.float32)            # [bk, d]
    v = v_ref[0].astype(jnp.float32)            # [bk, d]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    q_idx = gq * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_idx = gk * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones_like(s, dtype=jnp.bool_)
    if causal:
        mask &= k_idx <= q_idx
    if window is not None:
        mask &= k_idx > q_idx - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    # fully-masked rows: keep them inert (exp(NEG_INF - NEG_INF) = 1 trap)
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(gk == n_kv_blocks - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                              "interpret"))
def flash_attention(q: Array, k: Array, v: Array, *,
                    causal: bool = True, window: int | None = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = True) -> Array:
    """q: [B, Hq, S, D], k/v: [B, Hkv, S, D] with Hq % Hkv == 0."""
    b, hq, s, d = q.shape
    hkv = k.shape[1]
    assert hq % hkv == 0
    group = hq // hkv
    bq = min(block_q, s)
    bk = min(block_k, s)
    s_pad = -(-s // max(bq, bk)) * max(bq, bk)
    if s_pad != s:
        pad = ((0, 0), (0, 0), (0, s_pad - s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    # padded KV rows must never be attended to: they sit at indices >= s and
    # a query at index < s is protected by the causal mask; for non-causal
    # use we mask below via window=None + causal=False only with s == s_pad.
    if not causal and s_pad != s:
        raise NotImplementedError("non-causal requires s % block == 0")

    qf = q.reshape(b * hq, s_pad, d)
    kf = k.reshape(b * hkv, s_pad, d)
    vf = v.reshape(b * hkv, s_pad, d)
    n_q, n_k = s_pad // bq, s_pad // bk

    q_spec = pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0))
    kv_spec = pl.BlockSpec((1, bk, d), lambda h, i, j: (h // group, j, 0))
    o_spec = pl.BlockSpec((1, bq, d), lambda h, i, j: (h, i, 0))

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=d ** -0.5, causal=causal,
                          window=window, block_q=bq, block_k=bk,
                          n_kv_blocks=n_k),
        grid=(b * hq, n_q, n_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((b * hq, s_pad, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, hq, s_pad, d)[:, :, :s, :]
