"""Pallas TPU kernel: blocked online-softmax attention (forward).

The LM-substrate hot spot (beyond the paper): causal flash attention with
(block_q x block_k) tiles, fp32 running max / denominator / accumulator in
VMEM scratch. Grid: (batch*heads, Sq/bq, Skv/bk) with the KV axis as the
sequential (innermost) dimension.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.aggregations import resolve_interpret

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, block_q: int, block_k: int,
                  kv_steps: int):
    kv_i = pl.program_id(2)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale        # (bq, d)
    k = k_ref[0].astype(jnp.float32)                # (bk, d)
    v = v_ref[0].astype(jnp.float32)                # (bk, dv)
    s = q @ k.T                                     # (bq, bk)
    if causal:
        qi = pl.program_id(1)
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], k.shape[0]), 0)
        k_pos = kv_i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], k.shape[0]), 1)
        s = jnp.where(k_pos <= q_pos, s, NEG_INF)

    m_prev, l_prev, acc_prev = m_ref[...], l_ref[...], acc_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_new = acc_prev * corr + p @ v
    m_ref[...], l_ref[...], acc_ref[...] = m_new, l_new, acc_new

    @pl.when(kv_i == kv_steps - 1)
    def _done():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: (BH, Sq, D); k, v: (BH, Skv, D) -> (BH, Sq, D)."""
    bh, sq, d = q.shape
    skv, dv = k.shape[1], v.shape[-1]
    bq, bk = min(block_q, sq), min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "pad seq to tile multiples"
    kv_steps = skv // bk
    scale = d ** -0.5
    return pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, kv_steps=kv_steps),
        grid=(bh, sq // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, dv), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, dv), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dv), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(q, k, v)
