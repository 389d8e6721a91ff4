"""jit'd wrapper for flash_attention."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.flash_attention.ref import attention_ref


@partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                   "use_pallas", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 128,
                    block_k: int = 128, use_pallas: bool = True,
                    interpret: bool | None = None):
    """q/k/v: (B, H, S, D) or (BH, S, D)."""
    squeeze = q.ndim == 4
    if squeeze:
        b, h, s, d = q.shape
        rs = lambda t: t.reshape(b * h, *t.shape[2:])
        q, k, v = rs(q), rs(k), rs(v)
    if use_pallas:
        # pad seq dims to tile multiples
        sq, skv = q.shape[1], k.shape[1]
        bq, bk = min(block_q, sq), min(block_k, skv)
        pq, pk = (-sq) % bq, (-skv) % bk
        if pq:
            q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
        if pk:
            k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
            v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
        out = flash_attention_pallas(q, k, v, causal=causal, block_q=bq,
                                     block_k=bk, interpret=interpret)
        out = out[:, :sq]
    else:
        out = attention_ref(q, k, v, causal=causal)
    if squeeze:
        out = out.reshape(b, h, s, -1)
    return out
