"""Pure-jnp oracle for flash_attention."""
import jax.numpy as jnp


def attention_ref(q, k, v, *, causal: bool = True):
    bh, sq, d = q.shape
    skv = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * d ** -0.5
    if causal:
        mask = jnp.arange(skv)[None, :] <= jnp.arange(sq)[:, None]
        s = jnp.where(mask[None], s, -1e30)
    p = jnp.exp(s - s.max(-1, keepdims=True))
    p = p / p.sum(-1, keepdims=True)
    return jnp.einsum("bqk,bkd->bqd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


def segment_attention_ref(q, k, v, graph_id, num_heads: int,
                          num_graphs: int):
    """Dense oracle of ``segment_attention``: (N, N) scores per head,
    each row masked to the rows of its own graph; padding rows
    (``graph_id == num_graphs``) give 0."""
    n, d = q.shape
    dh = d // num_heads
    qh = q.astype(jnp.float32).reshape(n, num_heads, dh)
    kh = k.astype(jnp.float32).reshape(n, num_heads, dh)
    vh = v.astype(jnp.float32).reshape(n, num_heads, dh)
    s = jnp.einsum("qhd,khd->hqk", qh, kh) * dh ** -0.5
    same = (graph_id[:, None] == graph_id[None, :]) \
        & (graph_id < num_graphs)[:, None]
    s = jnp.where(same[None], s, -1e30)
    p = jnp.where(same[None], jnp.exp(s - s.max(-1, keepdims=True)), 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    return jnp.einsum("hqk,khd->qhd", p, vh).reshape(n, d)
