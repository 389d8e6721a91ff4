"""Compute/communication overlap: collective matmul (shard_map).

XLA's latency-hiding scheduler overlaps async collectives with compute on
TPU, but the *algorithmic* overlap for TP boundaries is the collective
matmul: instead of all-gather(X) then X@W, rotate shards around the ring
with ppermute and accumulate one shard-slice of the product per step —
each permute overlaps with the previous step's matmul. This removes the
serialized all-gather from the critical path (Wang et al., "Overlap
communication with dependent computation", the pattern behind Megatron's
`--overlap-grad-reduce`-style schedules on TPU).

``ag_matmul``  : Y = all_gather(X, seq) @ W        (forward TP boundary)
``matmul_rs``  : Y = reduce_scatter(X @ W, seq)    (output TP boundary)
Used opt-in via shard_map on the `model` axis; the pjit path keeps plain
GSPMD collectives (the dry-run measures those), and equivalence is tested
against the unoverlapped reference on a fake multi-device mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _ring_perm(n: int):
    return [(j, (j + 1) % n) for j in range(n)]


def ag_matmul_local(x_local, w, axis_name: str):
    """Per-shard body: y = all_gather(x, axis) @ w, overlapped.

    x_local: (m_local, k) — this shard's rows of the seq/row-sharded X.
    w: (k, n) replicated. Returns (m_local * world, n): the full product,
    computed as `world` local matmuls, each overlapping the ring permute
    that fetches the next shard.
    """
    world = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    m_local, _ = x_local.shape
    n_out = w.shape[1]
    out = jnp.zeros((m_local * world, n_out), x_local.dtype)

    def body(i, carry):
        out, x_cur = carry
        y = x_cur @ w                                # compute ...
        x_next = jax.lax.ppermute(                   # ... overlaps permute
            x_cur, axis_name, _ring_perm(world))
        src = (me - i) % world                       # whose rows these are
        out = jax.lax.dynamic_update_slice(out, y, (src * m_local, 0))
        return out, x_next

    out, _ = jax.lax.fori_loop(0, world, body, (out, x_local))
    return out


def matmul_rs_local(x_local, w_local, axis_name: str):
    """Per-shard body: y = reduce_scatter(x @ w, rows), overlapped.

    x_local: (m, k_local) row-full, contraction-sharded; w_local:
    (k_local, n). Returns (m / world, n): this shard's rows of the reduced
    product. Each step computes the slice destined for one shard and
    ring-forwards the partial accumulator (matmul overlaps the permute).
    """
    world = jax.lax.psum(1, axis_name)
    me = jax.lax.axis_index(axis_name)
    m = x_local.shape[0]
    assert m % world == 0
    m_loc = m // world
    n_out = w_local.shape[1]

    def slice_for(dst):
        return jax.lax.dynamic_slice(x_local, (dst * m_loc, 0),
                                     (m_loc, x_local.shape[1]))

    def contrib(dst):
        return (slice_for(dst) @ w_local).astype(jnp.float32)

    # ring schedule: at step i, shard `me` adds its contribution for
    # destination (me - i - 1) and forwards; the accumulator for shard d
    # visits every shard and arrives home at the final (unpermuted) step.
    def body(i, acc):
        d = (me - i - 1) % world
        acc = acc + contrib(d)
        return jax.lax.ppermute(acc, axis_name, _ring_perm(world))

    acc = jax.lax.fori_loop(0, world - 1, body,
                            jnp.zeros((m_loc, n_out), jnp.float32))
    acc = acc + contrib(me)          # d_{w-1}(me) == me
    return acc.astype(x_local.dtype)


def make_overlapped_ops(mesh: Mesh, axis: str = "model"):
    """shard_map-wrapped (ag_matmul, matmul_rs) bound to a mesh axis."""
    other = tuple(a for a in mesh.axis_names if a != axis)

    ag = jax.shard_map(
        functools.partial(ag_matmul_local, axis_name=axis), mesh=mesh,
        in_specs=(P(axis, None), P(None, None)),
        out_specs=P(None, None), check_vma=False)

    rs = jax.shard_map(
        functools.partial(matmul_rs_local, axis_name=axis), mesh=mesh,
        in_specs=(P(None, axis), P(axis, None)),
        out_specs=P(axis, None), check_vma=False)
    return ag, rs
