"""Plain reference of the molecule models, in float32 ``jax.numpy``.

It imports nothing of the program. Each graph is a dense block padded
to ``node_pad`` nodes and ``edge_pad`` edges; message passing is written
with one-hot incidence matrices rather than segment reductions, so it
shares no code path with the packed program it checks. It follows the
program's layer definitions (``repro.core.convs``, ``gnn_model``):

* ``gcn``: ``x' = W (A_hat x) + b`` with ``A_hat = D^-1/2 (A + I)
  D^-1/2``, degrees counted over in-edges plus the self loop.
* ``pna``: message ``relu(W_pre [x_dst ; x_src ; e] + b_pre)`` on every
  edge; mean, min, max and std over each node's in-edges (empty
  neighbourhoods give 0, and std clamps the variance at 1e-12 before
  the root); each tower scaled by identity, ``log(d + 1) / delta`` and
  ``delta / log(d + 1)`` with ``d = max(in_degree, 1)``; then
  ``W_post [x ; towers] + b_post``.
* every layer: ``relu(conv(x) + skip(x))`` masked to the graph's nodes,
  with a linear skip (no bias) where the widths differ.
* pooling: add, mean and max over the graph's nodes, concatenated; an
  MLP head with ReLU between its layers.

Departures from the published layers, kept because the program makes
them: PNA's ``delta`` is the configuration's ``pna_delta`` (1.0), not
the training set's mean log degree, and its message map takes no
separate towers. GCN's two layer orders (aggregate first or transform
first) are the same map; the reference always aggregates first.

``precision`` names how the weight matrices are multiplied: ``highest``
(full float32) or ``bf16x3`` (each float32 operand split into a high
and a low bfloat16 part, the low-by-low product dropped: what a TPU's
``high`` precision does, the control of ``bench_control``). The sums
over neighbours stay at full precision in both modes, since the program
computes them as float32 sums and not as matrix products.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench_flops import head_widths, layer_widths

PRECISIONS = ("highest", "bf16x3")
_HIGHEST = jax.lax.Precision.HIGHEST


def matmul(a, b, precision: str):
    if precision == "highest":
        return jnp.matmul(a, b, precision=_HIGHEST)
    if precision != "bf16x3":
        raise ValueError(f"unknown precision {precision!r}")

    def dot(x, y):
        return jnp.matmul(x, y, preferred_element_type=jnp.float32)

    def split(x):
        # explicit rounding: a compiler that may keep excess precision
        # drops a convert to bfloat16 and back (XLA on a TPU does)
        hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        lo = jax.lax.reduce_precision(x - hi, exponent_bits=8,
                                      mantissa_bits=7)
        return hi.astype(jnp.bfloat16), lo.astype(jnp.bfloat16)

    ah, al = split(a)
    bh, bl = split(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _exact(a, b):
    return jnp.matmul(a, b, precision=_HIGHEST)


# ------------------------------------------------------------- weights --
def param_shapes(model: dict) -> dict:
    """Weight tree of the model, in the layout the program's
    ``gnn_model.model_plan`` uses."""
    fe = model["edge_feat_dim"]
    convs = {}
    tree: dict = {"convs": convs}
    for i, (fi, fo) in enumerate(layer_widths(model)):
        if model["conv"] == "gcn":
            convs[f"c{i}"] = {"w": {"w": (fi, fo), "b": (fo,)}}
        elif model["conv"] == "pna":
            convs[f"c{i}"] = {"pre": {"w": (2 * fi + fe, fi), "b": (fi,)},
                              "post": {"w": (13 * fi, fo), "b": (fo,)}}
        else:
            raise ValueError(f"no reference for conv {model['conv']!r}")
        if model["skip_connection"] and fi != fo:
            tree[f"skip{i}"] = {"w": (fi, fo)}
    tree["mlp"] = {f"l{j}": {"w": (a, b), "b": (b,)}
                   for j, (a, b) in enumerate(head_widths(model))}
    return tree


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init_params(model: dict, key):
    """Seeded float32 weights: matrices normal / sqrt(fan-in), biases
    normal * 0.1 (non-zero, so that every bias add is checked)."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(
        param_shapes(model), is_leaf=_is_shape)
    leaves = []
    for i, (path, shape) in enumerate(paths):
        k = jax.random.fold_in(key, i)
        z = jax.random.normal(k, shape, jnp.float32)
        is_bias = path[-1].key == "b"
        leaves.append(z * (0.1 if is_bias else 1.0 / np.sqrt(shape[0])))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------- forward --
def _gcn(p, x, dst1h, src1h, in_deg, mm):
    adj = _exact(jnp.swapaxes(dst1h, 1, 2), src1h)        # (B, N, N) u->v
    inv = 1.0 / jnp.sqrt(in_deg + 1.0)
    n = x.shape[1]
    a_hat = inv[:, :, None] * adj * inv[:, None, :] \
        + jnp.eye(n, dtype=jnp.float32)[None] * (inv * inv)[:, :, None]
    return mm(_exact(a_hat, x), p["w"]["w"]) + p["w"]["b"]


def _take_rows(x, idx):
    return jnp.take_along_axis(x, jnp.maximum(idx, 0)[..., None], axis=1)


def _pna(p, x, ef, src, dst, dst1h, in_deg, delta, mm):
    msg = jax.nn.relu(mm(jnp.concatenate(
        [_take_rows(x, dst), _take_rows(x, src), ef], axis=-1),
        p["pre"]["w"]) + p["pre"]["b"])                    # (B, E, F)
    inc = jnp.swapaxes(dst1h, 1, 2)                         # (B, N, E)
    c = jnp.maximum(in_deg, 1.0)[..., None]
    mean = _exact(inc, msg) / c
    on = dst1h[..., None] > 0                               # (B, E, N, 1)
    big = msg[:, :, None, :]
    mx = jnp.max(jnp.where(on, big, -jnp.inf), axis=1)
    mn = jnp.min(jnp.where(on, big, jnp.inf), axis=1)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    mn = jnp.where(jnp.isfinite(mn), mn, 0.0)
    dev = msg - _take_rows(mean, dst)
    var = jnp.maximum(_exact(inc, dev * dev) / c, 1e-12)
    std = jnp.sqrt(var)
    logd = jnp.log(jnp.maximum(in_deg, 1.0) + 1.0)[..., None]
    towers = [x]
    for t in (mean, mn, mx, std):
        towers += [t, t * (logd / delta), t * (delta / logd)]
    return mm(jnp.concatenate(towers, axis=-1), p["post"]["w"]) \
        + p["post"]["b"]


@functools.partial(jax.jit, static_argnames=("model_key", "precision"))
def _forward(params, blk, model_key, precision):
    model = dict(model_key)
    mm = functools.partial(matmul, precision=precision)
    x = blk["node_feat"]
    src, dst = blk["edge_src"], blk["edge_dst"]
    b, n, _ = x.shape
    e = src.shape[1]
    node_mask = (jnp.arange(n)[None] < blk["num_nodes"][:, None])
    edge_ok = (jnp.arange(e)[None] < blk["num_edges"][:, None])
    nodes = jnp.arange(n)
    dst1h = ((dst[..., None] == nodes) & edge_ok[..., None]).astype(
        jnp.float32)                                        # (B, E, N)
    src1h = ((src[..., None] == nodes) & edge_ok[..., None]).astype(
        jnp.float32)
    in_deg = jnp.sum(dst1h, axis=1)                         # (B, N)
    maskf = node_mask[..., None].astype(jnp.float32)
    for i, (fi, fo) in enumerate(layer_widths(model)):
        p = params["convs"][f"c{i}"]
        if model["conv"] == "gcn":
            h = _gcn(p, x, dst1h, src1h, in_deg, mm)
        else:
            h = _pna(p, x, blk["edge_feat"], src, dst, dst1h, in_deg,
                     model["pna_delta"], mm)
        if model["skip_connection"]:
            h = h + (mm(x, params[f"skip{i}"]["w"]) if fi != fo else x)
        x = jax.nn.relu(h) * maskf
    count = jnp.maximum(jnp.sum(maskf, axis=1), 1.0)
    pools = {"add": jnp.sum(x * maskf, axis=1),
             "sum": jnp.sum(x * maskf, axis=1),
             "mean": jnp.sum(x * maskf, axis=1) / count,
             "max": jnp.max(jnp.where(node_mask[..., None], x, -jnp.inf),
                            axis=1)}
    pools["max"] = jnp.where(jnp.isfinite(pools["max"]), pools["max"], 0.0)
    h = jnp.concatenate([pools[k] for k in model["global_pooling"]], -1)
    heads = head_widths(model)
    for j in range(len(heads)):
        lp = params["mlp"][f"l{j}"]
        h = mm(h, lp["w"]) + lp["b"]
        if j < len(heads) - 1:
            h = jax.nn.relu(h)
    return h


def _freeze(model: dict) -> tuple:
    keys = ("conv", "node_feat_dim", "edge_feat_dim", "hidden_dim",
            "num_layers", "output_dim", "skip_connection",
            "global_pooling", "mlp_hidden_dim", "mlp_hidden_layers",
            "num_targets", "pna_delta")
    return tuple((k, tuple(model[k]) if isinstance(model[k], list)
                  else model[k]) for k in keys)


def dense_block(mols: list, node_pad: int, edge_pad: int) -> dict:
    """Stack molecules (the generator's dicts) into padded dense arrays."""
    b = len(mols)
    f = mols[0]["node_feat"].shape[1]
    fe = mols[0]["edge_feat"].shape[1]
    blk = {"node_feat": np.zeros((b, node_pad, f), np.float32),
           "edge_src": np.full((b, edge_pad), -1, np.int32),
           "edge_dst": np.full((b, edge_pad), -1, np.int32),
           "edge_feat": np.zeros((b, edge_pad, fe), np.float32),
           "num_nodes": np.zeros((b,), np.int32),
           "num_edges": np.zeros((b,), np.int32)}
    for i, m in enumerate(mols):
        n, e = m["num_nodes"], m["num_edges"]
        if n > node_pad or e > edge_pad:
            raise ValueError(f"molecule of {n} nodes/{e} edges exceeds the "
                             f"reference's pad ({node_pad}/{edge_pad})")
        blk["node_feat"][i, :n] = m["node_feat"][:n]
        blk["edge_src"][i, :e] = m["edge_index"][:e, 0]
        blk["edge_dst"][i, :e] = m["edge_index"][:e, 1]
        blk["edge_feat"][i, :e] = m["edge_feat"][:e]
        blk["num_nodes"][i] = n
        blk["num_edges"][i] = e
    return blk


def reference_outputs(params, model: dict, mols: list, *, node_pad: int,
                      edge_pad: int, block_graphs: int,
                      precision: str = "highest") -> np.ndarray:
    """(len(mols), num_targets) reference outputs, computed block by
    block; the last block is padded with copies so every call has one
    shape."""
    key = _freeze(model)
    out = []
    for s in range(0, len(mols), block_graphs):
        chunk = mols[s:s + block_graphs]
        real = len(chunk)
        chunk = chunk + [chunk[-1]] * (block_graphs - real)
        blk = dense_block(chunk, node_pad, edge_pad)
        out.append(np.asarray(_forward(params, blk, key, precision))[:real])
    return np.concatenate(out, axis=0)
