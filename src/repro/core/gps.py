"""GraphGPS blocks (Rampášek et al., arXiv:2205.12454) on the packed path.

A GPS model replaces the conv stack of ``gnn_model`` with

* an encoder: categorical atom embeddings summed (OGB's AtomEncoder)
  concatenated with a LapPE DeepSet over the graph's Laplacian
  eigenpairs, and categorical bond embeddings summed for the edge
  state;
* ``gnn_num_layers`` GPS layers, each the sum of a local conv with an
  edge state (the registry's ``gnn_conv``, GatedGCN in GraphGPS) and
  multi-head self-attention over the nodes of the same graph, then a
  feed-forward block, with BatchNorm in eval mode (a per-channel affine
  from the running statistics) after each part;

and ``gnn_model`` pools and applies the MLP head as for any model.

Node features carry, per node, the atom's categorical ids (as floats,
one column per vocabulary) and then ``pe_eigvecs`` eigenvector entries,
as many eigenvalues and as many 0/1 columns that say which eigenpairs
exist (a graph of fewer nodes has fewer). Edge features carry the bond's
categorical ids. The eigenpairs travel with the request, as GraphGPS's
pre-transform stores them with the dataset.

Attention runs through ``kernels/flash_attention``'s segment-masked
mode; its key-range table comes from ``graph_num_nodes``.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import convs as C
from repro.kernels.flash_attention import ops as attn_ops
from repro.nn.layers import linear, linear_plan
from repro.nn.param import ParamSpec


@dataclasses.dataclass(frozen=True)
class GPSConfig:
    """The GPS-specific widths and vocabularies; depth, width, the local
    conv, pooling and head are ``GNNModelConfig``'s own fields."""
    num_heads: int = 4
    ffn_dim: int = 192
    atom_vocab: tuple = (119, 5, 12, 12, 10, 6, 6, 2, 2)
    bond_vocab: tuple = (5, 6, 2)
    pe_eigvecs: int = 10
    pe_hidden: int = 32
    pe_dim: int = 16
    bn_eps: float = 1e-5

    def node_feat_dim(self) -> int:
        return len(self.atom_vocab) + 3 * self.pe_eigvecs


def _bn_plan(d: int, dtype):
    return {"scale": ParamSpec((d,), dtype, (None,), init="ones"),
            "bias": ParamSpec((d,), dtype, (None,), init="zeros"),
            "mean": ParamSpec((d,), dtype, (None,), init="zeros"),
            "var": ParamSpec((d,), dtype, (None,), init="ones")}


def _lin(a: int, b: int, dtype):
    return linear_plan(a, b, in_axis=None, out_axis=None, bias=True,
                       dtype=dtype)


def gps_plan(cfg, dtype=jnp.float32) -> dict:
    """Encoder and layer parameters of the GPS model ``cfg`` (a
    ``GNNModelConfig`` with ``gps`` set); ``gnn_model.model_plan`` adds
    the head."""
    g, d = cfg.gps, cfg.gnn_hidden_dim
    atom_dim = d - g.pe_dim
    enc = {"atom": {f"f{i}": ParamSpec((v, atom_dim), dtype, (None, None))
                    for i, v in enumerate(g.atom_vocab)},
           "pe": {"l1": _lin(2, g.pe_hidden, dtype),
                  "l2": _lin(g.pe_hidden, g.pe_dim, dtype)},
           "bond": {f"f{i}": ParamSpec((v, d), dtype, (None, None))
                    for i, v in enumerate(g.bond_vocab)}}
    layers = {}
    for i in range(cfg.gnn_num_layers):
        layers[f"l{i}"] = {
            "local": C.conv_plan(cfg.conv_cfg(i), dtype),
            "bn_node": _bn_plan(d, dtype), "bn_edge": _bn_plan(d, dtype),
            "bn_local": _bn_plan(d, dtype),
            "attn": {"in": _lin(d, 3 * d, dtype), "out": _lin(d, d, dtype)},
            "bn_attn": _bn_plan(d, dtype),
            "ffn": {"l1": _lin(d, g.ffn_dim, dtype),
                    "l2": _lin(g.ffn_dim, d, dtype)},
            "bn_ffn": _bn_plan(d, dtype)}
    return {"encoder": enc, "gps": layers}


def batch_norm(p, x, eps: float):
    """BatchNorm1d in eval mode: the running statistics' affine."""
    inv = jax.lax.rsqrt(p["var"].astype(jnp.float32) + eps)
    return (x - p["mean"]) * (inv * p["scale"]) + p["bias"]


def _embed(tables: dict, ids):
    ids = ids.astype(jnp.int32)
    out = 0.0
    for f in range(ids.shape[1]):
        t = tables[f"f{f}"]
        out = out + jnp.take(t, jnp.clip(ids[:, f], 0, t.shape[0] - 1),
                             axis=0)
    return out


def encode(params, cfg, x, edge_feat, node_mask):
    """(h, e): the node table (N, hidden) and the edge state (E,
    hidden) the first layer takes."""
    g = cfg.gps
    na, k = len(g.atom_vocab), g.pe_eigvecs
    h_atom = _embed(params["atom"], x[:, :na])
    pair = jnp.stack([x[:, na:na + k], x[:, na + k:na + 2 * k]], axis=-1)
    z = jax.nn.relu(linear(params["pe"]["l1"], pair))       # (N, k, 32)
    z = jax.nn.relu(linear(params["pe"]["l2"], z))          # (N, k, 16)
    pe = jnp.sum(z * x[:, na + 2 * k:na + 3 * k, None], axis=1)
    h = jnp.concatenate([h_atom, pe], axis=-1) * node_mask[:, None]
    return h, _embed(params["bond"], edge_feat)


def _layer(p, cfg, cc, lp, g, h, e, graph_id, graph_num_nodes):
    gc, eps = cfg.gps, cfg.gps.bn_eps
    cast = (lambda t: t) if lp is None else lp.cast_activation
    if lp is not None:
        p = dict(p, local=lp.cast_params(p["local"]),
                 attn=lp.cast_params(p["attn"]),
                 ffn=lp.cast_params(p["ffn"]))
    with jax.named_scope("local"):
        hc, ec = C.conv_apply(p["local"], dict(g, edge_state=cast(e)),
                              cast(h), cc)
        h_loc = h + jax.nn.relu(batch_norm(p["bn_node"],
                                           hc.astype(jnp.float32), eps))
        e = e + jax.nn.relu(batch_norm(p["bn_edge"],
                                       ec.astype(jnp.float32), eps))
        e = e * g["valid_e"][:, None]
        h_loc = batch_norm(p["bn_local"], h_loc, eps)
    with jax.named_scope("attn"):
        d = h.shape[1]
        qkv = linear(p["attn"]["in"], cast(h)).astype(jnp.float32)
        o = attn_ops.segment_attention(
            qkv[:, :d], qkv[:, d:2 * d], qkv[:, 2 * d:], graph_id,
            graph_num_nodes, num_heads=gc.num_heads)
        o = linear(p["attn"]["out"], cast(o)).astype(jnp.float32)
        h_att = batch_norm(p["bn_attn"], h + o, eps)
    h = h_loc + h_att
    with jax.named_scope("ffn"):
        f = jax.nn.relu(linear(p["ffn"]["l1"], cast(h)))
        f = linear(p["ffn"]["l2"], f).astype(jnp.float32)
        h = batch_norm(p["bn_ffn"], h + f, eps)
    return h, e


def gps_backbone(params, cfg, g, x, node_mask, graph_id, graph_num_nodes,
                 policy=None):
    """Encoder and GPS layers over a packed batch (or one padded graph
    as a batch of one): the (N, hidden) node table, padding rows 0.

    ``policy`` (None for fp32): each layer's matmuls and message stream
    run at the layer's compute width, the residual stream, BatchNorm and
    the attention softmax in fp32. int8 raises NotImplementedError."""
    if policy is not None and any(
            policy.layer(i).compute == "int8"
            for i in range(cfg.gnn_num_layers)):
        raise NotImplementedError(
            "GPS models run at fp32 or bf16; the int8 policy has no "
            "calibration probe for the attention and BatchNorm blocks")
    with jax.named_scope("encoder"):
        h, e = encode(params["encoder"], cfg, x.astype(jnp.float32),
                      g["edge_feat"], node_mask)
    mask = node_mask[:, None].astype(jnp.float32)
    for i in range(cfg.gnn_num_layers):
        with jax.named_scope(f"gps{i}"):
            lp = policy.layer(i) if policy is not None else None
            lp = None if lp is None or lp.compute == "fp32" else lp
            cc = cfg.conv_cfg(i)
            if lp is not None:
                cc = dataclasses.replace(cc, precision=lp)
            h, e = _layer(params["gps"][f"l{i}"], cfg, cc, lp, g, h, e,
                          graph_id, graph_num_nodes)
            h = h * mask
    return h

