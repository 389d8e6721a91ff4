"""Molecule generator of the benchmark: QM9-sized random graphs from a seed.

A copy of the algorithm of ``repro.data.pipeline.make_graph`` as it stood
when the benchmark was defined, kept here so that a later change to the
program's own generator cannot move the yardstick. Graph ``idx`` of seed
``seed`` draws from ``SeedSequence([seed, idx])``:

* ``n`` nodes, Poisson(``avg_nodes``) clipped to [``min_nodes``,
  ``max_nodes``];
* a random spanning tree (node ``i`` attaches to a parent below it),
  stored as both directions, plus ``n * (avg_degree - 2) / 2``
  ring-closing pairs (none at the QM9 degree of 2);
* standard-normal node and edge features;
* buffers padded to ``max_nodes`` rows (node features) and ``max_edges``
  rows (edge index, padded with -1; edge features, zero).

The result is a plain dict of numpy arrays; the harness wraps it in the
program's request type.
"""
from __future__ import annotations

import numpy as np


def make_molecule(mol: dict, seed: int, idx: int) -> dict:
    rng = np.random.default_rng(np.random.SeedSequence([seed, idx]))
    n = int(np.clip(rng.poisson(mol["avg_nodes"]), mol["min_nodes"],
                    mol["max_nodes"]))
    parents = np.array([rng.integers(0, max(i, 1)) for i in range(1, n)])
    src = np.concatenate([np.arange(1, n), parents])
    dst = np.concatenate([parents, np.arange(1, n)])
    extra = max(0, int(n * (mol["avg_degree"] - 2) / 2))
    if extra:
        a = rng.integers(0, n, extra)
        b = (a + 1 + rng.integers(0, n - 1, extra)) % n
        src = np.concatenate([src, a, b])
        dst = np.concatenate([dst, b, a])
    e = min(len(src), mol["max_edges"])
    edge_index = np.full((mol["max_edges"], 2), -1, np.int32)
    edge_index[:e, 0] = src[:e]
    edge_index[:e, 1] = dst[:e]
    node_feat = np.zeros((mol["max_nodes"], mol["node_feat_dim"]),
                         np.float32)
    node_feat[:n] = rng.standard_normal((n, mol["node_feat_dim"]))
    edge_feat = np.zeros((mol["max_edges"], mol["edge_feat_dim"]),
                         np.float32)
    edge_feat[:e] = rng.standard_normal((e, mol["edge_feat_dim"]))
    y = np.array([node_feat[:n].mean() + 0.1 * e / max(n, 1)]
                 * mol["num_targets"], np.float32)
    return {"node_feat": node_feat, "edge_index": edge_index,
            "edge_feat": edge_feat, "num_nodes": n, "num_edges": e, "y": y}


def make_pool(mol: dict, seed: int, size: int) -> list:
    """``size`` distinct molecules of one seed."""
    return [make_molecule(mol, seed, i) for i in range(size)]
