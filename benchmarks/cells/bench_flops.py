"""Model FLOPs of one graph, from the configuration's widths.

The count is the benchmark's own and does not look at how the program
computes: it counts the work the model needs for a graph of ``n`` nodes
and ``e`` directed edges, and none of the padding a packed batch
carries. Conventions:

* a linear map of ``r`` rows from width ``a`` to ``b``: ``2 r a b``
  (multiply and add), plus ``r b`` for its bias;
* one FLOP per message element per reduction step; a scaled sum (GCN)
  costs two (multiply by the edge's norm, add);
* activations, gathers and comparisons of masks cost nothing.

Per conv layer of input width ``fi`` and output width ``fo``:

* ``gcn``: the normalised sum over edges and self loops at the narrower
  of the two widths, ``2 (e + n) min(fi, fo)``, then the linear map.
* ``pna``: the message map from ``[x_dst, x_src, edge]`` (``2 fi + fe``
  wide) to ``fi`` on every edge; four reductions of the messages (mean,
  min and max one FLOP each, std three: subtract, square, add); the two
  degree scalers on each of the four towers (``8 n fi``); the linear map
  from ``[x ; 12 towers]`` (``13 fi`` wide) to ``fo``.
* a skip connection whose widths differ: its linear map (no bias); the
  residual add, ``n fo``.

Then three poolings over the nodes (``3 n fo``) and the MLP head on the
pooled row.
"""
from __future__ import annotations


def _linear(rows: int, a: int, b: int, bias: bool = True) -> int:
    return 2 * rows * a * b + (rows * b if bias else 0)


def layer_widths(model: dict) -> list:
    out = []
    for i in range(model["num_layers"]):
        fi = model["node_feat_dim"] if i == 0 else model["hidden_dim"]
        fo = model["output_dim"] if i == model["num_layers"] - 1 \
            else model["hidden_dim"]
        out.append((fi, fo))
    return out


def head_widths(model: dict) -> list:
    dims = [model["output_dim"] * len(model["global_pooling"])] \
        + [model["mlp_hidden_dim"]] * model["mlp_hidden_layers"] \
        + [model["num_targets"]]
    return list(zip(dims[:-1], dims[1:]))


def graph_flops(model: dict, n: int, e: int) -> int:
    total = 0
    fe = model["edge_feat_dim"]
    for fi, fo in layer_widths(model):
        if model["conv"] == "gcn":
            total += 2 * (e + n) * min(fi, fo) + _linear(n, fi, fo)
        elif model["conv"] == "pna":
            total += _linear(e, 2 * fi + fe, fi)
            total += 6 * e * fi + 8 * n * fi
            total += _linear(n, 13 * fi, fo)
        else:
            raise ValueError(f"no FLOP count for conv {model['conv']!r}")
        if model["skip_connection"]:
            if fi != fo:
                total += _linear(n, fi, fo, bias=False)
            total += n * fo
    total += len(model["global_pooling"]) * n \
        * layer_widths(model)[-1][1]
    total += sum(_linear(1, a, b) for a, b in head_widths(model))
    return total
