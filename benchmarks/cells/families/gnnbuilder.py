"""Family ``gnnbuilder``: the paper's conv-stack models (``gcn``, ``pna``)
on QM9-sized molecules.

A family module is how the harness builds, feeds, checks and counts the
model of a configuration. A configuration names its family with the
top-level key ``"family"``, ``gnnbuilder`` where the key is absent, and
the harness loads ``families/<family>.py`` by path. Every family defines
the five functions below; their docstrings state the contract. This one
delegates to ``bench_reference``, ``bench_flops`` and ``bench_molecules``.
"""
from __future__ import annotations

import bench_flops
import bench_molecules
import bench_reference


def program_config(config: dict):
    """The program's model configuration for ``config``: one that
    ``gnn_model.resolve_policy``, ``apply_packed`` and
    ``make_sharded_apply`` take, so the model runs through the program's
    normal entry points."""
    from repro.core.gnn_model import GNNModelConfig, MLPConfig
    m = config["model"]
    par = m["parallelism"]
    return GNNModelConfig(
        graph_input_feature_dim=m["node_feat_dim"],
        graph_input_edge_dim=m["edge_feat_dim"],
        gnn_hidden_dim=m["hidden_dim"], gnn_num_layers=m["num_layers"],
        gnn_output_dim=m["output_dim"], gnn_conv=m["conv"],
        gnn_activation=m["activation"],
        gnn_skip_connection=m["skip_connection"],
        global_pooling=tuple(m["global_pooling"]),
        mlp_head=MLPConfig(
            in_dim=m["output_dim"] * len(m["global_pooling"]),
            out_dim=m["num_targets"], hidden_dim=m["mlp_hidden_dim"],
            hidden_layers=m["mlp_hidden_layers"],
            activation=m["mlp_activation"], p_in=par["mlp_p_in"],
            p_hidden=par["mlp_p_hidden"], p_out=par["mlp_p_out"]),
        gnn_p_in=par["gnn_p_in"], gnn_p_hidden=par["gnn_p_hidden"],
        gnn_p_out=par["gnn_p_out"], pna_delta=m["pna_delta"],
        gnn_dataflow=m["dataflow"], avg_degree=m["avg_degree"],
        gnn_precision=config["precision"]["program"])


def init_params(model: dict, key):
    """Weights of ``config["model"]`` from a JAX key, in the layout of
    the program's ``gnn_model.model_plan``; traced inside one jitted
    call."""
    return bench_reference.init_params(model, key)


def make_pool(config: dict, seed: int, size: int) -> list:
    """``size`` distinct request dicts of one seed, with the keys
    ``node_feat``, ``edge_index``, ``edge_feat``, ``num_nodes``,
    ``num_edges`` and ``y``, padded per graph."""
    return bench_molecules.make_pool(config["molecules"], seed, size)


def reference_outputs(params, config: dict, graphs: list, *,
                      precision: str, **reference):
    """The plain reference, importing nothing of the program: one output
    row per graph of ``graphs`` (the pool's dicts). ``reference`` is the
    configuration's ``reference`` block; ``precision`` is ``highest``,
    or ``bf16x3`` for ``bench_control``'s control."""
    return bench_reference.reference_outputs(
        params, config["model"], graphs, precision=precision, **reference)


def graph_flops(model: dict, num_nodes: int, num_edges: int) -> int:
    """Model FLOPs of one graph of ``config["model"]``, under the
    conventions of ``bench_flops``'s docstring."""
    return bench_flops.graph_flops(model, num_nodes, num_edges)
