"""CPU tests of the ``gps-peptides`` configuration: its family's peptide
pool, FLOP count and program configuration, the cell run end to end at
a reduced batch, and the readers of the three attention metrics, which
read nothing (and do not raise) where the program has no attention."""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as H  # noqa: E402
import bench_run  # noqa: E402

CONFIG = json.loads((HERE / "configs" / "gps-peptides.json").read_text())
FAMILY = H.family_of(CONFIG)
SEED = 2 ** 31 + 1601


def test_pool_has_the_peptides_func_statistics():
    """512 peptides: mean size near LRGB's 150.94 nodes, ~2.03 directed
    edges a node (LRGB: 307.30 / 150.94), none above 444 nodes, ids
    inside the OGB vocabularies, eigenpairs finite and flagged."""
    pool = FAMILY.make_pool(CONFIG, SEED, 512)
    n = np.array([m["num_nodes"] for m in pool])
    e = np.array([m["num_edges"] for m in pool])
    assert 135 < n.mean() < 165 and n.max() <= 444
    assert 2.0 < e.sum() / n.sum() < 2.06
    model = CONFIG["model"]
    for m in pool[:64]:
        x = m["node_feat"]
        assert x.shape == (m["num_nodes"], 9 + 3 * model["pe_eigvecs"])
        assert np.isfinite(x).all()
        assert (x[:, :9] < np.asarray(model["atom_vocab"])).all()
        assert (m["edge_feat"] < np.asarray(model["bond_vocab"])).all()
        assert (x[:, 9 + 2 * model["pe_eigvecs"]:] == 1.0).all()
        assert H.to_graph(m).num_edges == len(m["edge_index"])
    again = FAMILY.make_pool(CONFIG, SEED, 4)
    for a, b in zip(pool, again):
        np.testing.assert_array_equal(a["node_feat"], b["node_feat"])


def test_graph_flops_holds_the_per_layer_sizing():
    """One 151-node, 307-edge peptide: ~50 MFLOP a layer, of which the
    projections (4 node maps, the edge map, QKV and out, the FFN) are
    ~39 M and QK^T with PV 4 n^2 d."""
    model = CONFIG["model"]
    total = FAMILY.graph_flops(model, 151, 307)
    d = model["hidden_dim"]
    per_layer = (total - FAMILY.graph_flops(
        dict(model, num_layers=0), 151, 307)) / model["num_layers"]
    assert 45e6 < per_layer < 60e6
    assert per_layer > 4 * 151 ** 2 * d + 2 * (4 + 4 + 4) * 151 * d * d
    flops, nbytes = FAMILY.attention_work(model, 151 ** 2, 151)
    assert flops == 4 * 4 * d * 151 ** 2 and nbytes == 4 * 4 * d * 4 * 151


def test_program_config_names_the_gps_model():
    cfg = FAMILY.program_config(CONFIG)
    assert cfg.gps is not None and cfg.gnn_conv == "gatedgcn"
    assert cfg.gps.num_heads == 4 and cfg.gnn_hidden_dim == 96
    assert cfg.mlp_head.dims == [96, 48, 24, 10]
    assert cfg.graph_input_feature_dim == 39


def test_reduced_cell_runs_end_to_end(monkeypatch):
    """``run_cell`` on the cell's own configuration and family with a
    16-graph batch and a 48-graph pool, the chip look skipped: correct,
    every request served, the attention fill (asked for here, as
    ``BENCHMARK.json`` does not list it yet) read from the counters."""
    import jax
    saved = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(
        bench_run, "configure_jax",
        lambda config: jax.config.update(
            "jax_default_matmul_precision",
            config["precision"]["matmul_precision"]))
    cell = H.load_cell("gps-peptides.screen")
    cell.config = copy.deepcopy(cell.config)
    cell.config["serving"].update(batch_graphs=16, node_budget=2560,
                                  edge_budget=5120)
    cell.traffic = dict(cell.traffic, pool_graphs=48, chunk_graphs=48,
                        warmup_s=0.0)
    cell.per_layer = cell.per_layer + [
        {"name": "attn_fill.screen", "unit": "%"}]
    try:
        res = bench_run.run_cell(cell, SEED, 0.3, True, require_chip=False,
                                 t_start=time.perf_counter())
    finally:
        jax.config.update("jax_default_matmul_precision", saved)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert 20.0 < res["metrics"]["attn_fill.screen"]["value"] <= 100.0


# ------------------------------------------------------------ readers --
def _view(device_ops, launches=4):
    cell = SimpleNamespace(family=FAMILY, config=CONFIG)
    trace = {"device_ops": device_ops, "window_s": 1.0, "busy_s": 0.5}
    return H.RunView(cell, {"launches": launches}, trace, 0.0,
                     {"bf16_flops_per_s": 197e12,
                      "hbm_bytes_per_s": 819e9}, 1)


def _snapshot(monkeypatch, counters, launches=4):
    import bench_spans
    snap = {"spans": {"device.launch": {"n": launches, "total_s": 0.0,
                                        "self_s": 0.0, "max_s": 0.0}},
            "counters": counters}
    monkeypatch.setattr(bench_spans, "snapshot", lambda: snap)


def test_attention_readers_on_a_trace_with_the_kernel(monkeypatch):
    ops = [["%segment_attention.3 custom-call f32[22272,96]", 0.004],
           ["%fusion.7 fusion f32[22272,96]", 0.009],
           ["%segment_attention.1 custom-call f32[22272,96]", 0.004]]
    pairs, rows = 4 * 128 * 23000, 4 * 19300
    _snapshot(monkeypatch, {"attn.pairs": pairs, "attn.rows": rows,
                            "attn.pairs_computed": 3 * pairs})
    view = _view(ops)
    assert H.load_reader("attn_ms.screen")(view) == pytest.approx(2.0)
    assert H.load_reader("attn_fill.screen")(view) \
        == pytest.approx(100 / 3)
    flops, nbytes = FAMILY.attention_work(CONFIG["model"], pairs, rows)
    want = 100 * max(flops / 197e12, nbytes / 819e9) / 0.008
    assert H.load_reader("attn_roofline.screen")(view) \
        == pytest.approx(want)


def test_attention_readers_read_nothing_without_the_kernel(monkeypatch):
    _snapshot(monkeypatch, {})
    view = _view([["%fusion.7 fusion f32[872,128]", 0.004]])
    for name in ("attn_ms.screen", "attn_fill.screen",
                 "attn_roofline.screen"):
        assert H.load_reader(name)(view) is None
    assert H.load_reader("attn_ms.screen")(_view([])) is None
    untraced = _view([])
    untraced.trace = None
    assert H.load_reader("attn_roofline.screen")(untraced) is None
