"""CPU tests of the chip benchmark's harness: files found by name,
the molecule generator, the FLOP count, percentiles, the plain
references against the packed program, the trace reduction, and the
refusal to run without a TPU."""
from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_flops  # noqa: E402
import bench_harness as H  # noqa: E402
import bench_molecules  # noqa: E402
import bench_reference  # noqa: E402
import bench_stats  # noqa: E402
import bench_trace  # noqa: E402

BENCH = H.load_benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in BENCH["configs"]}
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


# ------------------------------------------------------------- files --
def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "benchmarks/cells/run.py"]
    assert BENCH["paths"] == ["benchmarks/cells"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + list(CONFIGS)
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 2)
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(CELLS)
        for w in m["workloads"]:
            assert "workloads" not in e2e[m["moves"]] \
                or w in e2e[m["moves"]]["workloads"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_load_by_name(cell):
    c = H.load_cell(cell)
    assert c.traffic["mode"] in ("screen", "online")
    names = [m["name"] for m in c.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert callable(H.load_reader(m["name"]))
    if c.chips > 1:
        assert c.traffic["shards"] == c.chips


@pytest.mark.parametrize("conv", ["gcn", "pna"])
def test_config_matches_program_benchmark_config(conv):
    """The files state today's ``configs.gnn.benchmark_config`` and the
    budgets ``serve.py`` derives for 32-graph batches."""
    from repro.configs.gnn import DATASETS, benchmark_config
    from repro.data import pipeline as P
    config = CONFIGS[f"{conv}-qm9"]
    assert H.model_config(config) == benchmark_config(conv)
    ds = DATASETS["qm9"]
    sv = config["serving"]
    assert sv["node_budget"] == P.size_budget(sv["batch_graphs"],
                                              ds.avg_nodes)
    assert sv["edge_budget"] == P.size_budget(
        sv["batch_graphs"], ds.avg_nodes * ds.avg_degree)
    mol = config["molecules"]
    for k in ("avg_nodes", "avg_degree", "node_feat_dim", "edge_feat_dim",
              "num_targets", "max_nodes", "max_edges"):
        assert mol[k] == getattr(ds, k)


# ---------------------------------------------------------- generator --
MOL = CONFIGS["gcn-qm9"]["molecules"]


@pytest.mark.parametrize("seed", [0, 9, 2 ** 31 + 17])
def test_pool_is_deterministic_per_seed(seed):
    a = bench_molecules.make_pool(MOL, seed, 16)
    b = bench_molecules.make_pool(MOL, seed, 16)
    c = bench_molecules.make_pool(MOL, seed + 1, 16)
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])
    assert any(not np.array_equal(x["node_feat"], z["node_feat"])
               for x, z in zip(a, c))


def test_generator_is_a_copy_of_the_programs():
    from repro.configs.gnn import DATASETS
    from repro.data import pipeline as P
    ds = DATASETS["qm9"]
    for i in range(24):
        g = P.make_graph(ds, i)
        m = bench_molecules.make_molecule(MOL, ds.seed, i)
        assert (m["num_nodes"], m["num_edges"]) == (g.num_nodes,
                                                    g.num_edges)
        np.testing.assert_array_equal(m["edge_index"], g.edge_index)
        np.testing.assert_array_equal(m["node_feat"], g.node_feat)
        np.testing.assert_array_equal(m["edge_feat"], g.edge_feat)


# -------------------------------------------------------------- flops --
TINY = {"conv": "gcn", "node_feat_dim": 2, "edge_feat_dim": 1,
        "hidden_dim": 3, "num_layers": 2, "output_dim": 2,
        "skip_connection": True, "global_pooling": ["add", "max"],
        "mlp_hidden_dim": 2, "mlp_hidden_layers": 1, "num_targets": 1,
        "pna_delta": 1.0}


def test_flops_hand_count_gcn():
    n, e = 3, 4
    # layer 0 (2 -> 3): aggregate at width 2 over 4 edges + 3 self
    # loops (2 FLOPs each), W 2*3*2*3 + bias 3*3, skip 2*3*2*3, add 3*3
    l0 = 2 * 7 * 2 + 36 + 9 + 36 + 9
    # layer 1 (3 -> 2): aggregate at width 2, W 2*3*3*2 + 3*2, skip, add
    l1 = 2 * 7 * 2 + 36 + 6 + 36 + 6
    pool = 2 * 3 * 2
    head = (2 * 4 * 2 + 2) + (2 * 2 * 1 + 1)
    assert bench_flops.graph_flops(TINY, n, e) == l0 + l1 + pool + head


def test_flops_hand_count_pna():
    m = dict(TINY, conv="pna")
    n, e = 3, 4
    # layer 0 (fi 2, fo 3): message map 4 edges x (2*2+1 -> 2) + bias,
    # reductions 6*e*fi, scalers 8*n*fi, post 3 rows x (13*2 -> 3) + bias
    l0 = (2 * 4 * 5 * 2 + 4 * 2) + 6 * 4 * 2 + 8 * 3 * 2 \
        + (2 * 3 * 26 * 3 + 3 * 3) + 2 * 3 * 2 * 3 + 3 * 3
    l1 = (2 * 4 * 7 * 3 + 4 * 3) + 6 * 4 * 3 + 8 * 3 * 3 \
        + (2 * 3 * 39 * 2 + 3 * 2) + 2 * 3 * 3 * 2 + 3 * 2
    pool = 2 * 3 * 2
    head = (2 * 4 * 2 + 2) + (2 * 2 * 1 + 1)
    assert bench_flops.graph_flops(m, n, e) == l0 + l1 + pool + head


def test_pna_costs_about_ten_gcns_per_molecule():
    g = bench_flops.graph_flops(CONFIGS["gcn-qm9"]["model"], 18, 34)
    p = bench_flops.graph_flops(CONFIGS["pna-qm9"]["model"], 18, 34)
    assert 5 < p / g < 20


# -------------------------------------------------------------- stats --
@pytest.mark.parametrize("values,q,want", [
    ([], 50, None), ([3.0], 99, 3.0), ([1, 2, 3, 4], 50, 2.0),
    ([1, 2, 3, 4], 75, 3.0), (list(range(1, 101)), 99, 99.0),
    (list(range(100, 0, -1)), 100, 100.0), ([5, 1, 4], 1, 1.0)])
def test_percentile_is_nearest_rank(values, q, want):
    from repro.runtime.scheduler import percentile
    assert bench_stats.percentile(values, q) == want
    assert percentile(values, q) == want


def test_spread_is_quartile_distance_over_median():
    assert bench_stats.spread([1.0, 1.0, 1.0, 1.0]) == 0.0
    v = [9.0, 10.0, 10.0, 11.0, 12.0, 10.0]
    assert bench_stats.spread(v) == pytest.approx(
        (11.25 - 9.75) / 10.0)


# --------------------------------------------------------- reference --
def _packed_program_outputs(config, params, mols):
    import jax
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    cfg = H.model_config(config)
    sv = config["serving"]
    graphs = [H.to_graph(m) for m in mols]
    batches, dropped = P.pack_dataset(graphs, sv["node_budget"],
                                      sv["edge_budget"], sv["batch_graphs"])
    assert not dropped
    fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    return np.concatenate([np.asarray(fn(params, G.packed_to_device(b)))
                           [np.asarray(b["graph_valid"])] for b in batches])


def _tiny(config):
    c = json.loads(json.dumps(config))
    c["model"].update(hidden_dim=8, output_dim=4, mlp_hidden_dim=4,
                      mlp_hidden_layers=1)
    c["serving"].update(batch_graphs=8, node_budget=224, edge_budget=440)
    return c


@pytest.mark.parametrize("width", ["paper", "tiny"])
@pytest.mark.parametrize("conv", ["gcn", "pna"])
def test_reference_matches_packed_program(conv, width):
    """At ``highest`` precision the plain reference and the packed
    program agree on QM9-like batches to float32 rounding."""
    import jax
    config = CONFIGS[f"{conv}-qm9"]
    if width == "tiny":
        config = _tiny(config)
    mols = bench_molecules.make_pool(config["molecules"], 123, 40)
    with jax.default_matmul_precision("highest"):
        params = H.make_weights(config["model"], 123)
        got = _packed_program_outputs(config, params, mols)
        ref = config["reference"]
        want = bench_reference.reference_outputs(
            params, config["model"], mols, node_pad=ref["node_pad"],
            edge_pad=ref["edge_pad"], block_graphs=16)
    assert got.shape == want.shape == (40, 1)
    scale = np.sqrt(np.mean(want.astype(np.float64) ** 2))
    assert np.max(np.abs(got - want)) / scale < 1e-5


def test_reference_weights_fit_the_programs_layout():
    import jax
    from repro.core import gnn_model as G
    from repro.nn import param as prm
    for config in CONFIGS.values():
        shapes = jax.tree_util.tree_map(
            lambda a: a.shape, H.make_weights(config["model"], 0))
        plan = jax.tree_util.tree_map(
            lambda a: a.shape,
            prm.abstract(G.model_plan(H.model_config(config))))
        assert shapes == plan


@pytest.mark.parametrize("conv", ["gcn", "pna"])
def test_reference_bf16x3_is_near_but_not_float32(conv):
    """The control's products lose the low-by-low term: off float32 by
    more than float32 rounding, by far less than one bfloat16 pass."""
    config = CONFIGS[f"{conv}-qm9"]
    mols = bench_molecules.make_pool(config["molecules"], 5, 32)
    params = H.make_weights(config["model"], 5)
    out = {p: bench_reference.reference_outputs(
        params, config["model"], mols, node_pad=64, edge_pad=128,
        block_graphs=32, precision=p).astype(np.float64)
        for p in bench_reference.PRECISIONS}
    scale = np.sqrt(np.mean(out["highest"] ** 2))
    e3 = np.max(np.abs(out["bf16x3"] - out["highest"])) / scale
    assert 3e-6 < e3 < 1e-3


# -------------------------------------------------------------- trace --
def _events():
    ev = [("/host:CPU", "python", "bench.window", 0.0, 1000.0),
          ("/host:CPU", "python", "bench.drain", 0.0, 450.0),
          ("/host:CPU", "python", "bench.wait", 450.0, 550.0)]
    d0 = "/device:TPU:0"
    d1 = "/device:TPU:1"
    for name, s, e in [("fusion.1", 100, 200), ("fusion.2", 150, 300),
                       ("copy", 500, 600), ("fusion.1", 1100, 1200)]:
        ev.append((d0, "XLA Ops", name, float(s), float(e - s)))
    ev.append((d0, "XLA Modules", "jit_fn", 100.0, 500.0))
    ev.append((d1, "XLA Ops", "fusion.1", -50.0, 250.0))
    return ev


def test_trace_reduction_busy_union_and_window():
    r = bench_trace.reduce_events(_events())
    assert r["window_s"] == pytest.approx(1000e-9)
    # device 0: [100, 300] and [500, 600] -> 300 ns; device 1: [0, 200]
    assert r["busy_s_per_device"] == pytest.approx([300e-9, 200e-9])
    assert r["busy_s"] == pytest.approx(250e-9)


def test_trace_reduction_names_idle_gaps_by_host_span():
    r = bench_trace.reduce_events(_events())
    # device 0 idles over [0,100], [300,500] and [600,1000]
    assert r["idle_gaps"] == [["bench.wait", pytest.approx(400e-9)],
                              ["bench.drain", pytest.approx(200e-9)],
                              ["bench.drain", pytest.approx(100e-9)]]


def test_trace_reduction_top_ops():
    r = bench_trace.reduce_events(_events())
    ops = dict(r["device_ops"])
    # fusion.1: 100 ns on device 0 plus 200 ns on device 1, over 2
    assert ops["fusion.1"] == pytest.approx(150e-9)
    assert ops["fusion.2"] == pytest.approx(75e-9)
    assert list(ops)[0] == "fusion.1"


def test_trace_reduction_reads_nothing_from_an_empty_trace():
    ev = _events()
    assert bench_trace.reduce_events(
        [e for e in ev if e[2] != "bench.window"]) is None
    assert bench_trace.reduce_events(
        [e for e in ev if not e[0].startswith("/device")]) is None


def test_readers_return_nothing_without_a_trace():
    cell = H.load_cell(CELLS[0])
    view = H.RunView(cell, {"launches": 0}, None, 0.0, {}, 1)
    for name in ("idle_share.screen", "step_device_ms.screen",
                 "step_mfu.screen", "idle_share.online"):
        assert H.load_reader(name)(view) is None


# ------------------------------------------------------------- no chip --
def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "benchmarks/cells/run.py", "--workload", CELLS[0],
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "correct" in json.loads(line):
                return True
        except (ValueError, TypeError):
            continue
    return False


def test_run_exits_nonzero_without_a_tpu():
    p = _run_cli(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "no TPU" in p.stderr


def test_run_exits_nonzero_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(HERE, tmp_path / "benchmarks" / "cells",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_cli(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
