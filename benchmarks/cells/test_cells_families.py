"""CPU tests of the family modules through which the harness builds,
feeds, checks and counts a model.

* The ``gnnbuilder`` family reproduces, bit for bit, the pools, weights
  and FLOP counts that the harness made before families existed
  (digests taken from that harness), and its reference rows equal a
  direct call of ``bench_reference``.
* A new architecture lands as new files only: a temporary root that
  holds a ``BENCHMARK.json``, one configuration, one traffic file and
  ``families/toy.py`` (a GraphSAGE model, which no cell of the
  benchmark runs) is loaded, served through the packed program and
  checked against its own reference, with no file of the harness
  edited.
"""
from __future__ import annotations

import hashlib
import json
import sys
import textwrap
import time
from pathlib import Path

import numpy as np
import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as H  # noqa: E402
import bench_reference  # noqa: E402
import bench_run  # noqa: E402

CONFIGS = {c["name"]: json.loads((ROOT / c["file"]).read_text())
           for c in H.load_benchmark()["configs"]}
GNNBUILDER_FILE = HERE / "families" / "gnnbuilder.py"
POOL_KEYS = ("node_feat", "edge_index", "edge_feat", "num_nodes",
             "num_edges", "y")

# SHA-256 of the first 32 graphs of a seed's pool, of the weights (host
# bytes) and of the FLOP counts of those graphs, as the harness made
# them before the family modules; the last entry is the FLOPs' sum
GOLDEN = {
    ("gcn-qm9", 7): (
        "6638b3acd5d5f20e8c6fc305d3baffa3d94d1c9edbd1af817f46f2dbec713644",
        "3d7be93b546f29872bb7c4c95303fbf90c073042db1cb53cfcd5d50b071ef520",
        "d2c8a1c1a9d584f0c890e8721e45d50bfbc2ef56e98eae94b25b8c87c7d1317e",
        23587082),
    ("gcn-qm9", 2 ** 31 + 17): (
        "c7fc4bb7b40567e71753c75300ab60ffa3a224f7dc4c195578facbc1b3894530",
        "af4f622ad295cd3436912f7706b05fc2244ad2e69650b345c0dbad37962d2174",
        "81d23b26d96820a6de58dff3c1f0124a810e7da2059259d59ac8311b7d94ca8f",
        23547656),
    ("pna-qm9", 7): (
        "6638b3acd5d5f20e8c6fc305d3baffa3d94d1c9edbd1af817f46f2dbec713644",
        "beb769d63077a082eb43ce68a1ec44f0e57a9dfa3821e87e6a327b0cf91ee374",
        "ab065540cce6381351e621d582d40107955a510d8dac12b4900ad4fb53c1678a",
        226746642),
    ("pna-qm9", 2 ** 31 + 17): (
        "c7fc4bb7b40567e71753c75300ab60ffa3a224f7dc4c195578facbc1b3894530",
        "8136ce10c9dd8b338ecf7515fb7b87c89d51693b38699cd6ca8ef02f297abf41",
        "4e06aeb5aea3369da7525941b6ad243d027614bc338eb6040f8aefd77334e6d9",
        226339944),
}


def _digest_arrays(arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.asarray(a)
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()


def _digest_tree(tree) -> str:
    import jax
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        h.update(jax.tree_util.keystr(path).encode())
        a = np.asarray(jax.device_get(leaf))
        h.update(f"{a.dtype.str}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


# ---------------------------------------------------------- gnnbuilder --
@pytest.mark.parametrize("name,seed", sorted(GOLDEN))
def test_gnnbuilder_family_reproduces_the_golden_digests(name, seed):
    config = CONFIGS[name]
    family = H.family_of(config)
    pool = family.make_pool(config, seed, 32)
    weights = H.make_weights(family, config["model"], seed)
    flops = [family.graph_flops(config["model"], m["num_nodes"],
                                m["num_edges"]) for m in pool]
    got = (_digest_arrays([m[k] for m in pool for k in POOL_KEYS]),
           _digest_tree(weights),
           hashlib.sha256(json.dumps(flops).encode()).hexdigest(),
           sum(flops))
    assert got == GOLDEN[(name, seed)]
    assert H.model_config(config) == family.program_config(config)


@pytest.mark.parametrize("name", sorted({n for n, _ in GOLDEN}))
def test_reference_through_the_family_is_bench_reference(name):
    """``reference_for`` goes through the family; its rows equal a
    direct ``bench_reference`` call on weights made as before, bit for
    bit."""
    import jax
    config = CONFIGS[name]
    family = H.family_of(config)
    seed = 2 ** 31 + 17
    pool = family.make_pool(config, seed, 32)
    idx = np.arange(len(pool))[::-1]
    got = H.reference_for(family, config, seed, pool, idx)
    params = jax.jit(lambda k: bench_reference.init_params(
        config["model"], k))(H.weight_key(seed))
    ref = config["reference"]
    want = bench_reference.reference_outputs(
        params, config["model"], pool, node_pad=ref["node_pad"],
        edge_pad=ref["edge_pad"], block_graphs=ref["block_graphs"])
    assert sorted(got) == list(range(len(pool)))
    np.testing.assert_array_equal(np.stack([got[i] for i in sorted(got)]),
                                  want)


def test_a_configuration_without_a_family_key_is_gnnbuilder():
    for name in sorted({n for n, _ in GOLDEN}):
        config = dict(CONFIGS[name])
        config.pop("family", None)
        assert Path(H.family_of(config).__file__) == GNNBUILDER_FILE
        config["family"] = "gnnbuilder"
        assert Path(H.family_of(config).__file__) == GNNBUILDER_FILE


# ---------------------------------------------------------- new family --
TOY_FAMILY = textwrap.dedent('''
    """Family ``toy``: a small GraphSAGE stack on random small graphs."""
    import jax
    import numpy as np


    def _widths(model):
        out = []
        for i in range(model["num_layers"]):
            fi = model["node_feat_dim"] if i == 0 else model["hidden_dim"]
            fo = model["output_dim"] if i == model["num_layers"] - 1 \\
                else model["hidden_dim"]
            out.append((fi, fo))
        return out


    def _head(model):
        dims = [3 * model["output_dim"], model["mlp_hidden_dim"],
                model["num_targets"]]
        return list(zip(dims[:-1], dims[1:]))


    def program_config(config):
        from repro.core.gnn_model import GNNModelConfig, MLPConfig
        m = config["model"]
        return GNNModelConfig(
            graph_input_feature_dim=m["node_feat_dim"],
            graph_input_edge_dim=m["edge_feat_dim"],
            gnn_hidden_dim=m["hidden_dim"], gnn_num_layers=m["num_layers"],
            gnn_output_dim=m["output_dim"], gnn_conv="sage",
            global_pooling=("add", "mean", "max"),
            mlp_head=MLPConfig(in_dim=3 * m["output_dim"],
                               out_dim=m["num_targets"],
                               hidden_dim=m["mlp_hidden_dim"],
                               hidden_layers=1))


    def init_params(model, key):
        tree = {"convs": {}, "mlp": {}}
        shapes = []
        for i, (fi, fo) in enumerate(_widths(model)):
            shapes += [(("convs", f"c{i}", "w_self", "w"), (fi, fo)),
                       (("convs", f"c{i}", "w_self", "b"), (fo,)),
                       (("convs", f"c{i}", "w_neigh", "w"), (fi, fo))]
            if fi != fo:
                shapes.append(((f"skip{i}", "w"), (fi, fo)))
        for j, (a, b) in enumerate(_head(model)):
            shapes += [(("mlp", f"l{j}", "w"), (a, b)),
                       (("mlp", f"l{j}", "b"), (b,))]
        for n, (path, shape) in enumerate(shapes):
            node = tree
            for k in path[:-1]:
                node = node.setdefault(k, {})
            z = jax.random.normal(jax.random.fold_in(key, n), shape)
            node[path[-1]] = z / np.sqrt(shape[0])
        return tree


    def make_pool(config, seed, size):
        g = config["graphs"]
        pool = []
        for idx in range(size):
            rng = np.random.default_rng(np.random.SeedSequence([seed, idx]))
            n = int(rng.integers(3, g["max_nodes"] + 1))
            parents = [int(rng.integers(0, i)) for i in range(1, n)]
            src = list(range(1, n)) + parents
            dst = parents + list(range(1, n))
            e = len(src)
            edge_index = np.full((g["max_edges"], 2), -1, np.int32)
            edge_index[:e] = np.stack([src, dst], 1)
            node_feat = np.zeros((g["max_nodes"], g["node_feat_dim"]),
                                 np.float32)
            node_feat[:n] = rng.standard_normal((n, g["node_feat_dim"]))
            edge_feat = np.zeros((g["max_edges"], g["edge_feat_dim"]),
                                 np.float32)
            pool.append({"node_feat": node_feat, "edge_index": edge_index,
                         "edge_feat": edge_feat, "num_nodes": n,
                         "num_edges": e,
                         "y": np.zeros((g["num_targets"],), np.float32)})
        return pool


    def reference_outputs(params, config, graphs, *, precision, **reference):
        if precision != "highest":
            raise ValueError(f"no {precision!r} control for this family")
        model = config["model"]
        p = jax.device_get(params)
        rows = []
        for m in graphs:
            n, e = m["num_nodes"], m["num_edges"]
            x = m["node_feat"][:n]
            src, dst = m["edge_index"][:e, 0], m["edge_index"][:e, 1]
            for i, (fi, fo) in enumerate(_widths(model)):
                c = p["convs"][f"c{i}"]
                agg = np.zeros_like(x)
                np.add.at(agg, dst, x[src])
                deg = np.maximum(np.bincount(dst, minlength=n), 1)
                h = x @ c["w_self"]["w"] + c["w_self"]["b"] \\
                    + (agg / deg[:, None]) @ c["w_neigh"]["w"]
                h = h + (x @ p[f"skip{i}"]["w"] if fi != fo else x)
                x = np.maximum(h, 0.0)
            h = np.concatenate([x.sum(0), x.mean(0), x.max(0)])
            for j in range(len(_head(model))):
                h = h @ p["mlp"][f"l{j}"]["w"] + p["mlp"][f"l{j}"]["b"]
                if j < len(_head(model)) - 1:
                    h = np.maximum(h, 0.0)
            rows.append(h.astype(np.float32))
        return np.stack(rows)


    def graph_flops(model, num_nodes, num_edges):
        total = 0
        for fi, fo in _widths(model):
            # mean of the in-neighbours, then the two maps and the bias
            total += num_edges * fi + num_nodes * fi
            total += 4 * num_nodes * fi * fo + num_nodes * fo
            total += (2 * num_nodes * fi * fo if fi != fo else 0)
            total += num_nodes * fo
        total += 3 * num_nodes * _widths(model)[-1][1]
        return total + sum(2 * a * b + b for a, b in _head(model))
''')

TOY_CONFIG = {
    "name": "toy-sage", "family": "toy",
    "model": {"node_feat_dim": 3, "edge_feat_dim": 2, "hidden_dim": 8,
              "num_layers": 2, "output_dim": 4, "mlp_hidden_dim": 4,
              "num_targets": 2},
    "precision": {"program": "fp32", "matmul_precision": "highest"},
    "graphs": {"max_nodes": 8, "max_edges": 14, "node_feat_dim": 3,
               "edge_feat_dim": 2, "num_targets": 2},
    "serving": {"batch_graphs": 8, "node_budget": 96, "edge_budget": 160,
                "deadline_ms": 50, "queue_depth": 256, "max_retries": 2},
    "reference": {},
    "check": {"max_err": 1e-5, "lost": 0, "unserved": 0},
}
TOY_TRAFFIC = {"mode": "screen", "pool_graphs": 40, "warmup_s": 0.0,
               "chunk_graphs": 40, "shards": 1}


@pytest.fixture
def toy_root(tmp_path):
    """A tree that holds the benchmark's definition of one new cell and
    nothing of the harness."""
    cells = tmp_path / "benchmarks" / "cells"
    for d in ("configs", "traffic", "families"):
        (cells / d).mkdir(parents=True)
    (cells / "families" / "toy.py").write_text(TOY_FAMILY)
    (cells / "configs" / "toy-sage.json").write_text(json.dumps(TOY_CONFIG))
    (cells / "traffic" / "toy-screen.json").write_text(
        json.dumps(TOY_TRAFFIC))
    bench = {
        "configs": [{"name": "toy-sage", "source": "arXiv:1706.02216",
                     "file": "benchmarks/cells/configs/toy-sage.json",
                     "reduced": [], "why": "a conv no cell runs"}],
        "workloads": [{"name": "toy-sage.screen", "config": "toy-sage",
                       "traffic": "toy-screen", "chips": 1,
                       "why": "a new architecture from new files"}],
        "end_to_end": [
            {"name": "graphs_per_s", "unit": "graphs/s", "better": "higher",
             "bound": 0.1, "source": "host_clock"},
            {"name": "setup_s", "unit": "s", "better": "lower",
             "bound": 0.25, "source": "host_clock"}],
        "per_layer": []}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp_path


def test_new_family_from_new_files_alone(toy_root):
    """load_cell, weights, the family's pool, the packed program's wave
    drain and the family's reference, all from the temporary tree: the
    drain's rows agree with the reference to 1e-5 of its RMS."""
    import jax
    assert not (HERE / "families" / "toy.py").exists()
    cell = H.load_cell("toy-sage.screen", root=toy_root)
    assert Path(cell.family.__file__).is_relative_to(toy_root)
    assert cell.traffic == TOY_TRAFFIC
    config = cell.config
    seed = 2 ** 31 + 5
    with jax.default_matmul_precision("highest"):
        params = H.make_weights(cell.family, config["model"], seed)
        mols = cell.family.make_pool(config, seed,
                                     cell.traffic["pool_graphs"])
        sut = H.Sut(cell.family, config, params)
        idx = np.arange(len(mols))
        raw = sut.drain([H.to_graph(m) for m in mols], idx)
        H.free_device_state(sut)
    ans = H.screen_answers({"calls": [raw], "window_s": 1.0})
    assert ans["served"] == len(mols) and ans["lost"] == 0
    ref = H.reference_for(cell.family, config, seed, mols, ans["idx"])
    check = H.compare(ans, ref, config["check"])
    assert H.passes(check), check
    assert check["max_err"]["value"] > 0.0      # two paths, not one
    want = np.stack([ref[int(i)] for i in ans["idx"]])
    scale = np.sqrt(np.mean(want.astype(np.float64) ** 2))
    assert np.max(np.abs(ans["rows"] - want)) / scale < 1e-5
    assert H.served_flops(cell.family, config["model"], mols, idx) > 0


def test_new_family_cell_runs_end_to_end(toy_root, monkeypatch):
    """``bench_run.run_cell`` drives the new cell (set-up, window,
    check) with the chip look skipped: correct, every request served."""
    import jax
    saved = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(
        bench_run, "configure_jax",
        lambda config: jax.config.update(
            "jax_default_matmul_precision",
            config["precision"]["matmul_precision"]))
    try:
        cell = H.load_cell("toy-sage.screen", root=toy_root)
        res = bench_run.run_cell(cell, 2 ** 31 + 6, 0.3, False,
                                 require_chip=False,
                                 t_start=time.perf_counter())
    finally:
        jax.config.update("jax_default_matmul_precision", saved)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"graphs_per_s", "setup_s"}
