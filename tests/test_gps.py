"""GraphGPS on the packed path, on the CPU at a small size: hidden 16, 2
GPS layers, 2 heads, 8 graphs of 1 to 40 nodes in a 256-row batch (two
query tiles of the attention, graphs across the boundary), one graph of
fewer nodes than eigenpairs.

The reference is the benchmark's own (``families/graphgps.py``, loaded
by its path), so there is one reference and not two."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import convs as Cv
from repro.core import gnn_model as G
from repro.data import pipeline as P
from repro.kernels.flash_attention import kernel as K
from repro.kernels.flash_attention import ops as AO
from repro.kernels.flash_attention import ref as AR
from repro.launch import serve
from repro.nn import param as prm
from repro.runtime import trace

CELLS = Path(__file__).resolve().parents[1] / "benchmarks" / "cells"


@pytest.fixture(scope="module")
def family():
    if str(CELLS) not in sys.path:
        sys.path.insert(0, str(CELLS))
    spec = importlib.util.spec_from_file_location(
        "bench_family_graphgps_test", CELLS / "families" / "graphgps.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


MODEL = {"local_conv": "gatedgcn", "hidden_dim": 16, "num_layers": 2,
         "num_heads": 2, "ffn_dim": 32, "bn_eps": 1e-5,
         "atom_vocab": [119, 5, 12, 12, 10, 6, 6, 2, 2],
         "bond_vocab": [5, 6, 2], "pe_eigvecs": 10, "pe_hidden_dim": 8,
         "pe_dim": 4, "pooling": "mean", "head_dims": [8, 4],
         "num_targets": 3,
         "bn_calibration": {"graphs": 8, "seed": 0, "target_nodes": 30.0,
                            "size_sigma": 0.5, "min_nodes": 9,
                            "max_nodes": 40, "cyclic_share": 0.3}}
CONFIG = {"model": MODEL,
          "precision": {"program": "fp32", "matmul_precision": "highest"},
          "graphs": {"target_nodes": 30.0, "size_sigma": 0.5,
                     "min_nodes": 9, "max_nodes": 40, "cyclic_share": 0.3,
                     "num_targets": 3}}
REFERENCE = {"node_pad": 40, "edge_pad": 128, "block_graphs": 4}
NODE_BUDGET, EDGE_BUDGET, MAX_GRAPHS = 256, 512, 8
SEED = 2 ** 31 + 16


def _tiny(family, n: int, seed: int) -> dict:
    """A path of ``n`` nodes with the features of the peptide pool."""
    rng = np.random.default_rng(seed)
    a, b = np.arange(n - 1), np.arange(1, n)
    k = MODEL["pe_eigvecs"]
    adj = np.zeros((n, n))
    adj[a, b] = adj[b, a] = 1.0
    vals, vecs = np.linalg.eigh(np.diag(adj.sum(1)) - adj)
    m = min(k, n)
    pe = np.zeros((n, 3 * k))
    pe[:, :m], pe[:, k:k + m], pe[:, 2 * k:2 * k + m] = \
        vecs[:, :m], vals[None, :m], 1.0
    atoms = np.stack([rng.integers(0, v, n) for v in MODEL["atom_vocab"]],
                     axis=1)
    e = 2 * (n - 1)
    bonds = np.stack([rng.integers(0, v, e) for v in MODEL["bond_vocab"]],
                     axis=1)
    return {"node_feat": np.concatenate([atoms, pe], 1).astype(np.float32),
            "edge_index": np.stack([np.concatenate([a, b]),
                                    np.concatenate([b, a])],
                                   1).astype(np.int32).reshape(e, 2),
            "edge_feat": bonds.astype(np.float32), "num_nodes": n,
            "num_edges": e, "y": np.zeros(3, np.float32)}


@pytest.fixture(scope="module")
def graphs(family):
    """Six peptides of 9-40 nodes and two paths of 1 and 5 nodes, 8
    graphs that straddle row 128."""
    pool = family.make_pool(CONFIG, SEED, 6)
    pool[2:2] = [_tiny(family, 1, 1), _tiny(family, 5, 2)]
    assert min(g["num_nodes"] for g in pool) < MODEL["pe_eigvecs"]
    return pool


def _graph(m: dict) -> P.Graph:
    return P.Graph(m["node_feat"], m["edge_index"], m["edge_feat"],
                   m["num_nodes"], m["num_edges"], m["y"])


@pytest.fixture(scope="module")
def program(family):
    cfg = family.program_config(CONFIG)
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: family.init_params(MODEL, k))(
            jax.random.key(7))
    fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    return cfg, params, fn


def _packed(graphs):
    batch, k = P.pack_graphs([_graph(m) for m in graphs], NODE_BUDGET,
                             EDGE_BUDGET, MAX_GRAPHS)
    assert k == len(graphs)
    return batch


def test_apply_packed_matches_the_reference(family, graphs, program):
    cfg, params, fn = program
    batch = _packed(graphs)
    starts = np.cumsum([0] + [g["num_nodes"] for g in graphs])
    assert any(s < 128 < s + g["num_nodes"]
               for s, g in zip(starts, graphs))       # straddles a tile
    with jax.default_matmul_precision("highest"):
        got = np.asarray(fn(params, G.packed_to_device(batch)))
        want = family.reference_outputs(params, CONFIG, graphs,
                                        precision="highest", **REFERENCE)
    scale = np.sqrt(np.mean(want.astype(np.float64) ** 2))
    assert np.max(np.abs(got - want)) / scale < 2e-5


def test_padded_apply_matches_packed(graphs, program):
    """``apply``, the per-graph padded oracle, runs a GPS model too."""
    cfg, params, fn = program
    got = np.asarray(fn(params, G.packed_to_device(_packed(graphs))))
    one = jax.jit(lambda p, el: G.apply(p, cfg, el))
    for i, m in enumerate(graphs):
        n, e = m["num_nodes"], m["num_edges"]
        el = {"node_feat": np.pad(m["node_feat"], ((0, 40 - n), (0, 0))),
              "edge_index": np.pad(m["edge_index"], ((0, 128 - e), (0, 0)),
                                   constant_values=-1),
              "edge_feat": np.pad(m["edge_feat"], ((0, 128 - e), (0, 0))),
              "num_nodes": np.int32(n)}
        np.testing.assert_allclose(np.asarray(one(params, el)), got[i],
                                   atol=1e-5, rtol=1e-5)


def test_no_attention_leaks_across_graphs(graphs, program):
    """New features for one graph leave every other graph's output row
    bit for bit as it was."""
    cfg, params, fn = program
    base = np.asarray(fn(params, G.packed_to_device(_packed(graphs))))
    changed = [dict(m) for m in graphs]
    nf = changed[3]["node_feat"].copy()
    nf[:, 9:19] *= -1.0                       # the eigenvectors' signs
    nf[:, 0] = (nf[:, 0] + 1) % 119
    changed[3]["node_feat"] = nf
    got = np.asarray(fn(params, G.packed_to_device(_packed(changed))))
    others = [i for i in range(len(graphs)) if i != 3]
    np.testing.assert_array_equal(got[others], base[others])
    assert not np.array_equal(got[3], base[3])


def test_small_graph_is_served_not_rejected(graphs, program):
    """The one-node and five-node graphs (fewer nodes than eigenpairs)
    pass admission and get finite answers from the wave drain."""
    cfg, params, fn = program
    outs, stats = serve.drain_gnn_queue(
        fn, params, [_graph(m) for m in graphs], NODE_BUDGET, EDGE_BUDGET,
        MAX_GRAPHS)
    assert [o["status"] for o in stats["outcomes"]] \
        == ["served_packed"] * len(graphs)
    assert np.isfinite(np.asarray(outs[0])[:len(graphs)]).all()


def _attention_inputs(sizes, rows, heads=2, dh=8, seed=0):
    rng = np.random.default_rng(seed)
    gid = np.full(rows, len(sizes), np.int32)
    gid[:sum(sizes)] = np.repeat(np.arange(len(sizes)), sizes)
    q, k, v = (jnp.asarray(rng.standard_normal((rows, heads * dh)),
                           jnp.float32) for _ in range(3))
    return q, k, v, jnp.asarray(gid)


SIZES = [5, 40, 3, 130, 1, 60, 7, 0]


@pytest.mark.parametrize("sizes,rows", [
    (SIZES, 384),
    ([5, 600, 7, 0], 640),            # one graph over four key blocks
])
def test_kernel_matches_the_xla_form_and_the_dense_oracle(sizes, rows):
    heads = 2
    q, k, v, gid = _attention_inputs(sizes, rows, heads)
    xla = AO.segment_attention(q, k, v, gid, jnp.asarray(sizes, jnp.int32),
                               num_heads=heads, use_kernel=False)
    first, count = AO.segment_tiles(sizes, rows, 128)
    kern = K.segment_attention_pallas(
        q, k, v, gid, jnp.asarray(first), jnp.asarray(count),
        num_heads=heads, num_graphs=len(sizes), interpret=True)
    dense = AR.segment_attention_ref(q, k, v, gid, heads, len(sizes))
    np.testing.assert_allclose(np.asarray(kern), np.asarray(xla),
                               atol=2e-6)
    np.testing.assert_allclose(np.asarray(xla), np.asarray(dense),
                               atol=2e-6)
    assert not np.asarray(kern)[sum(sizes):].any()     # padding rows: 0


def test_large_graph_is_served_whole_beside_small_ones(family, graphs,
                                                       program):
    """A ~500-node graph packed after the eight small ones: every graph
    is served, the large one as the reference answers it, and the small
    ones' rows bit for bit as they are without it."""
    cfg, params, fn = program
    big = family.make_pool(dict(CONFIG, graphs=dict(
        CONFIG["graphs"], target_nodes=520.0, size_sigma=0.0,
        min_nodes=0, max_nodes=600)), SEED, 1)[0]
    assert 480 < big["num_nodes"] <= 522
    budgets = (1024, 2048, len(graphs) + 1)
    with_big, stats = serve.drain_gnn_queue(
        fn, params, [_graph(m) for m in graphs + [big]], *budgets)
    assert [o["status"] for o in stats["outcomes"]] \
        == ["served_packed"] * (len(graphs) + 1)
    alone, _ = serve.drain_gnn_queue(
        fn, params, [_graph(m) for m in graphs], *budgets)
    got, base = np.asarray(with_big[0]), np.asarray(alone[0])
    np.testing.assert_array_equal(got[:len(graphs)], base[:len(graphs)])
    with jax.default_matmul_precision("highest"):
        want = family.reference_outputs(
            params, CONFIG, [big], precision="highest",
            node_pad=big["num_nodes"], edge_pad=big["num_edges"],
            block_graphs=1)
    scale = np.sqrt(np.mean(want.astype(np.float64) ** 2))
    assert np.max(np.abs(got[len(graphs)] - want[0])) / scale < 2e-5


def test_tile_table_and_counters_against_hand_counts(tmp_path):
    """Rows 0-127 hold graphs 0-3 (graph 3 spans rows 48-177), rows
    128-245 graphs 3-6: both tiles visit key blocks 0 and 1; the third
    tile is padding and visits none. ``pack_graphs`` counts each batch
    it packs, and only under the profiler."""
    first, count = AO.segment_tiles(SIZES, 384, 128)
    assert first.tolist() == [0, 0, 0] and count.tolist() == [2, 2, 0]
    jf, jc = jax.jit(lambda s: AO.segment_tiles(s, 384, 128, jnp))(
        jnp.asarray(SIZES))
    assert np.asarray(jf).tolist() == [0, 0, 0]
    assert np.asarray(jc).tolist() == [2, 2, 0]
    paths = [_graph(_path(n)) for n in SIZES[:-1]]
    trace.reset()
    P.pack_graphs(paths, 384, 1024, 8)           # profiler off: nothing
    assert trace.snapshot()["counters"] == {}
    with jax.profiler.trace(str(tmp_path)):
        P.pack_graphs(paths, 384, 1024, 8)
        P.pack_graphs(paths, 300, 1024, 8)        # rows 300, tiles 3
    snap = trace.snapshot()
    counters = snap["counters"]
    trace.reset()
    assert snap["spans"]["pack.attn_counts"]["n"] == 2
    assert counters == {"attn.rows": 2 * 246,
                        "attn.pairs": 2 * (25 + 1600 + 9 + 16900 + 1
                                           + 3600 + 49),
                        "attn.pairs_computed": 2 * 4 * 128 * 128}


def _path(n: int) -> dict:
    """A path of ``n`` nodes with one feature column."""
    a = np.arange(max(n - 1, 0))
    return {"node_feat": np.ones((n, 1), np.float32),
            "edge_index": np.stack([a, a + 1], 1).astype(np.int32),
            "edge_feat": np.ones((len(a), 1), np.float32), "num_nodes": n,
            "num_edges": len(a), "y": np.zeros(1, np.float32)}


def test_gatedgcn_edge_state_follows_the_equations():
    rng = np.random.default_rng(3)
    n, f, fe = 6, 4, 3
    src = np.array([0, 1, 2, 3, 4, 5, 1, -1])
    dst = np.array([1, 0, 1, 1, 5, 4, 2, -1])
    cc = Cv.ConvConfig(in_dim=f, out_dim=f, edge_dim=fe, conv="gatedgcn")
    params = jax.tree_util.tree_map(
        lambda s: jnp.asarray(rng.standard_normal(s.shape), jnp.float32),
        Cv.conv_plan(cc), is_leaf=lambda s: hasattr(s, "shape"))
    x = rng.standard_normal((n, f)).astype(np.float32)
    e = rng.standard_normal((len(src), fe)).astype(np.float32)
    g = {"edge_index": jnp.asarray(np.stack([src, dst], 1)),
         "edge_feat": jnp.asarray(e), "valid_e": jnp.asarray(src >= 0)}
    with jax.default_matmul_precision("highest"):
        h, e_hat = Cv.conv_apply(params, g, jnp.asarray(x), cc)
    p = jax.tree_util.tree_map(np.asarray, params)
    lin = lambda q, a: a @ q["w"] + q["b"]                  # noqa: E731
    want_e = lin(p["D"], x)[np.maximum(dst, 0)] \
        + lin(p["E"], x)[np.maximum(src, 0)] + lin(p["C"], e)
    np.testing.assert_allclose(np.asarray(e_hat)[:-1], want_e[:-1],
                               atol=1e-5)
    gate = 1.0 / (1.0 + np.exp(-want_e))
    num, den = np.zeros((n, f)), np.zeros((n, f))
    for j in range(len(src) - 1):                     # the padding edge
        num[dst[j]] += gate[j] * lin(p["B"], x)[src[j]]
        den[dst[j]] += gate[j]
    want_h = lin(p["A"], x) + num / (den + 1e-6)
    np.testing.assert_allclose(np.asarray(h), want_h, atol=1e-5)
    assert Cv.conv_spec("gatedgcn").edge_state
    assert not Cv.conv_spec("gatedgcn").dse


def test_conv_stack_refuses_an_edge_state_conv(graphs):
    """GatedGCN's edge state has an update only inside a GPS block: a
    plain conv stack of it is refused, and the conv-stack grids leave
    it out."""
    cfg = G.GNNModelConfig(
        graph_input_feature_dim=39, graph_input_edge_dim=3,
        gnn_hidden_dim=16, gnn_num_layers=2, gnn_output_dim=16,
        gnn_conv="gatedgcn",
        mlp_head=G.MLPConfig(in_dim=48, out_dim=3, hidden_dim=8))
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    with pytest.raises(NotImplementedError, match="edge state"):
        G.apply_packed(params, cfg, G.packed_to_device(_packed(graphs)))
    assert "gatedgcn" in Cv.CONV_TYPES
    assert "gatedgcn" not in Cv.STACK_CONVS


def test_gps_int8_policy_is_refused(graphs, program):
    cfg, params, _ = program
    pol = G.resolve_policy(cfg, "int8")
    batch = G.packed_to_device(_packed(graphs))
    with pytest.raises(NotImplementedError, match="int8"):
        G.apply_packed(params, cfg, batch, None, pol)


def test_gps_bf16_policy_runs_near_fp32(graphs, program):
    cfg, params, fn = program
    batch = G.packed_to_device(_packed(graphs))
    ref = np.asarray(fn(params, batch))
    pol = G.resolve_policy(cfg, "bf16")
    got = np.asarray(jax.jit(lambda p, b: G.apply_packed(
        p, cfg, b, None, pol))(params, batch))
    scale = np.sqrt(np.mean(ref.astype(np.float64) ** 2))
    err = np.max(np.abs(got - ref)) / scale
    assert 0.0 < err < 0.1


def test_family_weights_follow_the_program_plan(family, program):
    cfg, params, _ = program
    plan = prm.abstract(G.model_plan(cfg))
    assert jax.tree_util.tree_structure(plan) \
        == jax.tree_util.tree_structure(params)
    assert all(a.shape == b.shape for a, b in zip(
        jax.tree_util.tree_leaves(plan), jax.tree_util.tree_leaves(params)))
