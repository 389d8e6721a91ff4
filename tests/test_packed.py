"""Packed GraphBatch IR: packed-vs-padded equivalence for every conv type
and aggregation (including isolated nodes and empty-edge graphs), packing
invariants, budget overflow handling, and deterministic bucketing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregations as A
from repro.core import gnn_model as G
from repro.core.convs import STACK_CONVS
from repro.core.pooling import POOLINGS, global_pool, segment_global_pool
from repro.data import pipeline as P
from repro.nn import param as prm
from repro.runtime import trace

DS = P.GraphDataConfig(avg_nodes=10, max_nodes=64, max_edges=64,
                       node_feat_dim=11, edge_feat_dim=4, seed=5)


def _cfg(conv, task="graph"):
    return G.GNNModelConfig(
        graph_input_feature_dim=11, graph_input_edge_dim=4,
        gnn_hidden_dim=16, gnn_num_layers=2, gnn_output_dim=8,
        gnn_conv=conv, task=task,
        mlp_head=G.MLPConfig(in_dim=24, out_dim=1, hidden_dim=8,
                             hidden_layers=1) if task == "graph" else None)


def _empty_edge_graph(n=3):
    """A graph whose nodes are all isolated (num_edges == 0)."""
    nf = np.zeros((DS.max_nodes, DS.node_feat_dim), np.float32)
    nf[:n] = np.random.default_rng(7).standard_normal(
        (n, DS.node_feat_dim))
    return P.Graph(node_feat=nf,
                   edge_index=np.full((DS.max_edges, 2), -1, np.int32),
                   edge_feat=np.zeros((DS.max_edges, DS.edge_feat_dim),
                                      np.float32),
                   num_nodes=n, num_edges=0,
                   y=np.zeros((1,), np.float32))


def _graphs():
    gs = [P.make_graph(DS, i) for i in range(5)]
    gs.insert(2, _empty_edge_graph())        # isolated nodes, zero edges
    return gs


def _el(g):
    return {"node_feat": jnp.asarray(g.node_feat),
            "edge_index": jnp.asarray(g.edge_index),
            "edge_feat": jnp.asarray(g.edge_feat),
            "num_nodes": jnp.int32(g.num_nodes)}


def _pack(graphs, max_graphs=8):
    batch, k = P.pack_graphs(graphs, 128, 256, max_graphs)
    assert k == len(graphs)
    return {kk: jnp.asarray(v) for kk, v in batch.items() if kk != "y"}


# -------------------------------------------------- model equivalence ---
@pytest.mark.parametrize("conv", STACK_CONVS)
def test_apply_packed_matches_apply(conv):
    """One jitted packed program == the per-graph padded oracle, for every
    conv type, including an empty-edge graph mid-batch."""
    cfg = _cfg(conv)
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    graphs = _graphs()
    jb = _pack(graphs)
    packed_fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    loop_fn = jax.jit(lambda p, el: G.apply(p, cfg, el))
    out = np.asarray(packed_fn(params, jb))
    for i, g in enumerate(graphs):
        ref = np.asarray(loop_fn(params, _el(g)))
        assert float(np.mean(np.abs(out[i] - ref))) < 1e-4, (conv, i)


@pytest.mark.parametrize("conv", STACK_CONVS)
def test_apply_packed_node_task(conv):
    cfg = _cfg(conv, task="node")
    params = prm.materialize(G.model_plan(cfg), jax.random.key(1))
    graphs = _graphs()
    jb = _pack(graphs)
    packed_fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    loop_fn = jax.jit(lambda p, el: G.apply(p, cfg, el))
    out = np.asarray(packed_fn(params, jb))
    off = 0
    for g in graphs:
        ref = np.asarray(loop_fn(params, _el(g)))[:g.num_nodes]
        got = out[off:off + g.num_nodes]
        assert float(np.mean(np.abs(got - ref))) < 1e-4
        off += g.num_nodes


def test_mse_loss_packed_matches_per_graph():
    cfg = _cfg("gcn")
    params = prm.materialize(G.model_plan(cfg), jax.random.key(2))
    graphs = _graphs()
    batch, k = P.pack_graphs(graphs, 128, 256, 8)
    jb = {kk: jnp.asarray(v) for kk, v in batch.items()}
    loss = float(G.mse_loss_packed(params, cfg, jb))
    per = [float(jnp.mean(jnp.square(
        G.apply(params, cfg, _el(g)) - jnp.asarray(g.y))))
        for g in graphs]
    np.testing.assert_allclose(loss, np.mean(per), rtol=1e-4)


# --------------------------------------------- aggregation equivalence --
@pytest.mark.parametrize("agg", A.AGGREGATIONS)
def test_packed_segment_aggregate_matches_per_graph(agg):
    """Segment aggregation over the packed edge buffer == per-graph
    aggregation, for all six aggregations."""
    graphs = _graphs()
    batch, _ = P.pack_graphs(graphs, 128, 256, 8)
    rng = np.random.default_rng(0)
    msgs = rng.standard_normal((256, 3)).astype(np.float32)
    dst = batch["edge_index"][:, 1]
    valid = batch["edge_index"][:, 0] >= 0
    out = np.asarray(A.segment_aggregate(
        agg, jnp.asarray(msgs), jnp.asarray(np.maximum(dst, 0)), 128,
        jnp.asarray(valid)))
    off_n = off_e = 0
    for g in graphs:
        for v in range(g.num_nodes):
            sel = (batch["edge_index"][:, 1] == off_n + v) & valid
            if not sel.any():
                np.testing.assert_allclose(out[off_n + v], 0.0, atol=1e-6)
                continue
            want = np.asarray(A.aggregate_stream(
                agg, jnp.asarray(msgs[sel])))
            np.testing.assert_allclose(out[off_n + v], want, rtol=1e-3,
                                       atol=1e-3)
        off_n += g.num_nodes
        off_e += g.num_edges


@pytest.mark.parametrize("kind", POOLINGS)
def test_segment_pooling_matches_dense(kind):
    graphs = _graphs()
    batch, _ = P.pack_graphs(graphs, 128, 256, 8)
    rng = np.random.default_rng(1)
    x = rng.standard_normal((128, 6)).astype(np.float32)
    gid = jnp.asarray(batch["node_graph_id"])
    got = np.asarray(segment_global_pool(kind, jnp.asarray(x), gid, 8))
    off = 0
    for i, g in enumerate(graphs):
        xg = x[off:off + g.num_nodes]
        mask = jnp.ones((g.num_nodes,), bool)
        want = np.asarray(global_pool(kind, jnp.asarray(xg), mask))
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-5)
        off += g.num_nodes
    # padding rows (beyond the packed graphs) pool to zero
    np.testing.assert_allclose(got[len(graphs):], 0.0, atol=1e-6)


def test_segment_counts_match_graph_num_nodes():
    """segment_counts over the packed node/edge ids reproduces the
    per-graph counts recorded at pack time (padding -> overflow bucket)."""
    graphs = _graphs()
    batch, k = P.pack_graphs(graphs, 128, 256, 8)
    node_counts = np.asarray(A.segment_counts(
        jnp.asarray(batch["node_graph_id"]), 8))
    assert node_counts.dtype == np.float32
    np.testing.assert_array_equal(node_counts,
                                  batch["graph_num_nodes"].astype(np.float32))
    edge_counts = np.asarray(A.segment_counts(
        jnp.asarray(batch["edge_graph_id"]), 8))
    np.testing.assert_array_equal(
        edge_counts, np.float32([g.num_edges for g in graphs] + [0, 0]))
    # explicit valid mask routes masked slots into the dropped bucket
    masked = np.asarray(A.segment_counts(
        jnp.asarray(batch["node_graph_id"]), 8,
        valid=jnp.asarray(batch["node_graph_id"] != 0)))
    assert masked[0] == 0.0


# ------------------------------------------------------- pack invariants --
def test_pack_dataset_partitions_and_respects_budgets():
    """Property test: over many budget settings, every graph lands in
    exactly one batch or in ``dropped``, and no batch overflows."""
    rng = np.random.default_rng(0)
    cfg = P.GraphDataConfig(avg_nodes=14, max_nodes=80, max_edges=120,
                            node_feat_dim=5, edge_feat_dim=2, seed=3)
    graphs = [P.make_graph(cfg, i) for i in range(40)]
    for trial in range(12):
        nb = int(rng.integers(8, 120))
        eb = int(rng.integers(8, 200))
        mg = int(rng.integers(1, 12))
        batches, dropped = P.pack_dataset(graphs, nb, eb, mg)
        n_packed = sum(int(b["num_graphs"]) for b in batches)
        assert n_packed + len(dropped) == len(graphs)
        for g in dropped:     # only graphs that can never fit are dropped
            assert g.num_nodes > nb or g.num_edges > eb
        for b in batches:
            k = int(b["num_graphs"])
            assert 1 <= k <= mg
            node_valid = b["node_graph_id"] < mg
            edge_valid = b["edge_index"][:, 0] >= 0
            assert int(node_valid.sum()) <= nb
            assert int(edge_valid.sum()) <= eb
            # edges reference valid nodes of their own graph
            src = b["edge_index"][edge_valid]
            assert (b["node_graph_id"][src[:, 0]]
                    == b["edge_graph_id"][edge_valid]).all()
            assert (b["node_graph_id"][src[:, 1]]
                    == b["edge_graph_id"][edge_valid]).all()
            # graph ids are contiguous 0..k-1 in packing order
            ids = b["node_graph_id"][node_valid]
            assert (np.diff(ids) >= 0).all() and set(ids) == set(range(k))


def _pack_whole_list(graphs, nb, eb, mg, num_shards=1):
    """The greedy packer as a loop over the whole list: the reference
    the incremental packer must reproduce batch for batch."""
    batches, dropped = [], []
    i = 0
    while i < len(graphs):
        if not P.graph_fits_budget(graphs[i], nb, eb):
            dropped.append(graphs[i])
            i += 1
            continue
        if num_shards > 1:
            batch, k = P.shard_pack(graphs[i:], nb, eb, mg, num_shards)
        else:
            batch, k = P.pack_graphs(graphs[i:], nb, eb, mg)
        batches.append(batch)
        i += k
    return batches, dropped


def _shards(batch):
    """A batch as its list of GraphBatch dicts (one, unless a wave)."""
    return batch.shards if isinstance(batch, P.ShardedBatch) else [batch]


def _assert_same_batches(got, want):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        if isinstance(w, P.ShardedBatch):
            assert g.index == w.index
        for gs, ws in zip(_shards(g), _shards(w), strict=True):
            assert gs.keys() == ws.keys()
            for key in ws:
                np.testing.assert_array_equal(gs[key], ws[key], err_msg=key)
                assert np.asarray(gs[key]).dtype == np.asarray(ws[key]).dtype


@pytest.mark.parametrize("num_shards", [1, 2])
def test_iter_pack_matches_the_whole_list_packer(num_shards):
    """Over many budgets, with oversize graphs inside the stream, the
    incremental packer fed from a generator gives the whole-list
    packer's batches array by array and drops the same graphs; it holds
    at most ``max_graphs * num_shards`` unpacked graphs when it yields;
    and ``pack_dataset`` is the same list."""
    rng = np.random.default_rng(11)
    cfg = P.GraphDataConfig(avg_nodes=14, max_nodes=80, max_edges=120,
                            node_feat_dim=5, edge_feat_dim=2, seed=3)
    graphs = [P.make_graph(cfg, i) for i in range(40)]
    drops = 0
    for _ in range(10):
        nb = int(rng.integers(16, 120))
        eb = int(rng.integers(16, 200))
        mg = int(rng.integers(1, 9))
        want, want_dropped = _pack_whole_list(graphs, nb, eb, mg, num_shards)
        pulled = []

        def source():
            for g in graphs:
                pulled.append(g)
                yield g

        got, dropped, packed = [], [], 0
        for batch in P.iter_pack(source(), nb, eb, mg, dropped=dropped,
                                 num_shards=num_shards):
            got.append(batch)
            packed += sum(int(s["num_graphs"]) for s in _shards(batch))
            assert len(pulled) - len(dropped) - packed <= mg * num_shards
        _assert_same_batches(got, want)
        assert [id(g) for g in dropped] == [id(g) for g in want_dropped]
        drops += len(want_dropped)
        batches, pd_dropped = P.pack_dataset(graphs, nb, eb, mg, num_shards)
        _assert_same_batches(batches, want)
        assert [id(g) for g in pd_dropped] == [id(g) for g in want_dropped]
    assert drops                       # oversize graphs closed batches


def _malformed(g):
    """``g`` with an edge endpoint past its last node: ``validate_graph``
    rejects it."""
    ei = g.edge_index.copy()
    ei[0, 1] = g.num_nodes
    return P.Graph(g.node_feat, ei, g.edge_feat, g.num_nodes, g.num_edges,
                   g.y)


def test_pipelined_drain_matches_the_serial_order(monkeypatch):
    """The drain launches each batch as soon as it is packed. Over a
    queue of packable, oversize and malformed requests spread over
    several batches, the batches it launches are ``pack_dataset`` on the
    admitted list, array by array, and its outputs, outcomes and stats
    are those of admitting the whole queue, packing it, then launching."""
    from repro.launch import serve
    nb, eb, mg = 48, 96, 4
    big = dataclasses.replace(DS, avg_nodes=56)
    queue = [P.make_graph(DS, i) for i in range(14)]
    queue.insert(3, P.make_graph(big, 0))
    queue.insert(6, _malformed(queue[5]))
    queue.insert(9, P.make_graph(big, 2))
    queue.insert(13, _malformed(queue[12]))
    assert sum(not P.graph_fits_budget(g, nb, eb) for g in queue) == 2
    cfg = _cfg("gcn")
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    fn = jax.jit(lambda p, b: G.apply_packed(p, cfg, b))
    fb = jax.jit(lambda p, el: G.apply(p, cfg, el))

    launched = []
    to_device = G.packed_to_device

    def recording(batch):
        launched.append(batch)
        return to_device(batch)

    monkeypatch.setattr(G, "packed_to_device", recording)
    outs, stats = serve.drain_gnn_queue(fn, params, queue, nb, eb, mg, fb)
    monkeypatch.undo()

    packable, oversize, outcomes = serve._admit(queue, nb, eb,
                                                can_fallback=True)
    batches, dropped = P.pack_dataset(packable, nb, eb, mg)
    assert not dropped and len(batches) >= 3
    _assert_same_batches(launched, batches)
    serial = [fn(params, G.packed_to_device(b)) for b in batches]
    serial += [fb(params, serve._fallback_input(g)) for g in oversize]
    assert len(outs) == len(serial)
    for got, want in zip(outs, serial):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert stats["outcomes"] == outcomes
    assert [o["index"] for o in stats["outcomes"]] == list(range(len(queue)))
    assert stats["n_batches"] == len(batches)
    assert stats["rejected_invalid"] == 2
    assert stats["fallback_served"] == 2
    assert stats["served"] == len(packable) + 2
    assert stats["node_slot_utilization"] == sum(
        int((b["node_graph_id"] < mg).sum()) for b in batches) / (
            len(batches) * nb)


def test_pack_graphs_raises_on_oversize_first():
    g = P.make_graph(DS, 0)
    with pytest.raises(ValueError):
        P.pack_graphs([g], node_budget=2, edge_budget=2, max_graphs=4)


def test_pack_graphs_stops_at_budget():
    graphs = [P.make_graph(DS, i) for i in range(10)]
    nb = graphs[0].num_nodes + graphs[1].num_nodes
    batch, k = P.pack_graphs(graphs, nb, 10_000, 10)
    assert k == 2                      # third graph would overflow nodes
    assert int((batch["node_graph_id"] < 10).sum()) <= nb


def test_graph_batch_packed_deterministic():
    b1 = P.graph_batch_packed(DS, step=3, node_budget=256,
                              edge_budget=512, max_graphs=8)
    b2 = P.graph_batch_packed(DS, step=3, node_budget=256,
                              edge_budget=512, max_graphs=8)
    np.testing.assert_array_equal(b1["node_feat"], b2["node_feat"])
    np.testing.assert_array_equal(b1["edge_index"], b2["edge_index"])
    b3 = P.graph_batch_packed(DS, step=4, node_budget=256,
                              edge_budget=512, max_graphs=8)
    assert not np.array_equal(b1["node_feat"], b3["node_feat"])


def test_size_budget_rule():
    assert P.size_budget(32, 18) % 8 == 0
    assert P.size_budget(32, 18) >= 32 * 18      # slack over the mean
    assert P.size_budget(1, 1) >= 1


# ------------------------------------------------ host-to-device transfer --
def _qm9_batch():
    batch, k = P.pack_graphs(_graphs(), 128, 256, 8)
    assert k == len(_graphs())
    return batch


def _partition_part():
    g = P.make_graph(P.GraphDataConfig(avg_nodes=40, avg_degree=2,
                                       node_feat_dim=7, edge_feat_dim=3,
                                       max_nodes=128, max_edges=192,
                                       seed=11), 0)
    part = P.partition_graph(g, 2, 96, 160).parts[0]
    assert {"node_in_deg", "node_out_deg"} <= set(part)
    return part


def _wide_dtypes_batch():
    """float64 and int64 leaves, which ``jnp.asarray`` narrows to
    float32 / int32 (values that round, and negative ints)."""
    batch = _qm9_batch()
    rng = np.random.default_rng(3)
    batch["node_feat"] = rng.standard_normal(batch["node_feat"].shape)
    batch["edge_index"] = batch["edge_index"].astype(np.int64)
    return batch


def _narrow_dtypes_batch():
    """Leaves narrower than a word: int8, uint16 and float16 with a NaN
    payload, beside bool."""
    batch = _qm9_batch()
    batch["node_code"] = np.arange(-64, 64, dtype=np.int8)
    batch["edge_tag"] = np.arange(65530, 65536, dtype=np.uint16)
    half = np.array([1.5, -0.0, np.inf, 65504.0], np.float16)
    half = np.concatenate([half, np.array([0x7D01], np.uint16)
                           .view(np.float16)])
    batch["edge_half"] = half
    return batch


@pytest.mark.parametrize("make", [
    lambda: P.empty_graph_batch(64, 128, 4, 11, 4),
    _qm9_batch,
    _partition_part,
    _wide_dtypes_batch,
    _narrow_dtypes_batch,
], ids=["empty", "qm9", "partition_part", "wide_dtypes", "narrow_dtypes"])
def test_packed_to_device_matches_per_leaf_transfer(make, tmp_path):
    """The single-buffer transfer returns what ``jnp.asarray`` per leaf
    returns: same keys in the same order, shapes, dtypes and weak types,
    bit-identical values, and ``y`` stripped. One buffer carries every
    leaf."""
    batch = make()
    ref = {k: jnp.asarray(v) for k, v in batch.items() if k != "y"}
    trace.reset()
    with jax.profiler.trace(str(tmp_path)):
        got = G.packed_to_device(batch)
    counters = trace.snapshot()["counters"]
    trace.reset()
    assert counters == {"put.buffers": 1}
    assert "y" not in got
    assert list(got) == list(ref)
    for k in ref:
        a, b = np.asarray(ref[k]), np.asarray(got[k])
        assert (b.dtype, b.shape) == (a.dtype, a.shape), k
        assert got[k].weak_type == ref[k].weak_type, k
        assert b.tobytes() == a.tobytes(), k


@pytest.mark.parametrize("leaf", [
    jnp.arange(5, dtype=jnp.int32),
    np.exp(1j * np.arange(4)).astype(np.complex64),
    3,
], ids=["jax_array", "complex", "python_int"])
def test_packed_to_device_refuses_leaves_not_in_words(leaf):
    """A leaf that cannot be laid in 4-byte words (already on the
    device, complex, not a numpy value) is refused by name."""
    batch = _qm9_batch()
    batch["extra"] = leaf
    with pytest.raises(TypeError, match="'extra'"):
        G.packed_to_device(batch)
