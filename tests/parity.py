"""Shared cross-backend parity harness: one conv x precision x backend
matrix, driven by the conv registry, reused by the packed
(test_fused_gather), sharded (test_sharded) and partitioned
(test_partitioned) parity suites.

Before this module the three suites each hardcoded their own
``("gcn", "sage", "gin", "pna")`` x ``("fp32", "bf16", "int8")`` grid —
adding a conv meant editing every test file and hoping none was missed.
Now the axes come from ``repro.core.convs.CONV_REGISTRY``:

* ``conv_axis()`` — every registered conv a plain conv stack runs
  (``STACK_CONVS``: not the edge-state convs, which only GraphGPS
  blocks run, tests/test_gps.py), in registration order;
* ``precision_axis(conv)`` — the precisions its ConvSpec declares
  (attention convs still list int8: only the projection and the
  aggregation stream quantize, the attention math itself is pinned to
  fp32 — see core/aggregations.segment_softmax);
* ``bitwise_convs()`` — convs whose ConvSpec promises *bitwise*
  fp32 partitioned parity against the padded oracle (the serve-path
  acceptance contract); the partitioned grid asserts array_equal for
  exactly this set and a 1e-4 tolerance for the rest (pna reduces its
  degree statistics in a different association order across devices).

``register_conv`` fires registry listeners, so a conv registered in a
test process appears in these axes — and therefore in the grid
parametrization — without touching any test file
(test_conv_registry.py pins that property).

The sharded/partitioned grids need the simulated device count pinned
before jax initializes, so they run as subprocess scripts; the scripts
import the registry in the child and derive the same axes there.
"""
import os
import subprocess
import sys
import textwrap

BACKENDS = ("xla", "pallas")

# packed-grid tolerances: xla-vs-pallas under one PrecisionPolicy — the
# backends share the quantization, so only aggregation order differs
PACKED_ATOL = {"fp32": 1e-4, "bf16": 1e-4, "int8": 1e-4}
ORACLE_ATOL = 1e-4          # fp32 packed vs the padded per-graph oracle


def conv_axis():
    """Every conv-stack conv — the rows of the parity matrix."""
    from repro.core import convs as Cv
    return tuple(Cv.STACK_CONVS)


def precision_axis(conv):
    """The precisions this conv's ConvSpec declares."""
    from repro.core import convs as Cv
    return tuple(Cv.conv_spec(conv).precisions)


def conv_precision_cases():
    """(conv, precision) pairs for pytest.mark.parametrize."""
    return [(c, p) for c in conv_axis() for p in precision_axis(c)]


def bitwise_convs():
    """Convs promising bitwise fp32 partitioned parity."""
    from repro.core import convs as Cv
    return tuple(n for n in Cv.STACK_CONVS
                 if Cv.conv_spec(n).partition_bitwise)


def model_cfg(conv, node_feat_dim=7, edge_feat_dim=3, hidden=8, out=8):
    """The small 2-layer model every parity grid runs."""
    from repro.core import gnn_model as G
    return G.GNNModelConfig(
        graph_input_feature_dim=node_feat_dim,
        graph_input_edge_dim=edge_feat_dim,
        gnn_hidden_dim=hidden, gnn_num_layers=2, gnn_output_dim=out,
        gnn_conv=conv,
        mlp_head=G.MLPConfig(in_dim=out * 3, out_dim=1, hidden_dim=8,
                             hidden_layers=1))


def check_packed(conv, precision, graphs, ds, atol=None):
    """The packed cell of the matrix: apply_packed traced under the
    pallas backend == the materialized XLA trace under one calibrated
    PrecisionPolicy; at fp32 also == the padded per-graph oracle."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import aggregations as A
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    from repro.nn import param as prm

    cfg = model_cfg(conv, ds.node_feat_dim, ds.edge_feat_dim)
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    batch, k = P.pack_graphs(graphs, 128, 256, 8)
    assert k == len(graphs)
    jb = {kk: jnp.asarray(v) for kk, v in batch.items() if kk != "y"}
    policy = None
    if precision != "fp32":
        policy = G.calibrated_policy(params, cfg, jb, precision)
    outs = {}
    for backend in BACKENDS:
        with A.backend_scope(backend, 32, 16):
            outs[backend] = np.asarray(jax.jit(
                lambda p, b: G.apply_packed(p, cfg, b, None, policy))(
                    params, jb))
    err = float(np.max(np.abs(outs["pallas"] - outs["xla"])))
    assert err < (atol or PACKED_ATOL[precision]), (conv, precision, err)
    if precision == "fp32":
        oracle = jax.jit(lambda p, e: G.apply(p, cfg, e))
        for i, g in enumerate(graphs):
            el = {"node_feat": jnp.asarray(g.node_feat),
                  "edge_index": jnp.asarray(g.edge_index),
                  "edge_feat": jnp.asarray(g.edge_feat),
                  "num_nodes": jnp.int32(g.num_nodes)}
            ref = np.asarray(oracle(params, el))
            got = outs["xla"][i]
            assert float(np.max(np.abs(got - ref))) < ORACLE_ATOL, \
                (conv, i)
    return outs


def run_parity_subprocess(script, token, timeout=900):
    """Run a parity grid in a fresh interpreter (the scripts pin
    XLA_FLAGS before jax imports) and assert its success token."""
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert token in out.stdout, (out.stdout[-2000:], out.stderr[-3000:])


# The shared subprocess header: device pinning, imports, and the
# registry-derived axes (the child re-derives them — same source of
# truth as conv_axis()/precision_axis()/bitwise_convs() above).
SCRIPT_PRELUDE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import convs as Cv
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    from repro.launch.mesh import make_data_mesh
    from repro.nn import param as prm
    from repro.core import aggregations as agg_mod

    CONVS = tuple(Cv.STACK_CONVS)
    BITWISE = tuple(n for n in CONVS
                    if Cv.conv_spec(n).partition_bitwise)

    def precisions(conv):
        return tuple(Cv.conv_spec(conv).precisions)

    def model_cfg(conv, node_feat_dim=7, edge_feat_dim=3):
        return G.GNNModelConfig(
            graph_input_feature_dim=node_feat_dim,
            graph_input_edge_dim=edge_feat_dim,
            gnn_hidden_dim=8, gnn_num_layers=2, gnn_output_dim=8,
            gnn_conv=conv,
            mlp_head=G.MLPConfig(in_dim=24, out_dim=1, hidden_dim=8,
                                 hidden_layers=1))

    def el(g):
        return {"node_feat": jnp.asarray(g.node_feat),
                "edge_index": jnp.asarray(g.edge_index),
                "edge_feat": jnp.asarray(g.edge_feat),
                "num_nodes": jnp.int32(g.num_nodes)}
""")


def sharded_parity_script():
    """Sharded-vs-single-device over the registry grid on 2 simulated
    host devices, plus host-order gather vs the padded oracle and a
    4-shard wave with idle shards (see test_sharded.py)."""
    return SCRIPT_PRELUDE + textwrap.dedent("""
    DS = P.GraphDataConfig(avg_nodes=10, max_nodes=64, max_edges=64,
                           node_feat_dim=7, edge_feat_dim=3, seed=5)
    graphs = [P.make_graph(DS, i) for i in range(9)]   # uneven over 2

    import tempfile
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro.kernels.flash_attention import ops as AO
    from repro.launch import serve
    from repro.runtime import trace

    def per_leaf_stack(w):
        return {k: jnp.stack([jnp.asarray(b[k]) for b in w.shards])
                for k in w.shards[0] if k != "y"}

    mesh2 = make_data_mesh(2)
    wave, k = P.shard_pack(graphs, 96, 192, 8, num_shards=2)
    assert k == len(graphs)
    # one transfer lands each shard on its device, bit for bit the
    # per-leaf stack, already under the sharded program's placement
    stacked = G.stack_shards(wave, mesh2)
    ref_stack = per_leaf_stack(wave)
    assert list(stacked) == list(ref_stack)
    for key, v in ref_stack.items():
        assert (stacked[key].dtype, stacked[key].shape) == (v.dtype, v.shape)
        assert np.asarray(stacked[key]).tobytes() == \
            np.asarray(v).tobytes(), key
        assert stacked[key].sharding == NamedSharding(mesh2, PS("data")), \
            (key, stacked[key].sharding)
    for conv in CONVS:
        cfg = model_cfg(conv)
        params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
        cal_batch, _ = P.pack_graphs(graphs, 192, 384, 16)
        for precision in precisions(conv):
            policy = G.calibrated_policy(
                params, cfg, G.packed_to_device(cal_batch), precision)
            for backend in ("xla", "pallas"):
                with agg_mod.backend_scope(backend, 32, 32):
                    fn = G.make_sharded_apply(cfg, mesh2, None, policy)
                    out = np.asarray(fn(params, stacked))
                    single = jax.jit(lambda p, b: G.apply_packed(
                        p, cfg, b, None, policy))
                    for s, shard in enumerate(wave.shards):
                        ref = np.asarray(single(
                            params, G.packed_to_device(shard)))
                        err = np.abs(out[s] - ref).max()
                        assert err < 1e-5, (conv, precision, backend, err)
        # host-order gather vs the padded per-graph oracle (fp32)
        fn = G.make_sharded_apply(cfg, mesh2)
        host = P.gather_shard_outputs(np.asarray(fn(params, stacked)),
                                      wave.index)
        oracle = jax.jit(lambda p, e, c=cfg: G.apply(p, c, e))
        for i, g in enumerate(graphs):
            ref = np.asarray(oracle(params, el(g)))
            assert np.abs(host[i] - ref).max() < 1e-4, (conv, i)
        # the sharded drain's outputs are those of the per-leaf stack
        trace.reset()
        with jax.profiler.trace(tempfile.mkdtemp()):
            outs, stats = serve.drain_gnn_queue_sharded(
                fn, params, graphs, 96, 192, 8, 2)
        counters = trace.snapshot()["counters"]
        waves, _ = P.pack_dataset(graphs, 96, 192, 8, num_shards=2)
        assert len(outs) == len(waves) == stats["n_batches"]
        for w, o in zip(waves, outs):
            ref = P.gather_shard_outputs(
                np.asarray(fn(params, per_leaf_stack(w))), w.index)
            assert np.array_equal(o, ref), conv
        # one buffer a wave; the packer counts each shard it packs
        # (empty shards are not packed)
        sizes = [np.asarray(b["graph_num_nodes"], np.int64)
                 for w in waves for b, ix in zip(w.shards, w.index) if ix]
        computed = sum(int(AO.segment_tiles(n, 128, 128)[1].sum())
                       for n in sizes)
        assert counters == {
            "put.buffers": len(waves),
            "attn.rows": int(sum(n.sum() for n in sizes)),
            "attn.pairs": int(sum((n * n).sum() for n in sizes)),
            "attn.pairs_computed": computed * 128 * 128}, counters
        # 4-shard wave with idle shards: one graph, three empty blocks
        wave4, k4 = P.shard_pack(graphs[:1], 96, 192, 8, num_shards=4)
        assert k4 == 1
        out4 = np.asarray(G.apply_packed_sharded(
            params, cfg, wave4, mesh=make_data_mesh(4)))
        host4 = P.gather_shard_outputs(out4, wave4.index)
        ref = np.asarray(oracle(params, el(graphs[0])))
        assert np.abs(host4[0] - ref).max() < 1e-4, conv
    print("SHARDED_PARITY_OK")
""")


def partitioned_parity_script():
    """Partitioned-vs-padded-oracle over the registry grid on 4
    simulated host devices; BITWISE convs assert array_equal at fp32
    (see test_partitioned.py)."""
    return SCRIPT_PRELUDE + textwrap.dedent("""
    DS = P.GraphDataConfig(avg_nodes=40, avg_degree=2, node_feat_dim=7,
                           edge_feat_dim=3, max_nodes=128, max_edges=192,
                           seed=11)
    g = P.make_graph(DS, 0)
    part4 = P.partition_graph(g, 4, 64, 128)
    mesh4 = make_data_mesh(4)
    stacked4 = G.stack_shards(part4.parts, mesh4)
    eg = el(g)

    for conv in CONVS:
        cfg = model_cfg(conv)
        params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
        oracle = jax.jit(lambda p, e, c=cfg: G.apply(p, c, e))
        ref32 = np.asarray(oracle(params, eg))
        cal_batch, _ = P.pack_graphs([g], 192, 384, 4)
        for precision in precisions(conv):
            policy = G.calibrated_policy(
                params, cfg, G.packed_to_device(cal_batch), precision)
            for backend in ("xla", "pallas"):
                with agg_mod.backend_scope(backend, 32, 32):
                    fn = G.make_partitioned_apply(
                        cfg, mesh4, None, policy,
                        out_rows=part4.padded_nodes)
                    out = np.asarray(fn(params, stacked4))
                    single = jax.jit(lambda p, b, c=cfg, po=policy:
                                     G.apply_packed(p, c, b, None, po))
                    ref = np.asarray(single(
                        params, G.packed_to_device(cal_batch)))[0]
                    err = np.abs(out - ref).max()
                    assert err < 1e-4, (conv, precision, backend, err)
                    if precision == "fp32" and conv in BITWISE:
                        # bitwise vs the padded oracle built under the
                        # SAME backend (the serve-path contract)
                        refb = np.asarray(jax.jit(
                            lambda p, e: G.apply(p, cfg, e))(params, eg))
                        assert np.array_equal(out, refb), \\
                            (conv, backend, np.abs(out - refb).max())
        # degenerate: 1-part partition over a 1-device mesh is the
        # padded program with an inert exchange — bitwise at fp32
        part1 = P.partition_graph(g, 1, 128, 192)
        out1 = np.asarray(G.apply_packed_partitioned(
            params, cfg, part1, mesh=make_data_mesh(1)))
        assert np.array_equal(out1, ref32), conv

    # degenerate: disconnected components split cut-free -> the SPMD
    # exchange runs with an all-padding halo and must be inert (gcn fp32)
    nf = np.zeros((128, 7), np.float32)
    nf[:8] = np.random.default_rng(1).normal(size=(8, 7)).astype(
        np.float32)
    ei = np.full((192, 2), -1, np.int32)
    edges = [(i, i + 1) for i in range(3)] \\
        + [(4 + i, 5 + i) for i in range(3)]
    for i, (s, d) in enumerate(edges):
        ei[i] = (s, d)
    gd = P.Graph(node_feat=nf, edge_index=ei,
                 edge_feat=np.zeros((192, 3), np.float32),
                 num_nodes=8, num_edges=len(edges),
                 y=np.zeros((1,), np.float32))
    cfg = model_cfg("gcn")
    params = prm.materialize(G.model_plan(cfg), jax.random.key(0))
    pd = P.partition_graph(gd, 2, 16, 16)
    assert pd.cut_edges == 0 and pd.halo_nodes == 0
    out = np.asarray(G.apply_packed_partitioned(
        params, cfg, pd, mesh=make_data_mesh(2)))
    ref = np.asarray(jax.jit(lambda p, e: G.apply(p, cfg, e))(
        params, el(gd)))
    assert np.array_equal(out, ref)
    print("PARTITIONED_PARITY_OK")
""")
