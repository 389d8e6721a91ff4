"""Compile rehearsals for a TPU v5e that is described, not attached.

Every test here lowers and compiles through the TPU compiler for the
``v5e:2x2`` topology: the kernels of the
main path at the serving shapes, ``apply_packed`` at the paper widths
for every registered conv on both aggregation backends, and the sharded
and partitioned programs on a 4-device mesh. Nothing runs; a refusal by
Mosaic (misaligned slice, too much VMEM or SMEM) fails here instead of
on the chip.

The topology is described only inside the module fixture, which skips
where libtpu cannot describe it. The process default backend stays the
CPU, so kernels are asked for ``interpret=False`` explicitly.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from parity import BACKENDS, conv_axis

# the serving shapes: a packed qm9 batch of 32 graphs
BATCH_GRAPHS = 32
N, E = 872, 1736


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:          # noqa: BLE001 — any libtpu refusal
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without the chip: keep the cache out
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from jax.sharding import Mesh
    return Mesh(np.asarray(topo.devices[:4]), ("data",))


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sharding)


def _specs(tree, sharding):
    return jax.tree_util.tree_map(
        lambda a: _spec(np.shape(a), np.asarray(a).dtype, sharding), tree)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _kernel_ok(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _qm9_batch():
    from repro.configs.gnn import DATASETS
    from repro.data import pipeline as P
    ds = DATASETS["qm9"]
    nb = P.size_budget(BATCH_GRAPHS, ds.avg_nodes)
    eb = P.size_budget(BATCH_GRAPHS, ds.avg_nodes * ds.avg_degree)
    assert (nb, eb) == (N, E)
    graphs = [P.make_graph(ds, i) for i in range(BATCH_GRAPHS)]
    batch, _ = P.pack_graphs(graphs, nb, eb, BATCH_GRAPHS)
    return {k: v for k, v in batch.items() if k != "y"}


def _paper_cfg(conv):
    from repro.configs.gnn import DATASETS, benchmark_config
    return dataclasses.replace(
        benchmark_config(conv),
        avg_degree=float(DATASETS["qm9"].avg_degree))


def _param_specs(cfg, sharding):
    from repro.core import gnn_model as G
    from repro.nn import param as prm
    return jax.tree_util.tree_map(
        lambda s: _spec(s.shape, s.dtype, sharding),
        prm.abstract(G.model_plan(cfg)))


# ------------------------------------------------------------ kernels --
@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int8"])
def test_v2_fused_gather_compiles(one_chip, dtype):
    from repro.kernels.fused_gather_aggregate.kernel import \
        fused_gather_aggregate_v2_pallas
    for f in (11, 128):
        c = _compile(
            lambda x, s, d, sc: fused_gather_aggregate_v2_pallas(
                x, s, d, N, scale=sc, agg="mean", interpret=False),
            _spec((N, f), dtype, one_chip), _spec((E,), jnp.int32, one_chip),
            _spec((E,), jnp.int32, one_chip),
            _spec((E,), jnp.float32, one_chip))
        _kernel_ok(c)


@pytest.mark.parametrize("width", [11, 128])
def test_segment_aggregate_dma_compiles(one_chip, width):
    from repro.kernels.segment_aggregate.kernel import \
        segment_aggregate_v2_pallas
    for dtype in ("float32", "bfloat16"):
        for agg in ("sum", "max", "std"):
            c = _compile(
                lambda m, d: segment_aggregate_v2_pallas(
                    m, d, N, agg=agg, interpret=False),
                _spec((E, width), dtype, one_chip),
                _spec((E,), jnp.int32, one_chip))
            _kernel_ok(c)


def test_segment_softmax_compiles(one_chip):
    from repro.kernels.segment_softmax.kernel import segment_softmax_pallas
    c = _compile(lambda z, d: segment_softmax_pallas(z, d, N,
                                                     interpret=False),
                 _spec((E,), jnp.float32, one_chip),
                 _spec((E,), jnp.int32, one_chip))
    _kernel_ok(c)


def _stack_specs(n, fmax, k, sharding):
    f32 = jnp.float32
    return (_spec((n, fmax), f32, sharding),
            _spec((E,), jnp.int32, sharding),
            _spec((E,), jnp.int32, sharding),
            _spec((E,), f32, sharding),
            _spec((n,), f32, sharding), _spec((n,), f32, sharding),
            _spec((k, fmax, fmax), f32, sharding),
            _spec((k, fmax, fmax), f32, sharding),
            _spec((k, fmax, fmax), f32, sharding),
            _spec((k, fmax), f32, sharding), _spec((k, 4), f32, sharding))


def _compile_stack(n, fmax, kind, quantized, sharding, k=2):
    from repro.kernels.fused_gather_aggregate.residency import \
        fused_layer_stack_pallas
    return _compile(
        lambda *a: fused_layer_stack_pallas(
            *a, kind=kind, interpret=False, quantized=quantized),
        *_stack_specs(n, fmax, k, sharding))


@pytest.mark.parametrize("kind", ["gcn", "sage"])
def test_residency_stack_compiles(one_chip, kind):
    _kernel_ok(_compile_stack(N, 128, kind, False, one_chip))
    _kernel_ok(_compile_stack(N, 128, kind, True, one_chip))


@pytest.mark.budget(120)
def test_residency_plan_budget_is_the_compiled_limit(one_chip):
    """The largest node table ``residency_plan`` calls legal compiles
    under the kernels' scoped-VMEM limit, and a table twice as large is
    both called illegal and refused by Mosaic: the planner's budget and
    the compiler's limit are the same number."""
    from repro.core import convs as C
    from repro.kernels.tpu import VMEM_LIMIT_BYTES
    fmax, kind, quantized = 256, "sage", True
    dims = [(fmax, fmax), (fmax, fmax)]

    def legal(n):
        return C.residency_plan(dims, n, kind, 2, quantized=quantized).legal

    lo, hi = 8, 1 << 18
    while hi - lo > 8:
        mid = (lo + hi) // 2 // 8 * 8
        lo, hi = (mid, hi) if legal(mid) else (lo, mid)
    plan = C.residency_plan(dims, lo, kind, 2, quantized=quantized)
    assert plan.vmem_budget == VMEM_LIMIT_BYTES
    assert plan.vmem_required <= VMEM_LIMIT_BYTES
    c = _compile_stack(lo, fmax, kind, quantized, one_chip)
    _kernel_ok(c)
    ma = c.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes)
    assert used < VMEM_LIMIT_BYTES
    assert not legal(2 * lo)
    with pytest.raises(Exception, match="vmem"):
        _compile_stack(2 * lo, fmax, kind, quantized, one_chip)


@pytest.mark.parametrize("agg", ["max", "var"])
def test_onehot_refuses_uncompilable_aggs(agg):
    from repro.kernels.segment_aggregate.kernel import \
        segment_aggregate_pallas
    m = jnp.ones((16, 8), jnp.float32)
    d = jnp.zeros((16,), jnp.int32)
    with pytest.raises(NotImplementedError, match="gather_mode='dma'"):
        segment_aggregate_pallas(m, d, 4, agg=agg, interpret=False)


# ----------------------------------------------------------- programs --
@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("conv", conv_axis())
def test_apply_packed_paper_widths_compiles(one_chip, conv, backend):
    from repro.core import aggregations as A
    from repro.core import gnn_model as G
    from repro.core import quantization as Q
    cfg = _paper_cfg(conv)
    assert (cfg.gnn_hidden_dim, cfg.gnn_output_dim) == (128, 64)
    params = _param_specs(cfg, one_chip)
    batch = _specs(_qm9_batch(), one_chip)
    for prec in ("fp32", "bf16"):
        pol = Q.resolve_policy(prec, cfg.gnn_num_layers)
        with A.backend_scope(backend, interpret=False):
            c = _compile(lambda p, b: G.apply_packed(p, cfg, b, None, pol),
                         params, batch)
        assert ("tpu_custom_call" in c.as_text()) == (backend == "pallas")


def test_sharded_program_compiles_on_4_devices(mesh4):
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.core import gnn_model as G
    cfg = _paper_cfg("gcn")
    batch = _qm9_batch()
    stacked = {k: np.stack([v] * 4) for k, v in batch.items()}
    c = _compile(G.make_sharded_apply(cfg, mesh4),
                 _param_specs(cfg, NamedSharding(mesh4, PS())),
                 _specs(stacked, NamedSharding(mesh4, PS("data"))))
    assert len(c.output_shardings.device_set) == 4


@pytest.mark.parametrize("shards", [1, 4])
def test_transfer_split_compiles(one_chip, mesh4, shards):
    """The split program of the one-buffer transfer, for a packed batch
    on one chip and for a wave of four shards landed on a 4-device mesh:
    there every output keeps the ("data",) placement and nothing moves
    between devices."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.core import gnn_model as G
    layout, _, width = G._word_layout([_qm9_batch()] * shards)
    if shards == 1:
        G._split_program(layout, None).lower(
            _spec((width,), np.uint32, one_chip)).compile()
        return
    data = NamedSharding(mesh4, PS("data"))
    c = G._split_program(layout, data).lower(
        _spec((shards, width), np.uint32, data)).compile()
    assert all(s == data for s in jax.tree_util.tree_leaves(
        c.output_shardings))
    text = c.as_text()
    assert not any(op in text for op in (
        "all-gather", "all-to-all", "collective-permute", "all-reduce"))


def test_partitioned_program_compiles_on_4_devices(mesh4):
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.configs.gnn import DATASETS
    from repro.core import gnn_model as G
    from repro.data import pipeline as P
    cfg = _paper_cfg("gcn")
    ds = DATASETS["qm9"]
    big = dataclasses.replace(ds, avg_nodes=int(1.2 * N), max_nodes=4 * N,
                              max_edges=4 * E, seed=ds.seed + 0x0B1)
    part = P.partition_graph(P.make_graph(big, 0), 4, N, E)
    stacked = {k: np.stack([np.asarray(p[k]) for p in part.parts])
               for k in part.parts[0] if k != "y"}
    fn = G.make_partitioned_apply(cfg, mesh4, out_rows=part.padded_nodes)
    c = _compile(fn, _param_specs(cfg, NamedSharding(mesh4, PS())),
                 _specs(stacked, NamedSharding(mesh4, PS("data"))))
    assert "all-gather" in c.as_text()


# ------------------------------------------------------------ GraphGPS --
# the gps-peptides cell: 128-graph batches of 22,272 node rows, 4 heads
# of 24
GPS_N, GPS_E, GPS_G = 22272, 45240, 128


def test_segment_attention_compiles_at_the_cell_shapes(one_chip):
    from repro.kernels.flash_attention.kernel import \
        segment_attention_pallas
    tiles = GPS_N // 128
    rows = _spec((GPS_N, 96), jnp.float32, one_chip)
    c = _compile(
        lambda q, k, v, g, f, n: segment_attention_pallas(
            q, k, v, g, f, n, num_heads=4, num_graphs=GPS_G,
            interpret=False),
        rows, rows, rows, _spec((GPS_N,), jnp.int32, one_chip),
        _spec((tiles,), jnp.int32, one_chip),
        _spec((tiles,), jnp.int32, one_chip))
    _kernel_ok(c)


def test_gps_program_compiles_with_the_kernel(one_chip, monkeypatch):
    """The whole GPS program at the cell's shapes, with the attention
    on the kernel as it runs on a TPU: four kernel calls, one a layer,
    all named ``segment_attention`` (the op label the benchmark's
    ``attn_ms.screen`` reads)."""
    import functools
    import re

    from repro.core import gnn_model as G
    from repro.core import gps as GPS
    from repro.data import pipeline as P
    monkeypatch.setattr(GPS.attn_ops, "segment_attention", functools.partial(
        GPS.attn_ops.segment_attention, use_kernel=True))
    cfg = G.GNNModelConfig(
        graph_input_feature_dim=39, graph_input_edge_dim=3,
        gnn_hidden_dim=96, gnn_num_layers=4, gnn_output_dim=96,
        gnn_conv="gatedgcn", global_pooling=("mean",),
        mlp_head=G.MLPConfig(in_dim=96, out_dim=10, hidden_dims=(48, 24)),
        gps=GPS.GPSConfig())
    batch = {k: v for k, v in P.empty_graph_batch(
        GPS_N, GPS_E, GPS_G, 39, 3, 10).items() if k != "y"}
    c = _compile(lambda p, b: G.apply_packed(p, cfg, b),
                 _param_specs(cfg, one_chip), _specs(batch, one_chip))
    names = re.findall(r"%(segment_attention[.\d]*) = ", c.as_text())
    assert len(names) == 4, names
