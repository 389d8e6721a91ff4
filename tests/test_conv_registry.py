"""The conv registry contract: capability tuples, the DSE conv axis,
perf-model conv one-hots and the parity-grid axes all derive live from
``repro.core.convs.CONV_REGISTRY`` — the bugfix for the conv tuples
that used to be duplicated (and could drift) across convs.py, dse.py
and perf_model.py. A toy conv registered here must appear everywhere
with zero edits to any other module."""
import jax
import numpy as np

import parity
from repro.core import convs as Cv
from repro.core import dse
from repro.core import perf_model as PM
from repro.data import pipeline as P


def _dse_convs():
    return [n for n in Cv.CONV_TYPES if Cv.conv_spec(n).dse]


def test_capability_tuples_derive_from_registry():
    assert Cv.CONV_TYPES == tuple(Cv.CONV_REGISTRY)
    assert Cv.REORDERABLE_CONVS == tuple(
        n for n in Cv.CONV_TYPES if Cv.conv_spec(n).reorderable)
    assert Cv.RESIDENT_CONVS == tuple(
        n for n in Cv.CONV_TYPES if Cv.conv_spec(n).resident)
    assert Cv.STACK_CONVS == tuple(
        n for n in Cv.CONV_TYPES if not Cv.conv_spec(n).edge_state)
    # the attention conv is registered with the documented capabilities
    gat = Cv.conv_spec("gat")
    assert gat.attention and gat.partition_bitwise
    assert not gat.reorderable and not gat.resident
    assert gat.precisions == Cv.PRECISION_GRID
    with np.testing.assert_raises(ValueError):
        Cv.conv_spec("nope")


def test_dse_space_perf_features_and_registry_agree():
    """The agreement pin: dse.SPACE['conv'], the perf-model conv
    one-hots and the registry enumerate the same convs in the same
    order — the drift this PR's registry refactor closes."""
    dse_convs = _dse_convs()
    assert dse.SPACE["conv"] == dse_convs
    onehots = [f for f in PM.FEATURE_NAMES if f.startswith("conv_")]
    assert onehots == [f"conv_{c}" for c in dse_convs]
    assert parity.conv_axis() == tuple(Cv.STACK_CONVS)
    # featurization one-hot roundtrip, gat included
    rng = np.random.default_rng(0)
    d = dict(dse.sample_design(rng), conv="gat")
    v = PM.features(d)
    assert v[PM.FEATURE_NAMES.index("conv_gat")] == 1.0
    assert sum(v[PM.FEATURE_NAMES.index(f"conv_{c}")]
               for c in dse_convs) == 1.0
    # database rows recorded before the attention conv landed still
    # featurize — as non-attention designs (conv_gat stays cold)
    w = PM.features(dict(d, conv="gcn"))
    assert w[PM.FEATURE_NAMES.index("conv_gat")] == 0.0
    assert w[PM.FEATURE_NAMES.index("conv_gcn")] == 1.0


def test_toy_conv_appears_everywhere_without_edits():
    """register_conv('toy', ...) -> the conv shows up in dse.SPACE, the
    perf-model featurization, and the parity-grid parametrization, and
    its packed parity cell actually runs — no edits anywhere else."""
    assert "toy" not in Cv.CONV_TYPES
    n_features = len(PM.FEATURE_NAMES)
    try:
        Cv.register_conv("toy", Cv.gin_plan, Cv.gin_apply,
                         precisions=("fp32",))
        assert "toy" in Cv.CONV_TYPES
        # DSE search space
        assert "toy" in dse.SPACE["conv"]
        rng = np.random.default_rng(1)
        assert any(dse.sample_design(rng)["conv"] == "toy"
                   for _ in range(64))
        # perf-model featurization
        assert "conv_toy" in PM.FEATURE_NAMES
        assert len(PM.FEATURE_NAMES) == n_features + 1
        d = dict(dse.sample_design(rng), conv="toy")
        v = PM.features(d)
        assert len(v) == len(PM.FEATURE_NAMES)
        assert v[PM.FEATURE_NAMES.index("conv_toy")] == 1.0
        # parity-grid axes (what parametrizes the packed grid and what
        # the subprocess grids re-derive in the child)
        assert "toy" in parity.conv_axis()
        assert parity.precision_axis("toy") == ("fp32",)
        assert ("toy", "fp32") in parity.conv_precision_cases()
        assert "toy" not in parity.bitwise_convs()
        # and the cell itself runs: xla == pallas == padded oracle
        ds = P.GraphDataConfig(avg_nodes=8, max_nodes=64, max_edges=64,
                               node_feat_dim=5, edge_feat_dim=2, seed=3)
        parity.check_packed("toy", "fp32",
                            [P.make_graph(ds, i) for i in range(3)], ds)
    finally:
        Cv.unregister_conv("toy")
    assert "toy" not in Cv.CONV_TYPES
    assert "toy" not in dse.SPACE["conv"]
    assert "conv_toy" not in PM.FEATURE_NAMES
    assert len(PM.FEATURE_NAMES) == n_features
