"""CPU tests that the benchmark's ``correct`` catches what it must.

Each test skips the harness's look for a chip and drives the rest of a
run (set-up, the window, the check) at a size a test run can hold:
once as the program is, where ``correct`` must hold, and once with the
timed path broken underneath, where it must not. The faults a serving
cell can have: an answer altered where it is produced; half of a batch
left out, its rows filled with the mean of the rest; and, across chips,
the exchange of the shards' outputs left out. The controls read by
``bench_control`` (the reference at ``bf16x3`` in the program's place,
and the program's own ``bf16`` policy) must fail the limit too.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap
import time
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_control  # noqa: E402
import bench_harness as H  # noqa: E402
import bench_run  # noqa: E402

SEED = 2 ** 31 + 99


def small_cell(name: str) -> H.Cell:
    cell = H.load_cell(name)
    cell.traffic["pool_graphs"] = 96
    cell.traffic["warmup_s"] = 0.2
    if cell.traffic["mode"] == "screen":
        cell.traffic["chunk_graphs"] = 64
    else:
        cell.traffic["rate_per_s"] = 400.0
    return cell


@pytest.fixture
def cpu_jax(monkeypatch):
    """Run cells in this process without the persistent cache, and put
    the matmul precision back afterwards."""
    import jax
    saved = jax.config.jax_default_matmul_precision
    monkeypatch.setattr(
        bench_run, "configure_jax",
        lambda config: jax.config.update(
            "jax_default_matmul_precision",
            config["precision"]["matmul_precision"]))
    yield
    jax.config.update("jax_default_matmul_precision", saved)


def run_small(name: str) -> dict:
    return bench_run.run_cell(small_cell(name), SEED, 0.5, False,
                              require_chip=False,
                              t_start=time.perf_counter())


def alter_one_answer(apply_packed):
    def broken(params, cfg, batch, *a, **k):
        return apply_packed(params, cfg, batch, *a, **k).at[0].add(1e-3)
    return broken


def drop_half_the_batch(apply_packed):
    import jax.numpy as jnp

    def broken(params, cfg, batch, *a, **k):
        out = apply_packed(params, cfg, batch, *a, **k)
        valid = batch["graph_valid"]
        half = jnp.sum(valid.astype(jnp.int32)) // 2
        keep = jnp.arange(valid.shape[0]) < half
        w = keep.astype(out.dtype)[:, None]
        mean = jnp.sum(out * w, 0) / jnp.maximum(jnp.sum(w), 1.0)
        return jnp.where(keep[:, None] | ~valid[:, None], out, mean)
    return broken


CELLS_1 = ["gcn-qm9.screen", "pna-qm9.screen", "gcn-qm9.online"]


@pytest.mark.parametrize("name", CELLS_1)
def test_sound_run_is_correct(name, cpu_jax):
    res = run_small(name)
    assert res["correct"], res["check"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "check"
    assert set(res["metrics"]) == {m["name"]
                                   for m in H.load_cell(name).end_to_end}


@pytest.mark.parametrize("fault", [alter_one_answer, drop_half_the_batch])
@pytest.mark.parametrize("name", CELLS_1)
def test_planted_fault_is_not_correct(name, fault, cpu_jax, monkeypatch):
    from repro.core import gnn_model as G
    monkeypatch.setattr(G, "apply_packed", fault(G.apply_packed))
    res = run_small(name)
    assert not res["correct"], res["check"]
    assert res["check"]["max_err"]["value"] > \
        res["check"]["max_err"]["limit"]


@pytest.mark.parametrize("name", ["gcn-qm9.screen", "pna-qm9.screen",
                                  "gcn-qm9.online"])
def test_controls_fail_the_limit(name, cpu_jax):
    """Sound below the limit; the reference at bf16x3 in the program's
    place, and the program's own bf16 policy, above it."""
    import jax
    cell = small_cell(name)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["precision"]["matmul_precision"])
    r = bench_control.seed_readings(cell, H.norm_seed(SEED), 0.5, False)
    limit = cell.config["check"]["max_err"]
    assert r["sound"] <= limit < r["ref_bf16x3"], r
    assert limit < r["program_bf16"], r


FOUR_CHIP = textwrap.dedent("""
    import json, sys, time
    sys.path[:0] = [{src!r}, {here!r}]
    import numpy as np
    import jax
    import bench_harness as H, bench_run
    from repro.data import pipeline as P
    bench_run.configure_jax = lambda config: jax.config.update(
        "jax_default_matmul_precision",
        config["precision"]["matmul_precision"])
    if {broken!r}:
        gather = P.gather_shard_outputs
        def no_exchange(outs, index):
            outs = np.array(outs)
            outs[1:] = outs[0]          # the other chips' rows never arrive
            return gather(outs, index)
        P.gather_shard_outputs = no_exchange
    cell = H.load_cell("gcn-qm9.screen-x4")
    cell.traffic.update(pool_graphs=96, chunk_graphs=160, warmup_s=0.2)
    res = bench_run.run_cell(cell, {seed}, 1.0, False, require_chip=False,
                             t_start=time.perf_counter())
    print(json.dumps(res))
""")


@pytest.mark.parametrize("broken", [False, True])
def test_four_chip_exchange_left_out_is_not_correct(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = FOUR_CHIP.format(src=str(ROOT / "src"), here=str(HERE),
                            broken=broken, seed=SEED)
    p = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["count"] == 4
    assert res["correct"] is (not broken), res["check"]
