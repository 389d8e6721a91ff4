"""Collective-matmul overlap primitives: equivalence vs plain matmul on a
fake 8-device mesh (subprocess — tests must see 1 device by default)."""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.distributed.overlap import make_overlapped_ops

    mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("data", "model"))
    ag, rs = make_overlapped_ops(mesh, "model")
    rng = np.random.default_rng(0)

    # ag_matmul: Y = all_gather(X_rowsharded) @ W
    x = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 24)), jnp.float32)
    with jax.set_mesh(mesh):
        y = jax.jit(ag)(x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)

    # matmul_rs: Y = reduce_scatter(X @ W) with contraction sharded
    x2 = jnp.asarray(rng.standard_normal((32, 64)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((64, 8)), jnp.float32)
    with jax.set_mesh(mesh):
        y2 = jax.jit(rs)(x2, w2)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2),
                               rtol=1e-4, atol=1e-4)
    print("OVERLAP_OK")
""")


def test_collective_matmul_equivalence():
    env = dict(os.environ,
               PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert "OVERLAP_OK" in out.stdout, (out.stdout[-500:], out.stderr[-2000:])
