"""Distributed SpMM (shard_map, 8 fake devices).

Runs DIRECTLY when the interpreter already has ≥8 devices (the CI
multi-device leg sets ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
process-wide); otherwise falls back to a subprocess so the flag never leaks
into other tests.
"""
import os
import subprocess
import sys
from pathlib import Path

import pytest

BODY = r"""
import numpy as np, jax, jax.numpy as jnp
from repro.core import distributed

rng = np.random.default_rng(1)
n, e, d = 96, 700, 32
rows = rng.integers(0, n, e); cols = rng.integers(0, n, e)
vals = rng.normal(size=e).astype(np.float32)
x = rng.normal(size=(n, d)).astype(np.float32)
dense = np.zeros((n, n), np.float32); np.add.at(dense, (rows, cols), vals)
ref = dense @ x

mesh = jax.make_mesh((4, 2), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
plan = distributed.plan_distributed_spmm(rows, cols, vals, n, n_shards=4,
                                         ring=True)
xp = distributed.permute_features(x, plan)

f = distributed.make_allgather_spmm(mesh, plan)
with jax.set_mesh(mesh):
    y = f(jnp.asarray(xp), jnp.asarray(plan.rows_local),
          jnp.asarray(plan.cols_perm), jnp.asarray(plan.vals))
err = abs(distributed.unpermute_features(np.asarray(y), plan, n) - ref).max()
assert err < 1e-4, f"allgather spmm err {err}"

g = distributed.make_ring_spmm(mesh, plan)
with jax.set_mesh(mesh):
    y2 = g(jnp.asarray(xp), jnp.asarray(plan.ring_rows),
           jnp.asarray(plan.ring_cols), jnp.asarray(plan.ring_vals))
err2 = abs(distributed.unpermute_features(np.asarray(y2), plan, n) - ref).max()
assert err2 < 1e-4, f"ring spmm err {err2}"

# gradients agree between the two schedules
def loss_ag(xp_):
    return jnp.sum(f(xp_, jnp.asarray(plan.rows_local),
                     jnp.asarray(plan.cols_perm), jnp.asarray(plan.vals))**2)
def loss_ring(xp_):
    return jnp.sum(g(xp_, jnp.asarray(plan.ring_rows),
                     jnp.asarray(plan.ring_cols),
                     jnp.asarray(plan.ring_vals))**2)
with jax.set_mesh(mesh):
    g1 = jax.grad(loss_ag)(jnp.asarray(xp))
    g2 = jax.grad(loss_ring)(jnp.asarray(xp))
gerr = float(jnp.abs(g1 - g2).max())
assert gerr < 1e-3, f"grad mismatch {gerr}"

# exact per-shard balance (DRHM bijection)
assert plan.rows_local.size == plan.n_shards * plan.edges_per_shard

# compressed psum matches plain psum within int8 tolerance
from jax.sharding import PartitionSpec as P
from repro.optim.compression import compressed_psum
def ps(z):
    return jax.lax.psum(z, "data")
def cps(z):
    return compressed_psum(z, "data")
z = rng.normal(size=(8, 64)).astype(np.float32)
sm_ps = jax.shard_map(ps, mesh=mesh, in_specs=P("data"), out_specs=P())
sm_cps = jax.shard_map(cps, mesh=mesh, in_specs=P("data"), out_specs=P())
with jax.set_mesh(mesh):
    a = sm_ps(jnp.asarray(z))
    b = sm_cps(jnp.asarray(z))
cerr = float(jnp.abs(a - b).max() / (jnp.abs(a).max() + 1e-9))
assert cerr < 0.05, f"compressed psum rel err {cerr}"
print("DISTRIBUTED_OK")
"""

SCRIPT = ('import os\n'
          'os.environ["XLA_FLAGS"] = '
          '"--xla_force_host_platform_device_count=8"\n' + BODY)


def test_distributed_spmm_direct():
    """The multi-device CI leg exercises the distributed executor in-process
    (no subprocess indirection)."""
    import jax
    if jax.device_count() < 8:
        pytest.skip("needs 8 devices (CI multi-device leg)")
    exec(compile(BODY, "<distributed-checks>", "exec"), {})


def test_distributed_spmm_subprocess():
    import jax
    if jax.device_count() >= 8:
        pytest.skip("direct multi-device test covers this")
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        # JAX_PLATFORMS must survive into the child: without it jax may
        # probe accelerator backends (e.g. a baked-in libtpu) and hang for
        # minutes on metadata timeouts
        env={"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "DISTRIBUTED_OK" in proc.stdout
