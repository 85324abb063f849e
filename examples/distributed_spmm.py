"""DRHM-sharded decoupled SpMM across 8 (emulated) devices — the paper's
NeuraCore/NeuraMem dataflow at pod scale, plus the ring-pipelined
rolling-eviction schedule.

  PYTHONPATH=src python examples/distributed_spmm.py
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax                      # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402

from repro.core import distributed, drhm   # noqa: E402


def main():
    rng = np.random.default_rng(0)
    n, e, d = 4096, 65536, 64
    rows = rng.integers(0, n, e)
    cols = rng.integers(0, n, e)
    vals = rng.normal(size=e).astype(np.float32)
    x = rng.normal(size=(n, d)).astype(np.float32)

    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    plan = distributed.plan_distributed_spmm(rows, cols, vals, n,
                                             n_shards=4, ring=True)
    print(f"DRHM plan: {plan.n_shards} shards × {plan.rows_per_shard} rows, "
          f"{plan.edges_per_shard} edges/shard (exact balance), "
          f"ring cell pad {plan.e_blk}")
    xp = jnp.asarray(distributed.permute_features(x, plan))

    ag = distributed.make_allgather_spmm(mesh, plan)     # paper-faithful
    ring = distributed.make_ring_spmm(mesh, plan)        # overlap schedule
    with jax.set_mesh(mesh):
        y1 = ag(xp, jnp.asarray(plan.rows_local),
                jnp.asarray(plan.cols_perm), jnp.asarray(plan.vals))
        y2 = ring(xp, jnp.asarray(plan.ring_rows),
                  jnp.asarray(plan.ring_cols), jnp.asarray(plan.ring_vals))
    print("allgather vs ring max err:",
          float(jnp.abs(y1 - y2).max()))

    # hot-spot metric under the four mappings (paper Fig 12/13)
    tags = jnp.asarray(rows)
    gamma = drhm.reseed(jax.random.key(0))
    lut = jax.random.randint(jax.random.key(1), (n,), 0, 32)
    for name in ("ring", "modular", "random", "drhm"):
        a = drhm.MAPPINGS[name](tags, 32, gamma=gamma, lookup=lut)
        print(f"  {name:8s} imbalance (max/mean): "
              f"{float(drhm.imbalance(a, 32)):.3f}")


if __name__ == "__main__":
    main()
