"""End-to-end numerical check of the §Perf optimized paths:

1. the DRHM-sharded GCN train step (launch/variants.py) computes the SAME
   loss/gradients as the local GCN step on identical data (8 fake devices);
2. elastic rescale: checkpoint written under one mesh restores onto a
   different device count.
Run in a subprocess so the XLA device-count flag cannot leak.
"""
import os
import subprocess
import sys
from pathlib import Path

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import numpy as np, jax, jax.numpy as jnp
from repro.configs import registry
from repro.core import distributed
from repro.launch import variants
from repro.models.gnn import gcn
from repro.optim import adamw
from repro.sparse.graph import sym_norm_weights

# ---- tiny graph, full local reference ----
rng = np.random.default_rng(0)
n, e, d_in, n_cls = 60, 300, 12, 4
s = rng.integers(0, n, e); r = rng.integers(0, n, e)
s2, r2, w = sym_norm_weights(s, r, n, add_self_loops=False)
x = rng.normal(size=(n, d_in)).astype(np.float32)
y = rng.integers(0, n_cls, n).astype(np.int32)
mask = np.zeros(n, bool); mask[:30] = True

cfg = gcn.GCNConfig(n_layers=2, d_in=d_in, d_hidden=8, n_classes=n_cls)
params = gcn.init_params(jax.random.key(0), cfg)

ref_loss = gcn.loss_fn(params, cfg, jnp.asarray(x), jnp.asarray(s2),
                       jnp.asarray(r2), jnp.asarray(w),
                       jnp.ones(len(s2), bool), jnp.asarray(y),
                       jnp.asarray(mask))

# ---- DRHM-sharded step on a (4, 2) mesh ----
AUTO = (jax.sharding.AxisType.Auto,) * 2
mesh = jax.make_mesh((4, 2), ("data", "model"), axis_types=AUTO)
# aggregation direction: rows=receivers, cols=senders
plan = distributed.plan_distributed_spmm(r2, s2, w, n, n_shards=4)
xp = distributed.permute_features(x, plan)
yp = np.zeros(plan.n_pad, np.int32); yp[plan.perm[:n]] = y
mp = np.zeros(plan.n_pad, bool);     mp[plan.perm[:n]] = mask

batch = {"x_perm": jnp.asarray(xp), "labels_perm": jnp.asarray(yp),
         "mask_perm": jnp.asarray(mp),
         "rows_local": jnp.asarray(plan.rows_local),
         "cols_perm": jnp.asarray(plan.cols_perm),
         "vals": jnp.asarray(plan.vals)}
step = variants.build_gcn_drhm_step(cfg, mesh, plan.n_pad, ring=False,
                                    opt_cfg=adamw.AdamWConfig(lr=1e-2))
opt = adamw.init_state(params)
with jax.set_mesh(mesh):
    new_p, new_o, metrics = jax.jit(step)(params, opt, batch)
err = abs(float(metrics["loss"]) - float(ref_loss))
assert err < 1e-4, f"DRHM step loss mismatch: {err}"
assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(new_p))
print("VARIANT_LOSS_OK", float(ref_loss))

# ---- elastic rescale: save under 8-device mesh, restore under 1 device ----
from repro.checkpoint import store
import tempfile
tmp = tempfile.mkdtemp()
store.save(tmp, 1, (new_p, new_o))
mesh1 = jax.make_mesh((1, 1), ("data", "model"), axis_types=AUTO)
from jax.sharding import NamedSharding, PartitionSpec as P
like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                    (new_p, new_o))
sh = jax.tree.map(lambda a: NamedSharding(mesh1, P()), like)
(rp, ro), _ = store.restore(tmp, 1, like, shardings=sh)
for a, b in zip(jax.tree.leaves(new_p), jax.tree.leaves(rp)):
    assert np.allclose(np.asarray(a), np.asarray(b)), "elastic restore drift"
print("ELASTIC_OK")
"""


def test_variants_subprocess():
    repo = Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, "-c", SCRIPT], capture_output=True, text=True,
        # JAX_PLATFORMS must survive into the child: without it jax probes
        # the TPU library and fails on host bounds
        env={"PYTHONPATH": str(repo / "src"), "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", str(repo)),
             "JAX_PLATFORMS": os.environ.get("JAX_PLATFORMS", "cpu")},
        timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "VARIANT_LOSS_OK" in proc.stdout
    assert "ELASTIC_OK" in proc.stdout
