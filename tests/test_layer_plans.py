"""Per-layer plans of the fused bucket step: each conv layer aggregates only
the rows the next layer reads, cut on the host from the bucket's one plan.

The trimmed step must answer every seed as the untrimmed forward over the
whole bucket plan does, on the ``dense`` executor and on the Gustavson
kernel (interpreted here); the cut must be a prefix of the bucket plan's
tables; and warming a server's bucket ladder must load one program per
bucket, as before the cut.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data import synthetic as syn
from repro.serve import compute
from repro.serve.buckets import all_buckets, build_bucket_structure
from repro.serve.compute import FeatureStore
from repro.sparse import stats as kstats
from repro.sparse.plan import plan_with_values, row_prefix_plan

N, E, D = 300, 2400, 12
LADDER = all_buckets(16)


def _models():
    from repro.models.gnn import gat, gcn, gin, sage
    return {
        "sage-2hop": ("sage", sage, sage.SAGEConfig(
            n_layers=2, d_in=D, d_hidden=8, n_classes=5), (4, 3)),
        "sage-3hop": ("sage", sage, sage.SAGEConfig(
            n_layers=3, d_in=D, d_hidden=8, n_classes=5), (3, 2, 2)),
        "gat": ("gat", gat, gat.GATConfig(
            n_layers=3, d_in=D, d_hidden=4, n_heads=4, n_classes=5,
            out_heads=4, skip=True), (3, 2, 2)),
        "gcn": ("gcn", gcn, gcn.GCNConfig(
            n_layers=2, d_in=D, d_hidden=8, n_classes=5), (3, 2)),
        # three layers over two hops: the first layer keeps every row
        "gin": ("gin", gin, gin.GINConfig(
            n_layers=3, d_in=D, d_hidden=8, n_classes=5), (3, 2)),
    }


def _inputs(struct, seed):
    """A bucket's node ids (a quarter of the lanes padding) and hop
    validity (a fifth of the edges invalid), drawn from ``seed``."""
    rng = np.random.default_rng(seed)
    node_ids = rng.integers(0, N, struct.n_nodes)
    node_ids[rng.random(struct.n_nodes) < 0.25] = -1
    hop_valid = rng.random(struct.n_hop_edges) >= 0.2
    return node_ids, hop_valid


def _untrimmed(arch, mod, cfg, store, struct, backend, params, node_ids,
               hop_valid):
    """Every layer over the whole bucket plan, the seed rows taken last."""
    plan = compute.bucket_plan(struct, backend, need_ell=True)
    ev = (np.concatenate([hop_valid, node_ids >= 0]) if struct.with_loops
          else hop_valid)
    w = None
    if arch == "gcn":
        deg = np.zeros(struct.n_nodes, np.float32)
        np.add.at(deg, struct.receivers, ev.astype(np.float32))
        dinv = 1.0 / np.sqrt(np.maximum(deg, 1.0))
        w = dinv[struct.senders] * dinv[struct.receivers]
    x = jnp.take(store.x, store.row_index(jnp.asarray(node_ids)), axis=0)
    pl = plan_with_values(plan, edge_weight=w, edge_valid=ev)
    forward = jax.jit(lambda p, x, pl: mod.forward(p, cfg, x,
                                                   backend=backend, plan=pl))
    return np.asarray(forward(params, x, pl))[:struct.n_seeds]


@pytest.mark.parametrize("backend", ["dense", "pallas"])
@pytest.mark.parametrize("model", ["sage-2hop", "sage-3hop", "gat", "gcn",
                                   "gin"])
def test_trimmed_step_answers_the_seeds_as_the_whole_bucket_does(model,
                                                                 backend):
    arch, mod, cfg, fanouts = _models()[model]
    params = mod.init_params(jax.random.key(0), cfg)
    x = np.random.default_rng(1).normal(size=(N, D)).astype(np.float32)
    store = FeatureStore.build(N, x=x)
    loops = arch in ("gcn", "gat")
    for bucket in LADDER:
        struct = build_bucket_structure(bucket, fanouts, with_loops=loops)
        node_ids, hop_valid = _inputs(struct, seed=bucket)
        step = compute.build_infer_step(arch, cfg, store, struct,
                                        backend=backend)
        got = np.asarray(step(params, node_ids, hop_valid))
        want = _untrimmed(arch, mod, cfg, store, struct, backend, params,
                          node_ids, hop_valid)
        assert got.shape == want.shape == (bucket, cfg.n_classes)
        gap = float(np.abs(got - want).max())
        assert gap <= 1e-5 * float(np.abs(want).max()), (model, bucket, gap)


@pytest.mark.parametrize("fanouts,loops,n_layers,rows", [
    ((10, 10, 10), True, 3, (1776, 176, 16)),    # gat-products
    ((15, 10, 5), False, 3, (2656, 256, 16)),    # sage-products
    ((25, 10), False, 2, (416, 16)),             # sage-reddit
    ((3, 2), False, 3, (160, 64, 16)),           # deeper than the tree
])
def test_layer_rows_are_the_levels_the_next_layer_reads(fanouts, loops,
                                                        n_layers, rows):
    struct = build_bucket_structure(16, fanouts, with_loops=loops)
    assert compute.layer_rows(struct, n_layers) == rows


@pytest.mark.parametrize("bucket", LADDER)
def test_a_layer_plan_is_the_bucket_plans_table_prefix(bucket):
    struct = build_bucket_structure(bucket, (3, 2, 2), with_loops=True)
    plan = compute.bucket_plan(struct, "pallas", need_ell=True)
    cuts = compute.layer_plans(struct, "pallas", 3)
    n_in = struct.n_nodes
    for (cut, edges), r in zip(cuts, compute.layer_rows(struct, 3)):
        assert (cut.n_rows, cut.out_rows) == (n_in, r)
        np.testing.assert_array_equal(edges,
                                      np.flatnonzero(struct.receivers < r))
        np.testing.assert_array_equal(cut.rows, plan.rows[edges])
        np.testing.assert_array_equal(cut.cols, plan.cols[edges])
        assert cut.cols.max() < n_in
        for pre, n_out, reads in (("ell_", r, n_in), ("ell_t_", n_in, r)):
            k = getattr(cut, pre + "u_cols").shape[0]
            assert getattr(cut, "n_blocks" if pre == "ell_" else
                           "n_t_blocks") == -(-n_out // plan.block_rows)
            for f in ("remaining", "out_block", "first"):
                np.testing.assert_array_equal(getattr(cut, pre + f),
                                              getattr(plan, pre + f)[:k])
            whole = getattr(plan, pre + "u_cols")[:k]
            # operands no kept row reads point at row 0; the rest are kept
            np.testing.assert_array_equal(getattr(cut, pre + "u_cols"),
                                          np.where(whole < reads, whole, 0))
            slots = getattr(plan, pre + "slots")[edges]
            np.testing.assert_array_equal(getattr(cut, pre + "slots"), slots)
            assert slots.max() < getattr(cut, pre + "a").size
        # the forward tiles hold exactly the bucket's, row for kept row
        a, whole = cut.ell_a, plan.ell_a[:cut.ell_a.shape[0]]
        rloc = np.arange(a.shape[0]) % plan.block_rows
        kept = cut.ell_out_block.repeat(plan.block_rows) * plan.block_rows \
            + rloc < r
        np.testing.assert_array_equal(a[kept], whole[kept])
        assert not a[~kept].any()
        n_in = r


def test_a_full_cut_is_the_plan_itself():
    struct = build_bucket_structure(4, (3, 2))
    plan = compute.bucket_plan(struct, "pallas", need_ell=True)
    cut, edges = row_prefix_plan(plan, struct.n_nodes, struct.n_nodes)
    assert cut is plan
    np.testing.assert_array_equal(edges, np.arange(struct.n_edges))


def test_a_cut_refuses_rows_that_read_past_its_input():
    struct = build_bucket_structure(4, (3, 2))
    plan = compute.bucket_plan(struct, "dense", need_ell=False)
    with pytest.raises(ValueError, match="read rows past"):
        row_prefix_plan(plan, 16, 10)     # level 1 reads level 2's rows


def test_each_bucket_packs_once_and_records_its_layer_rows(monkeypatch):
    monkeypatch.setattr(compute, "_BUCKET_PLANS",
                        compute.StepCache(compute._build_bucket_plan, 32))
    monkeypatch.setattr(compute, "_LAYER_PLANS",
                        compute.StepCache(compute._build_layer_plans, 32))
    kstats.reset()
    fanouts = (3, 2, 2)
    structs = [build_bucket_structure(b, fanouts, with_loops=True)
               for b in LADDER]
    for struct in structs:
        compute.layer_plans(struct, "pallas", 3)
        compute.layer_plans(struct, "pallas", 3)     # cached: no new cut
    snap = kstats.stats(include_caches=False)
    assert snap["counters"]["plan.dedup_packs"] == 2 * len(LADDER)
    rows = [compute.layer_rows(s, 3) for s in structs]
    assert snap["series"]["plan.layer_rows"]["sample"] == [
        float(r) for rs in rows for r in rs]
    assert snap["counters"]["plan.rows_trimmed"] == sum(
        s.n_nodes - r for s, rs in zip(structs, rows) for r in rs)


def test_a_distributed_bucket_keeps_the_whole_plan_in_every_layer():
    struct = build_bucket_structure(2, (3, 2))
    cuts = compute.layer_plans(struct, "distributed", 2)
    plan = compute.bucket_plan(struct, "distributed", need_ell=True)
    assert all(cut is plan for cut, _ in cuts)


_COUNT_SCRIPT = textwrap.dedent("""
    import json, sys
    import jax, jax.monitoring
    import numpy as np
    from repro.data import synthetic as syn
    from repro.models.gnn import gat, sage
    from repro.serve import GNNServer
    from repro.serve.compute import FeatureStore
    from repro.sparse.graph import coo_to_csr

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, dur, **kw: compiles.append(ev)
        if ev == "/jax/core/compile/backend_compile_duration" else None)
    n = 200
    s, r = syn.powerlaw_graph(n, 2000, seed=0)
    indptr, indices = coo_to_csr(s, r, n)[:2]
    x = np.random.default_rng(0).normal(size=(n, 24)).astype(np.float32)
    out = {}
    for arch, mod, cfg in (
            ("gat", gat, gat.GATConfig(n_layers=3, d_in=24, d_hidden=8,
                                       n_heads=2, n_classes=5, out_heads=2,
                                       skip=True)),
            ("sage", sage, sage.SAGEConfig(n_layers=3, d_in=24,
                                           d_hidden=16, n_classes=5))):
        params = mod.init_params(jax.random.key(0), cfg)
        store = FeatureStore.build(n, x=x)
        del compiles[:]
        server = GNNServer(arch, cfg, params, indptr, indices, store,
                           fanouts=(3, 2, 2), backend="pallas",
                           sampler="device", max_batch_seeds=16)
        server.warmup()
        server.close()
        out[arch] = len(compiles)
    print(json.dumps(out))
""")


def test_warming_the_ladder_compiles_one_program_a_bucket():
    """In a fresh process (no program compiled before it), a server's
    set-up and the warm-up of its five buckets compile five programs: the
    fused step of each bucket, and nothing eager beside them."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    res = subprocess.run([sys.executable, "-c", _COUNT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, res.stderr[-3000:]
    counts = json.loads(res.stdout.strip().splitlines()[-1])
    assert counts == {"gat": len(LADDER), "sage": len(LADDER)}


def test_a_bucket_step_lowers_to_the_same_module_after_a_fresh_pack(
        monkeypatch):
    """The persistent cache keys a program by its module text: packing and
    cutting a bucket's plans again (as a new process does) must give the
    same text."""
    from repro.models.gnn import gat
    cfg = gat.GATConfig(n_layers=3, d_in=D, d_hidden=4, n_heads=2,
                        n_classes=5, out_heads=2, skip=True)
    params = gat.init_params(jax.random.key(0), cfg)
    x = np.random.default_rng(1).normal(size=(N, D)).astype(np.float32)
    store = FeatureStore.build(N, x=x)
    struct = build_bucket_structure(8, (3, 2, 2), with_loops=True)
    node_ids, hop_valid = _inputs(struct, seed=0)
    texts = []
    for _ in range(2):
        monkeypatch.setattr(compute, "_BUCKET_PLANS",
                            compute.StepCache(compute._build_bucket_plan, 32))
        monkeypatch.setattr(compute, "_LAYER_PLANS",
                            compute.StepCache(compute._build_layer_plans,
                                              32))
        step = compute.build_infer_step("gat", cfg, store, struct,
                                        backend="pallas")
        texts.append(step.func.lower(*step.args, params, node_ids,
                                     hop_valid).as_text())
    assert texts[0] == texts[1]


def test_one_plan_forward_is_unchanged_by_the_per_layer_form():
    """A model given one plan runs every layer on it, as given a list of
    that plan once a layer."""
    from repro.models.gnn import gat
    cfg = gat.GATConfig(n_layers=2, d_in=D, d_hidden=4, n_heads=2,
                        n_classes=5)
    params = gat.init_params(jax.random.key(0), cfg)
    s, r = syn.powerlaw_graph(N, E, seed=3)
    x = jnp.asarray(np.random.default_rng(2).normal(size=(N, D)),
                    jnp.float32)
    from repro.sparse.plan import make_plan
    plan = make_plan(s, r, N, backends=("dense", "pallas"))
    for backend in ("dense", "pallas"):
        one = gat.forward(params, cfg, x, backend=backend, plan=plan)
        per = gat.forward(params, cfg, x, backend=backend,
                          plan=[plan, plan])
        np.testing.assert_array_equal(np.asarray(one), np.asarray(per))
    with pytest.raises(ValueError, match="3 plans for 2 layers"):
        gat.forward(params, cfg, x, plan=[plan] * 3)
