"""``archs/gat.py``: multi-head GAT with skips against the served path.

A tiny GAT cell (3 layers, 4 heads, the last averaged, skips, fanouts
(3, 3, 3)) runs through a whole run on the CPU, and its served answers
agree with the plain reference; the reference computed in bfloat16, put in
the program's place, fails a limit.  The work counts of ``gat-products``
are pinned as numbers, and GraphSAGE's pinned readings hold beside the
new module."""
import hashlib
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path.insert(0, ROOT)

from benchmarks.chip import (archs, harness, loadgen,  # noqa: E402
                             workcount, worldgen)

SEED = 2 ** 31 + 11
# SHA-256 of ``data/sage_parent_digest.json`` as pinned when GraphSAGE
# moved into ``archs/sage.py``
SAGE_DIGEST_FILE = ("06e201d6a9142a5ebef2a9b60c407dc1"
                    "9816d7658c697d47f1a724cb192303a8")
TRAFFIC = {"kind": "closed", "clients": 4, "seeds_per_request": 16,
           "popularity": {"law": "zipf", "s": 0.99}}


def config(name: str) -> dict:
    with open(os.path.join(harness.HERE, "configs", name + ".json")) as f:
        return json.load(f)


def tiny_cfg() -> dict:
    cfg = config("gat-products")
    cfg.update(name="tiny-gat", n_nodes=300, n_edges=1500, d_in=12,
               d_hidden=8, n_classes=5, fanouts=[3, 3, 3])
    return cfg


def test_tiny_gat_cell_serves_the_reference_answers():
    cell = harness.Cell("tiny-gat.closed", 1, tiny_cfg(), TRAFFIC,
                        [{"name": "seeds_per_s", "unit": "seeds/s"}], [])
    out = harness.run_cell(cell, SEED, 0.5, False, time.monotonic(),
                           require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["metrics"]["seeds_per_s"]["value"]
    assert out["checks"]["max_logit_gap_over_rms"]["value"] < 1e-4


def test_bf16_control_fails_a_limit_where_the_program_passes():
    cfg = tiny_cfg()
    world = worldgen.make_world(cfg, SEED)
    csr = world.host_csr()
    server = harness.build_server(cfg, world, csr, SEED)
    counter = harness.CompileCounter()
    try:
        server.warmup(loadgen.buckets_used(TRAFFIC, 16))
        win = harness.run_window(server, TRAFFIC, world.n_nodes, SEED, 0.5,
                                 counter)
    finally:
        counter.close()
        server.close()
    sample = harness.served_sample(win, SEED)
    program = harness.compare(cfg, world, csr, SEED, sample)
    control = harness.compare(cfg, world, csr, SEED, sample, "bf16")
    limits = {"rel_err": harness.REL_ERR_LIMIT,
              "bf16_share": harness.BF16_SHARE_LIMIT}
    assert program["seeds"] > 0
    assert all(program[k] <= v for k, v in limits.items()), program
    assert any(control[k] > v for k, v in limits.items()), control


def test_reference_attends_over_children_and_itself():
    """One seed with one valid child, one head of width 1: the seed's
    attention splits between the child and itself by the softmax of the
    two LeakyReLU scores."""
    import jax.numpy as jnp
    gat = archs.load("gat")
    one = {"w": np.ones((1, 1, 1), np.float32),
           "a_src": np.array([[1.0]], np.float32),
           "a_dst": np.array([[0.5]], np.float32),
           "b": np.zeros(1, np.float32), "w_skip": np.zeros((1, 1),
                                                            np.float32),
           "b_skip": np.zeros(1, np.float32)}
    x = [jnp.array([[[2.0]]]), jnp.array([[[-1.0], [7.0]]])]
    valid = [np.array([[True, False]])]
    got = float(gat.tree_forward({"layer0": one}, x, valid, [2])[0, 0])
    e_child = max(-1.0 + 1.0, 0.2 * (-1.0 + 1.0))
    e_self = 2.0 + 1.0
    w_child = np.exp(e_child) / (np.exp(e_child) + np.exp(e_self))
    assert got == pytest.approx(w_child * -1.0 + (1 - w_child) * 2.0,
                                rel=1e-6)


def test_gat_products_work_is_pinned():
    cfg = config("gat-products")
    gat = archs.of(cfg)
    assert gat.__file__ == archs.path("gat")
    assert gat.kernel_calls_per_layer(cfg) == 4
    assert gat.flops_per_seed(cfg) == 194_020_808
    work = gat.aggregation_work(cfg)
    assert work == [
        {"flops": 1_250_304, "bytes": 2_752_356},
        {"flops": 123_904, "bytes": 272_756},
        {"flops": 4_136, "bytes": 9_244},
    ]
    with open(os.path.join(harness.HERE, "peaks.json")) as f:
        peaks = json.load(f)["devices"]["TPU v5 lite"]
    floor = workcount.aggregation_floor_s(work, peaks["bf16_flops"],
                                          peaks["hbm_bytes_per_s"])
    assert floor == pytest.approx(sum(w["bytes"] for w in work) / 819e9,
                                  rel=1e-12)


def test_sage_readings_hold_beside_the_gat_module():
    """GraphSAGE's pinned digest file is untouched, and its tiny world
    and reference still match it with the GAT module loaded first."""
    with open(os.path.join(HERE, "data", "sage_parent_digest.json"),
              "rb") as f:
        assert hashlib.sha256(f.read()).hexdigest() == SAGE_DIGEST_FILE
    archs.load("gat")
    import test_chipbench_archs as sage_tests
    for name in sorted(sage_tests.PINNED["worlds"]):
        sage_tests.test_tiny_world_and_reference_match_the_pinned_digest(
            name)


def test_gustavson_ms_reads_kernel_time_per_batch():
    """On a recorded chip trace (sage-reddit, closed loop): the kernel's
    mean device time a call, times the calls a batch makes."""
    import test_chipbench_trace as traces
    from benchmarks.chip import tracereduce
    read = harness.reader("gustavson_ms")
    summary = tracereduce.summarize(traces.recorded()[0])
    calls = [v for name, v in summary.ops.items()
             if name.startswith("%_spmm_dedup_chunks.")]
    per_call = sum(s for _, s in calls) / sum(c for c, _ in calls)
    for name, per_batch in (("sage-reddit", 2), ("gat-products", 12)):
        ctx = {"trace": summary, "config": config(name)}
        assert read(ctx) == pytest.approx(1e3 * per_call * per_batch)
    assert read({"trace": None, "config": config("gat-products")}) is None
