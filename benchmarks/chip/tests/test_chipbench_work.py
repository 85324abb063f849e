"""Work counts of the benchmark (``workcount`` and GraphSAGE's
``archs/sage.py``) against hand counts."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmarks.chip import archs, workcount  # noqa: E402

sage = archs.load("sage")
# widths (4, 5, 6), fanouts (2, 3)
TINY = {"arch": "sage", "fanouts": [2, 3], "d_in": 4, "d_hidden": 5,
        "n_classes": 6, "n_layers": 2}


def test_layer_shapes_tiny_bucket():
    # fanouts (2, 3): levels of 1, 2, 6 nodes per seed
    shapes = workcount.layer_shapes((2, 3), (4, 5, 6))
    assert shapes == [
        {"nodes": 3, "edges": 8, "d_in": 4, "d_out": 5},   # levels 0-1
        {"nodes": 1, "edges": 2, "d_in": 5, "d_out": 6},   # level 0
    ]


def test_flops_per_seed_by_hand():
    # layer 0: 4*3*4*5 + 2*8*4 = 240 + 64; layer 1: 4*1*5*6 + 2*2*5
    assert sage.layer_dims(TINY) == (4, 5, 6)
    assert sage.flops_per_seed(TINY) == 304 + 140


def test_aggregation_work_and_floor_by_hand():
    work = sage.aggregation_work(TINY)
    assert work[0] == {"flops": 64, "bytes": 4 * (8 * 4 + 3 * 4 + 8)}
    assert work[1] == {"flops": 20, "bytes": 4 * (2 * 5 + 1 * 5 + 2)}
    # bytes bound at 1 B/s against 1e9 FLOP/s: the floor is the bytes
    assert workcount.aggregation_floor_s(work, 1e9, 1.0) == \
        4 * (52 + 17)
    # FLOPs bound when bytes are free
    assert workcount.aggregation_floor_s(work, 1.0, 1e30) == \
        pytest.approx(84.0)


def test_mfu_reader_by_hand():
    from benchmarks.chip import harness

    import numpy as np
    req = {"ok": np.array([1, 1, 0, 1]), "done": np.array([1.0, 1.5, np.nan,
                                                            2.5]),
           "n_seeds": np.array([16, 16, 16, 16])}    # 32 served inside
    ctx = {"config": TINY,
           "peaks": {"bf16_flops": 1e6}, "requests": req,
           "t_end": 2.0, "seconds": 2.0}
    got = harness.reader("mfu")(ctx)
    assert got == pytest.approx(100.0 * 444 * 32 / 2.0 / 1e6)


def test_gustavson_roofline_reader_by_hand():
    from benchmarks.chip import harness, tracereduce
    summary = tracereduce.Summary(
        window_s=1.0, busy_s=0.5, n_devices=1,
        ops={"%_spmm_dedup_chunks.2 = f32[8,128] custom-call(s32[4] %a)":
             [4, 2e-3],
             "%fusion.3 = f32[8,6] fusion(f32[8,128] %_spmm_dedup_chunks.2)":
             [2, 1.0]},
        modules={}, idle_gaps=[])
    ctx = {"trace": summary, "peaks": {"bf16_flops": 1e12,
                                       "hbm_bytes_per_s": 1e9},
           "config": TINY,
           "batches": 10, "seeds_submitted": 40}
    # 4 kernel calls over 2 layers = 2 steps of 4 seeds: 8 seeds, each
    # needing (52 + 17) * 4 bytes at 1e9 B/s
    want = 100.0 * 8 * 4 * 69 / 1e9 / 2e-3
    assert harness.reader("gustavson_roofline")(ctx) == pytest.approx(want)
    # no kernel in the trace: nothing to read, never 0
    summary.ops = {"%fusion.3 = f32[8,6] fusion(f32[8,128] "
                   "%_spmm_dedup_chunks.2)": [2, 1.0]}
    assert harness.reader("gustavson_roofline")(ctx) is None


def test_gustavson_roofline_counts_kernel_calls_per_layer(tmp_path,
                                                          monkeypatch):
    # an architecture that calls the kernel once per head, 4 heads a
    # layer: each call serves a quarter of the seeds of a GraphSAGE call
    from benchmarks.chip import harness, tracereduce
    src = open(archs.path("sage")).read()
    one = "aggregates a layer's neighbours for the batch.\"\"\"\n    return 1\n"
    assert src.count(one) == 1
    (tmp_path / "sage-heads4.py").write_text(
        src.replace(one, one.replace("return 1", "return 4")))
    monkeypatch.setattr(archs, "DIR", str(tmp_path))
    summary = tracereduce.Summary(
        window_s=1.0, busy_s=0.5, n_devices=1,
        ops={"%_spmm_dedup_chunks.2 = f32[8,128] custom-call(s32[4] %a)":
             [16, 2e-3]},
        modules={}, idle_gaps=[])
    ctx = {"trace": summary, "peaks": {"bf16_flops": 1e12,
                                       "hbm_bytes_per_s": 1e9},
           "config": dict(TINY, arch="sage-heads4"),
           "batches": 10, "seeds_submitted": 40}
    # 16 calls over 2 layers x 4 heads = 2 steps of 4 seeds: 8 seeds
    want = 100.0 * 8 * 4 * 69 / 1e9 / 2e-3
    assert harness.reader("gustavson_roofline")(ctx) == pytest.approx(want)

def test_open_cell_step_readers_by_hand():
    from benchmarks.chip import harness, tracereduce
    summary = tracereduce.Summary(
        window_s=1.0, busy_s=0.25, n_devices=1, ops={},
        modules={"jit_fused(123)": [5, 0.01], "jit_other": [9, 1.0]},
        idle_gaps=[])
    ctx = {"trace": summary, "peaks": {"bf16_flops": 1e6},
           "config": TINY,
           "seeds_submitted": 20}
    assert harness.reader("step_ms.open")(ctx) == pytest.approx(2.0)
    # 20 seeds of 444 FLOPs over 0.01 s of steps at 1e6 FLOP/s
    assert harness.reader("step_mfu.open")(ctx) == \
        pytest.approx(100.0 * 444 * 20 / 0.01 / 1e6)
    assert harness.reader("idle_share.open")(ctx) == pytest.approx(75.0)
    summary.modules = {"jit_other": [9, 1.0]}
    assert harness.reader("step_ms.open")(ctx) is None
    assert harness.reader("step_mfu.open")(ctx) is None
