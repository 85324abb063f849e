"""The reduction from a profiler trace to busy time, kernel time and idle
gaps (``tracereduce``), on a hand-made trace and on a recorded one."""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip import tracereduce as tr  # noqa: E402

E = tr.Event
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def hand_made():
    return {
        "/host:CPU": {
            "bench": [E("bench.window", 100, 1100)],
            "engine": [E("dispatch", 150, 260), E("sync", 700, 990)],
        },
        "/device:TPU:0": {
            "XLA Ops": [E("fusion.1", 50, 120),       # half before window
                        E("gustavson_spmm", 300, 400),
                        E("fusion.2", 380, 500),      # overlaps the kernel
                        E("gustavson_spmm", 600, 650),
                        E("copy.3", 1050, 1200)],     # runs past the end
            "XLA Modules": [E("jit_fused(7)", 290, 660)],
        },
    }


def test_busy_idle_and_kernel_time_by_hand():
    s = tr.summarize(hand_made())
    assert s.window_s == pytest.approx(1000e-9)
    # busy: [100,120] + [300,500] + [600,650] + [1050,1100] = 320 ns
    assert s.busy_s == pytest.approx(320e-9)
    assert s.idle_share == pytest.approx(0.68)
    assert s.ops["gustavson_spmm"] == pytest.approx([2, 150e-9])
    assert s.modules["jit_fused(7)"] == pytest.approx([1, 370e-9])
    assert s.top_ops(1) == [["gustavson_spmm", pytest.approx(150e-9)]]
    # gaps [120,300] -> dispatch (110 ns of overlap), [500,600] -> none,
    # [650,1050] -> sync
    gaps = dict(s.idle_gaps)
    assert gaps["sync"] == pytest.approx(400e-9)
    assert gaps["dispatch"] == pytest.approx(180e-9)
    assert gaps["host idle"] == pytest.approx(100e-9)


def test_no_window_mark_is_an_error():
    planes = hand_made()
    planes["/host:CPU"]["bench"] = []
    with pytest.raises(ValueError):
        tr.summarize(planes)


def recorded():
    """Every event of a 3 ms slice of a chip trace (sage-reddit, closed
    loop, TPU v5 lite), with the window mark over that slice."""
    with open(os.path.join(DATA, "trace_reddit_closed.json")) as f:
        rec = json.load(f)
    lo, hi = rec["window_ns"]
    planes = {p: {ln: [E(*e) for e in evs] for ln, evs in lines.items()}
              for p, lines in rec["planes"].items()}
    planes.setdefault("/host:CPU", {})["bench"] = [E(tr.WINDOW_MARK, lo, hi)]
    return planes, lo, hi


def test_recorded_trace_busy_and_kernel_time():
    planes, lo, hi = recorded()
    s = tr.summarize(planes)
    ops = planes["/device:TPU:0"][tr.OPS_LINE]
    # busy, counted a nanosecond at a time
    busy = np.zeros(int(hi - lo), bool)
    for e in ops:
        a, b = max(e.start_ns, lo) - lo, min(e.end_ns, hi) - lo
        if b > a:
            busy[int(a):int(b)] = True
    assert s.window_s == pytest.approx(3e-3)
    assert s.busy_s == pytest.approx(busy.sum() / 1e9, abs=2e-9)
    assert 0.0 < s.idle_share < 1.0
    assert sum(sec for _, sec in s.idle_gaps) == \
        pytest.approx(s.window_s - s.busy_s, abs=2e-9)
    kernel = [e for e in ops if e.name.startswith("%_spmm_dedup_chunks.")]
    assert kernel
    want = sum(min(e.end_ns, hi) - max(e.start_ns, lo) for e in kernel)
    got = sum(sec for name, (_, sec) in s.ops.items()
              if name.startswith("%_spmm_dedup_chunks."))
    assert got == pytest.approx(want / 1e9)
