"""NeuraScope over a real flight recorder (``launch/neurascope.py``).

One traced ``ClusterServer`` run with a scripted lane kill writes its
telemetry + span-tree JSONL; the recorder must hold the failover's events,
the hub's samples and one well-formed span tree per request, and
NeuraScope's ``--summary --check``, HTML and ``--live`` modes must all run
off it.  ``--check`` must also fail on a malformed recorder.
"""
import json
import time

import pytest

from repro.launch import neurascope
from repro.launch.gnn_serve import build_world
from repro.serve import ChaosInjector, ClusterServer, LaneFault, verify_traces

N_LANES = 4


@pytest.fixture(scope="module")
def flight(tmp_path_factory):
    """(recorder path, settled requests) of a lane-kill run that restarts
    the killed lane before it closes."""
    path = str(tmp_path_factory.mktemp("flight") / "flight.jsonl")
    cfg, params, indptr, indices, store = build_world("sage", 256, 1024, 8,
                                                      seed=0)
    chaos = ChaosInjector(lane_faults=[LaneFault(lane=2, at_round=3)])
    srv = ClusterServer("sage", cfg, params, indptr, indices, store,
                        n_lanes=N_LANES, fanouts=(2, 2), backend="dense",
                        max_batch_seeds=4, seed=0, chaos=chaos,
                        telemetry_jsonl=path, telemetry_interval=0.02,
                        tracing=True, stall_timeout=0.15, restart_after=0.2)
    with srv:
        srv.warmup()
        reqs = srv.submit_many([[i % 256] for i in range(128)])
        srv.drain(timeout=120)
        deadline = time.monotonic() + 30
        while srv.router.n_active < N_LANES and time.monotonic() < deadline:
            time.sleep(0.02)
        assert srv.router.n_active == N_LANES, srv.lane_states()
    return path, reqs


def test_flight_recorder_holds_the_failover(flight):
    path, reqs = flight
    assert all(r.done and r.error is None and r.n_settles == 1
               for r in reqs)
    recs, meta = neurascope.load_flight(path)
    assert not meta["version_errors"] and not meta["bad_lines"]
    events = {e["event"] for e in recs["event"]}
    assert {"lane_dead", "lane_restored"} <= events
    assert recs["sample"]
    assert len(recs["trace"]) >= len(reqs)
    assert verify_traces(recs["trace"]) == []


def test_neurascope_summary_and_check_pass(flight, capsys):
    path, _ = flight
    assert neurascope.main([path, "--summary", "--check"]) == 0
    out = capsys.readouterr().out
    assert "neurascope check OK" in out
    assert "lane_dead=1" in out


def test_neurascope_renders_html(flight, tmp_path):
    path, _ = flight
    out = tmp_path / "report" / "neurascope.html"
    assert neurascope.main([path, "--out", str(out)]) == 0
    doc = out.read_text()
    assert doc.startswith("<!doctype html>") and doc.endswith("</html>")
    assert "<svg" in doc and "lane_dead" in doc


def test_neurascope_live_renders_frames(flight, capsys):
    path, _ = flight
    assert neurascope.main([path, "--live", "--frames", "2",
                            "--interval", "0.01"]) == 0
    assert capsys.readouterr().out


def test_neurascope_check_fails_on_malformed_recorder(flight, tmp_path):
    path, _ = flight
    lines = open(path).read().splitlines()
    first = next(i for i, ln in enumerate(lines)
                 if json.loads(ln).get("kind") == "trace")
    bad = json.loads(lines[first])
    bad["spans"] = bad["spans"] + bad["spans"][-1:]    # a second terminal
    lines[first] = json.dumps(bad)
    broken = tmp_path / "broken.jsonl"
    broken.write_text("\n".join(lines) + "\n")
    assert neurascope.main([str(broken), "--check"]) != 0
    assert neurascope.main([str(tmp_path / "absent.jsonl"), "--check"]) == 2
