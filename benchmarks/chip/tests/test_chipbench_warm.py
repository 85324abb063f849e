"""The measured process loads its programs from the persistent cache: the
first run of a cell in a checkout fills the cache from a child process
(``run.py --warm-only``), once, and a failed child stops the run."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness  # noqa: E402


class FakeRun:
    def __init__(self, rc):
        self.rc, self.calls = rc, []

    def __call__(self, cmd, **kw):
        self.calls.append((cmd, kw))
        return subprocess.CompletedProcess(cmd, self.rc)


def test_the_child_fills_the_cache_once(tmp_path, monkeypatch):
    fake = FakeRun(0)
    monkeypatch.setattr(harness.subprocess, "run", fake)
    cell = harness.load_cell("sage-reddit.saturate")
    harness.warm_in_child(cell, 2 ** 31 + 5, str(tmp_path))
    harness.warm_in_child(cell, 2 ** 31 + 6, str(tmp_path))
    assert len(fake.calls) == 1
    cmd, kw = fake.calls[0]
    assert cmd[:2] == [sys.executable, os.path.join(harness.HERE, "run.py")]
    assert cmd[2:] == ["--workload", "sage-reddit.saturate", "--seed",
                       str(2 ** 31 + 5), "--seconds", "0", "--warm-only"]
    # the parent's last line of standard output stays its result
    assert kw["stdout"] is sys.stderr
    assert (tmp_path / "chipbench-warm-sage-reddit.saturate").exists()


def test_a_failed_child_stops_the_run_and_leaves_no_mark(tmp_path,
                                                         monkeypatch):
    fake = FakeRun(2)
    monkeypatch.setattr(harness.subprocess, "run", fake)
    cell = harness.load_cell("sage-products.poisson")
    for _ in range(2):
        with pytest.raises(harness.HarnessError, match="exited with 2"):
            harness.warm_in_child(cell, 7, str(tmp_path))
    assert len(fake.calls) == 2
    assert not list(tmp_path.iterdir())


def test_warm_only_without_a_chip_exits_nonzero_with_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "sage-reddit.saturate", "--seed", "3", "--seconds", "0",
         "--warm-only"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr
