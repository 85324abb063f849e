"""The load generator: deterministic per seed, on rate on a virtual
clock, closed loop holding its callers."""
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))))

from benchmarks.chip import loadgen  # noqa: E402

OPEN = {"kind": "open_poisson", "rate_per_s": 500.0, "seeds_per_request": 1,
        "popularity": {"law": "zipf", "s": 0.99}}
CLOSED = {"kind": "closed", "clients": 4, "seeds_per_request": 16,
          "popularity": {"law": "zipf", "s": 0.99}}


class VirtualClock:
    def __init__(self):
        self.t = 100.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += dt


class Handle:
    rid = 0

    def __init__(self, t_done=0.0):
        Handle.rid += 1
        self.rid = Handle.rid
        self.done, self.error, self.t_done = True, None, t_done

    def wait_done(self, timeout=None):
        return True


def test_schedule_and_seeds_deterministic_per_seed():
    big = 2 ** 31 + 12345
    a = loadgen.open_schedule(OPEN, 4.0, big)
    b = loadgen.open_schedule(OPEN, 4.0, big)
    c = loadgen.open_schedule(OPEN, 4.0, 7)
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    # every seed sends the same set of gaps, in its own order (the gap
    # after the last arrival is not sent)
    shared = np.isin(np.round(np.diff(c), 12), np.round(np.diff(a), 12))
    assert shared.sum() >= len(shared) - 1
    d1 = loadgen.SeedDraw(OPEN, 1000, big).draw(50)
    d2 = loadgen.SeedDraw(OPEN, 1000, big).draw(50)
    np.testing.assert_array_equal(d1, d2)
    assert d1.shape == (50, 1) and d1.min() >= 0 and d1.max() < 1000


def test_zipf_popularity_is_skewed():
    draw = loadgen.SeedDraw(OPEN, 10_000, 3).draw(20_000)[:, 0]
    counts = np.sort(np.bincount(draw, minlength=10_000))[::-1]
    # Zipf 0.99: the hottest node takes ~10%, a uniform draw ~0.01%
    assert counts[0] > 0.05 * draw.size
    assert counts[:100].sum() > 0.4 * draw.size


def test_open_loop_on_rate_on_virtual_clock():
    clock = VirtualClock()
    due = loadgen.open_schedule(OPEN, 10.0, 5)
    seeds = loadgen.SeedDraw(OPEN, 100, 5).draw(len(due))
    ledger = loadgen.Ledger(5, keep=0.01)
    loadgen.run_open(lambda s: Handle(clock()), due, seeds, clock(), ledger,
                     clock=clock, sleep=clock.sleep)
    a = ledger.arrays()
    assert len(ledger) == 5000                     # 500/s for 10 s
    np.testing.assert_array_equal(a["sent"], a["due"])   # never late here
    assert abs(len(ledger) / (a["sent"][-1] - a["sent"][0]) - 500.0) < 5.0
    assert a["ok"].all() and np.isfinite(a["done"]).all()
    assert 20 <= len(ledger.kept) <= 90            # ~1% kept for the check


def test_closed_loop_keeps_its_clients_and_stops():
    clock = VirtualClock()
    t_end = clock() + 1.0

    def submit(s):
        clock.sleep(0.01)                          # each send takes 10 ms
        return Handle(clock())

    seeds = loadgen.SeedDraw(CLOSED, 100, 1).stream()
    ledger = loadgen.Ledger(1, keep=0.0)
    loadgen.run_closed(submit, seeds, CLOSED["clients"], t_end, ledger,
                       clock=clock)
    # 4 first sends, then one per 10 ms until the second is up
    assert 99 <= len(ledger) <= 101
    assert set(ledger.n_seeds) == {16} and not ledger.kept


def test_buckets_used():
    assert loadgen.buckets_used(OPEN, 16) == [1, 2, 4, 8, 16]
    assert loadgen.buckets_used(CLOSED, 16) == [16]
    assert loadgen.buckets_used(dict(OPEN, seeds_per_request=3), 16) == \
        [4, 8, 16]


def test_ledger_lets_go_of_settled_handles():
    class Pending(Handle):
        def __init__(self):
            super().__init__()
            self.done = False

        def wait_done(self, timeout=None):
            return False

    ledger = loadgen.Ledger(3, keep=0.0)
    late = Pending()
    ledger.add(0.0, 0.0, 1, Handle(1.0))
    ledger.add(0.1, 0.1, 1, late)
    ledger.add(0.2, 0.2, 1, Handle(1.2))
    ledger.harvest()                  # stops at the first open reply
    assert len(ledger._open) == 2
    ledger.settle(timeout=0.01)       # the late one never comes
    a = ledger.arrays()
    assert list(a["ok"]) == [1, 0, 1] and np.isnan(a["done"][1])
    assert not ledger._open
