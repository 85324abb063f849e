"""Request plane: scheduler utilities + dynamic batcher property tests.

The batcher runs on a virtual clock here — the properties (every request
batched exactly once, deadlines respected, capacity never exceeded, no
head-of-line blocking) are asserted over seeded random arrival traces
without any real threads or sleeps.
"""
import numpy as np
import pytest

from repro.serve.batcher import DynamicBatcher, ServeRequest
from repro.serve.scheduler import SlotPool, pack_fifo


# ---------------------------------------------------------------------------
# scheduler primitives
# ---------------------------------------------------------------------------

def test_slot_pool_acquire_release():
    pool = SlotPool(3)
    assert pool.free_count == 3
    assert pool.acquire("a") == 0
    assert pool.acquire("b") == 1
    assert pool.acquire("c") == 2
    assert pool.acquire("d") is None          # full
    assert pool.release(1) == "b"
    assert pool.free_count == 1
    assert pool.acquire("d") == 1             # lowest free slot reused
    assert pool.live() == [(0, "a"), (1, "d"), (2, "c")]


def test_slot_pool_double_release_raises():
    pool = SlotPool(2)
    pool.acquire("x")
    pool.release(0)
    with pytest.raises(ValueError):
        pool.release(0)


def test_pack_fifo_skip_ahead():
    sizes = {"a": 10, "b": 9, "c": 3, "d": 2}
    taken, rest, used = pack_fifo(list("abcd"), 16, size_of=sizes.get)
    assert taken == ["a", "c", "d"] and rest == ["b"] and used == 15
    # strict FIFO stops at the first misfit
    taken, rest, _ = pack_fifo(list("abcd"), 16, size_of=sizes.get,
                               skip_ahead=False)
    assert taken == ["a"] and rest == ["b", "c", "d"]


def test_pack_fifo_preserves_order():
    taken, rest, used = pack_fifo(list(range(10)), 4)
    assert taken == [0, 1, 2, 3] and rest == [4, 5, 6, 7, 8, 9]
    assert used == 4


# ---------------------------------------------------------------------------
# dynamic batcher on a virtual clock
# ---------------------------------------------------------------------------

class Clock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _req(rid, k=1):
    return ServeRequest(rid=rid, seeds=np.arange(k, dtype=np.int64))


def test_size_trigger_fires_full_bucket():
    clk = Clock()
    b = DynamicBatcher(max_seeds=4, max_wait=1.0, clock=clk)
    for i in range(3):
        b.submit(_req(i))
    assert b.poll() is None                  # 3 < 4 and no deadline yet
    b.submit(_req(3))
    batch = b.poll()                         # size trigger, zero wait
    assert [r.rid for r in batch] == [0, 1, 2, 3]
    assert b.poll() is None


def test_deadline_trigger_fires_partial_batch():
    clk = Clock()
    b = DynamicBatcher(max_seeds=8, max_wait=0.5, clock=clk)
    b.submit(_req(0))
    clk.t = 0.4
    assert b.poll() is None                  # deadline not reached
    clk.t = 0.51
    batch = b.poll()
    assert [r.rid for r in batch] == [0]


@pytest.mark.parametrize("trigger", ["size", "deadline"])
def test_take_stamps_when_the_batch_ripened(trigger):
    """``t_ripe``: the fill moment under the size trigger, the oldest
    request's ``t_ready + max_wait`` under the deadline trigger, whenever
    the engine gets round to taking the batch."""
    clk = Clock()
    b = DynamicBatcher(max_seeds=4, max_wait=0.5, clock=clk)
    b.submit(_req(0, k=2))                   # t_ready 0.0
    clk.t = 0.1
    b.submit(_req(1))
    if trigger == "size":
        clk.t = 0.3
        b.submit(_req(2))                    # 4 seeds: full at 0.3
        want = 0.3
    else:
        want = 0.0 + 0.5
    clk.t = 0.9                              # the engine comes late
    batch = b.poll()
    assert batch and b.t_ripe == pytest.approx(want)
    assert b.t_ripe <= clk.t


def test_oversized_request_rejected():
    b = DynamicBatcher(max_seeds=4, max_wait=0.1, clock=Clock())
    with pytest.raises(ValueError):
        b.submit(_req(0, k=5))


def test_no_head_of_line_blocking():
    clk = Clock()
    b = DynamicBatcher(max_seeds=8, max_wait=0.5, clock=clk)
    b.submit(_req(0, k=6))
    b.submit(_req(1, k=5))                   # does not fit with rid 0
    b.submit(_req(2, k=2))                   # fits alongside rid 0
    clk.t = 0.6
    batch = b.poll()
    assert [r.rid for r in batch] == [0, 2]  # rid 1 skipped, not starved:
    clk.t = 1.2
    assert [r.rid for r in b.poll()] == [1]  # it leads the next batch


def test_property_random_trace_exactly_once_and_deadlines():
    """Seeded random arrival traces: every request leaves in exactly one
    batch, no batch exceeds capacity, and no request launches later than
    its deadline (ready time + max_wait) while the consumer polls."""
    for seed in range(5):
        rng = np.random.default_rng(seed)
        clk = Clock()
        max_seeds, max_wait = 16, 0.05
        b = DynamicBatcher(max_seeds, max_wait, clock=clk)
        n = 60
        arrivals = np.cumsum(rng.exponential(0.01, n))
        sizes = rng.integers(1, 5, n)
        served = {}
        i = 0
        t = 0.0
        while len(served) < n:
            # advance the clock in small ticks, submitting due arrivals
            while i < n and arrivals[i] <= t:
                b.submit(_req(i, int(sizes[i])))
                i += 1
            batch = b.poll()
            if batch:
                assert sum(r.n_seeds for r in batch) <= max_seeds
                for r in batch:
                    assert r.rid not in served, "served twice"
                    served[r.rid] = clk()
                    # poll cadence (2 ms) bounds the detection lag
                    assert clk() <= r.t_ready + max_wait + 0.002 + 1e-9
            t += 0.002
            clk.t = t
        assert len(served) == n
        assert b.poll() is None and len(b) == 0


def test_take_blocking_with_timeout_returns_none():
    b = DynamicBatcher(max_seeds=4, max_wait=10.0)
    assert b.take(timeout=0.01) is None


def test_take_blocking_deadline_wakeup():
    import time
    b = DynamicBatcher(max_seeds=100, max_wait=0.02)
    b.submit(_req(0))
    t0 = time.monotonic()
    batch = b.take(timeout=5.0)
    dt = time.monotonic() - t0
    assert batch and batch[0].rid == 0
    assert dt < 1.0                          # woke on the deadline, not the timeout


def test_flush_drains_everything():
    clk = Clock()
    b = DynamicBatcher(max_seeds=4, max_wait=100.0, clock=clk)
    for i in range(11):
        b.submit(_req(i))
    batches = b.flush()
    assert [len(x) for x in batches] == [4, 4, 3]
    assert sorted(r.rid for x in batches for r in x) == list(range(11))
    assert b.t_ripe <= clk.t                # an unripe flush ripens now


# ---------------------------------------------------------------------------
# deadlines + exactly-once settlement (DESIGN.md §13)
# ---------------------------------------------------------------------------

def test_reap_expired_removes_only_past_deadline():
    clk = Clock()
    b = DynamicBatcher(max_seeds=100, max_wait=10.0, clock=clk)
    early, late, none = _req(0), _req(1, k=2), _req(2)
    early.deadline, late.deadline = 0.5, 2.0
    for r in (early, late, none):
        b.submit(r)
    assert b.reap_expired(0.4) == []
    reaped = b.reap_expired(1.0)
    assert [r.rid for r in reaped] == [0]
    assert len(b) == 2 and b.n_expired == 1
    # remaining bookkeeping stays consistent: a full flush yields the rest
    assert sorted(r.rid for x in b.flush() for r in x) == [1, 2]


def test_reap_expired_is_noop_without_deadlines():
    b = DynamicBatcher(max_seeds=4, max_wait=10.0, clock=Clock())
    for i in range(3):
        b.submit(_req(i))
    assert b.reap_expired(1e9) == []          # O(1) fast path
    assert len(b) == 3 and b.n_expired == 0


def test_reaped_seeds_do_not_count_toward_size_trigger():
    clk = Clock()
    b = DynamicBatcher(max_seeds=4, max_wait=10.0, clock=clk)
    doomed = _req(0, k=3)
    doomed.deadline = 0.1
    b.submit(doomed)
    b.reap_expired(1.0)
    b.submit(_req(1, k=3))
    assert b.poll() is None                   # 3 < 4: reap fixed the sum
    b.submit(_req(2, k=1))
    assert [r.rid for r in b.poll()] == [1, 2]


def test_settlement_is_first_transition_wins():
    r = _req(0)
    assert r.finish(np.zeros((1, 2)), 1.0)
    assert not r.fail(RuntimeError("late failover duplicate"), 2.0)
    assert not r.finish(np.ones((1, 2)), 3.0)
    assert r.error is None and r.n_settles == 1
    assert r.t_done == 1.0 and (r.result == 0).all()

    f = _req(1)
    assert f.fail(ValueError("boom"), 1.0)
    assert not f.finish(np.zeros((1, 2)), 2.0)
    assert f.result is None and f.n_settles == 1
    assert f.wait_done(0)                     # settled: no blocking
    with pytest.raises(ValueError, match="boom"):
        f.wait()                              # the typed error, re-raised


def test_wait_raises_the_typed_error_object():
    from repro.serve.errors import DeadlineExceeded
    r = _req(0)
    err = DeadlineExceeded(0, deadline=1.0, now=2.0)
    r.fail(err, 2.0)
    with pytest.raises(DeadlineExceeded) as ei:
        r.wait()
    assert ei.value is err and ei.value.rid == 0
    assert isinstance(ei.value, TimeoutError)  # and shed/crash types differ
    assert isinstance(ei.value, RuntimeError)  # old call sites keep passing
