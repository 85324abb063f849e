"""The one load generator: reads a traffic file and drives ``submit``.

A traffic file (``traffic/<name>.json``) holds parameters only:

* ``"kind": "open_poisson"`` — independent users: ``rate_per_s`` Poisson
  arrivals, each request sent when it is due whatever the server is doing;
* ``"kind": "closed"`` — ``clients`` callers, each sending its next
  request when its previous one has settled;
* ``seeds_per_request`` seed nodes per request, drawn by ``popularity``
  (``{"law": "zipf", "s": 0.99}`` over a permutation of node ids drawn
  from the seed, or ``{"law": "uniform"}``).

Every seed gives the same amount of work: an open loop's gaps are one
fixed set of exponential draws, scaled to the window, that each seed sends
in its own order.  Requests carry fixed-shape trees, so which nodes are
drawn does not change the work.

The generator keeps its own record of every request (``Ledger``), on the
caller's clock; ``submit`` returns a handle with ``.rid``, ``.done``,
``.wait_done``, ``.t_done`` and ``.error``.
"""
from __future__ import annotations

import array
import collections
import contextlib
import time
from typing import Callable, ContextManager, Dict, Iterator, List

import numpy as np

# the fixed set of gaps is drawn once from this stream, for every seed
GAP_STREAM = 0x5EED
SETTLE_WAIT_S = 60.0


class Ledger:
    """The generator's record of every request, in flat arrays: when it was
    due, sent and settled, whether it succeeded, its seed count and id.  A
    reply is copied in as soon as it has come and its handle let go, so
    the record adds no Python objects for the collector to walk while the
    server runs in the same process.  The handles of a sample drawn from
    the seed (each request kept with probability ``keep``) stay, for the
    check of the answers."""

    def __init__(self, seed: int, keep: float):
        self.due, self.sent = array.array("d"), array.array("d")
        self.done, self.ok = array.array("d"), array.array("b")
        self.n_seeds, self.rid = array.array("l"), array.array("q")
        self.kept: Dict[int, object] = {}
        self._open: "collections.deque" = collections.deque()
        self._rng = np.random.default_rng([int(seed), 0xC0FFEE])
        self._keep = float(keep)

    def __len__(self) -> int:
        return len(self.due)

    def add(self, due: float, sent: float, n_seeds: int, handle) -> None:
        i = len(self.due)
        self.due.append(due)
        self.sent.append(sent)
        self.done.append(float("nan"))
        self.ok.append(0)
        self.n_seeds.append(n_seeds)
        self.rid.append(handle.rid)
        self._open.append((i, handle))
        if self._rng.random() < self._keep:
            self.kept[i] = handle

    def harvest(self) -> None:
        """Copy in every reply that has come, oldest first."""
        while self._open and self._open[0][1].done:
            self._close(*self._open.popleft())

    def _close(self, i: int, handle) -> None:
        self.done[i] = handle.t_done
        self.ok[i] = int(handle.error is None)

    def settle(self, timeout: float = SETTLE_WAIT_S) -> None:
        """Wait for every reply, at most ``timeout`` past now in all; one
        that never comes stays not ok."""
        deadline = time.monotonic() + timeout
        while self._open:
            i, handle = self._open.popleft()
            left = deadline - time.monotonic()
            if left > 0 and handle.wait_done(left):
                self._close(i, handle)

    def arrays(self) -> Dict[str, np.ndarray]:
        return {name: np.frombuffer(getattr(self, name), dtype=dt).copy()
                for name, dt in (("due", np.float64), ("sent", np.float64),
                                 ("done", np.float64), ("ok", np.int8),
                                 ("n_seeds", np.int_), ("rid", np.int64))}


def buckets_used(traffic: dict, max_batch_seeds: int) -> List[int]:
    """Power-of-two batch sizes this traffic can fill: a batch holds one or
    more whole requests, so any bucket from the smallest that holds one
    request up to the cap."""
    k = int(traffic["seeds_per_request"])
    out, b = [], 1
    while b < max_batch_seeds:
        if b >= k:
            out.append(b)
        b *= 2
    return out + [max_batch_seeds]


class SeedDraw:
    """Seed nodes by popularity, deterministic per seed."""

    def __init__(self, traffic: dict, n_nodes: int, seed: int):
        self.rng = np.random.default_rng(int(seed))
        self.k = int(traffic["seeds_per_request"])
        pop = traffic.get("popularity", {"law": "uniform"})
        self.n = int(n_nodes)
        self.cdf = None
        if pop["law"] == "zipf":
            w = np.arange(1, self.n + 1, dtype=np.float64) ** -float(pop["s"])
            self.cdf = np.cumsum(w)
            self.cdf /= self.cdf[-1]
            self.perm = self.rng.permutation(self.n)
        elif pop["law"] != "uniform":
            raise ValueError(f"unknown popularity law {pop['law']!r}")

    def draw(self, n_requests: int) -> np.ndarray:
        """(n_requests, seeds_per_request) int64 node ids."""
        shape = (int(n_requests), self.k)
        if self.cdf is None:
            return self.rng.integers(0, self.n, shape)
        rank = np.searchsorted(self.cdf, self.rng.random(shape), side="right")
        return self.perm[np.minimum(rank, self.n - 1)]

    def stream(self, block: int = 4096) -> Iterator[np.ndarray]:
        while True:
            yield from self.draw(block)


def open_schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of an open loop: the fixed
    set of gaps for this rate and length, in the seed's order."""
    n = max(int(round(float(traffic["rate_per_s"]) * seconds)), 1)
    gaps = np.random.default_rng(GAP_STREAM).exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    gaps = np.random.default_rng(int(seed)).permutation(gaps)
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])


def run_open(submit: Callable, due_s: np.ndarray, seeds: np.ndarray,
             t0: float, ledger: Ledger,
             clock: Callable[[], float] = time.monotonic,
             sleep: Callable[[float], None] = time.sleep,
             mark: Callable[[str], ContextManager] = contextlib.nullcontext
             ) -> None:
    """Send request ``i`` at ``t0 + due_s[i]``; a late generator sends at
    once, and its lateness shows as ``sent - due``.  ``mark(name)`` wraps
    the generator's own sleeps and sends (host annotations in a trace)."""
    for d, s in zip(due_s, seeds):
        due = t0 + float(d)
        wait = due - clock()
        if wait > 0:
            with mark("bench.generator_sleep"):
                sleep(wait)
        sent = clock()
        with mark("bench.submit"):
            ledger.add(due, sent, len(s), submit(s))
        ledger.harvest()


def run_closed(submit: Callable, seeds: Iterator[np.ndarray], clients: int,
               t_end: float, ledger: Ledger,
               clock: Callable[[], float] = time.monotonic,
               mark: Callable[[str], ContextManager] = contextlib.nullcontext
               ) -> None:
    """``clients`` callers until ``t_end``.  Replies come back in the order
    sent, so one thread serves every caller: it waits for the oldest reply
    and sends that caller's next request."""
    live: "collections.deque" = collections.deque()

    def send():
        s = next(seeds)
        now = clock()
        with mark("bench.submit"):
            handle = submit(s)
        ledger.add(now, now, len(s), handle)
        live.append(handle)

    for _ in range(int(clients)):
        send()
    while live:
        handle = live.popleft()
        with mark("bench.client_wait"):
            if not handle.wait_done(SETTLE_WAIT_S):
                break
        ledger.harvest()
        if clock() < t_end:
            send()
