"""Request plane: host-side dynamic batcher for GNN inference.

Seed-node requests coalesce into minibatches under two triggers:

* **size** — pending seed count reaches ``max_seeds`` (a full bucket);
* **deadline** — the oldest pending request has waited ``max_wait`` seconds
  (bounded tail latency: a lone request never waits for a full batch).

Each take stamps ``t_ripe``, the instant the batch's trigger fired, so a
tracer can split a request's queue wait into the wait for its trigger and
the wait for the engine after it (``engine_lag``).

Packing is skip-ahead FIFO (``scheduler.pack_fifo``): a request that does
not fit the remaining seed budget stays at the front of the line while
later, smaller requests may still ride along — no head-of-line blocking.

The batcher is pure host logic with an injectable ``clock`` so the property
tests drive it on virtual time; thread-safety (one lock + condition) is for
the engine's sampler workers and compute loop.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Callable, List, Optional

import numpy as np

from repro.serve.scheduler import pack_fifo


@dataclasses.dataclass
class ServeRequest:
    """One inference request: return embeddings/logits for ``seeds``.

    Delivery is **exactly-once** by construction: ``finish``/``fail`` are
    first-transition-wins (the settle lock), so a request ends up with a
    result XOR a typed error — never both, never twice — no matter how the
    failover machinery races the happy path (DESIGN.md §13).
    """

    rid: int                          # also the NeuraScope trace id (the
    #                                   TAG key stream derives from it too)
    seeds: np.ndarray                 # (k,) int64 seed node ids
    lane: Optional[int] = None        # serving lane (cluster tier routing)
    t_submit: float = 0.0             # clock time at submit
    t_ready: float = 0.0              # sampling finished, joined the queue
    t_done: float = 0.0               # result materialized
    deadline: Optional[float] = None  # absolute clock time; None = none
    cls: str = "interactive"          # request class (serve.slo): SLO
    #                                   objective + shed precedence
    attempts: int = 0                 # dispatch attempts (transient retries)
    reroutes: int = 0                 # lane re-assignments (failover)
    trees: Optional[list] = None      # per-seed SampledSubgraph (data plane)
    tkm: Optional[tuple] = None       # per-seed (hi, lo) uint32 counter
    #                                   terms — device-sampling data plane
    result: Optional[np.ndarray] = None  # (k, d_out) seed outputs
    error: Optional[BaseException] = None  # pipeline failure, re-raised
    params_version: Optional[int] = None  # weight version the dispatch ran
    #                                   on (live hot-swap, DESIGN.md §16)
    graph_epoch: Optional[int] = None  # resident-graph epoch sampled on
    n_settles: int = 0                # terminal transitions taken (always ≤1)
    _event: threading.Event = dataclasses.field(
        default_factory=threading.Event, repr=False)
    _settle_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock, repr=False)

    @property
    def n_seeds(self) -> int:
        return int(np.asarray(self.seeds).shape[0])

    @property
    def done(self) -> bool:
        return self._event.is_set()

    @property
    def latency(self) -> float:
        return self.t_done - self.t_submit

    def expired(self, now: float) -> bool:
        return self.deadline is not None and now >= self.deadline

    def finish(self, result: np.ndarray, t_done: float) -> bool:
        """Deliver the result; ``False`` if the request already settled
        (a raced failover duplicate — dropped, not double-delivered)."""
        with self._settle_lock:
            if self._event.is_set():
                return False
            self.result = result
            self.t_done = t_done
            self.n_settles += 1
            self._event.set()
            return True

    def fail(self, exc: BaseException, t_done: float) -> bool:
        """Mark the request failed — ``wait`` re-raises instead of hanging.
        First-transition-wins like ``finish``."""
        with self._settle_lock:
            if self._event.is_set():
                return False
            self.error = exc
            self.t_done = t_done
            self.n_settles += 1
            self._event.set()
            return True

    def wait_done(self, timeout: Optional[float] = None) -> bool:
        """Block until settled (result OR error) without raising — the
        drain path's primitive (a failed request must not abort a drain)."""
        return self._event.wait(timeout)

    def wait(self, timeout: Optional[float] = None) -> np.ndarray:
        if not self._event.wait(timeout):
            raise TimeoutError(f"request {self.rid} not served in {timeout}s")
        if self.error is not None:
            raise self.error            # typed (serve.errors) — callers
            #                             branch on shed vs timeout vs crash
        return self.result


class DynamicBatcher:
    """Deadline- or size-triggered batch former over a FIFO of requests."""

    def __init__(self, max_seeds: int, max_wait: float,
                 clock: Callable[[], float] = time.monotonic):
        if max_seeds <= 0:
            raise ValueError(f"max_seeds must be positive, got {max_seeds}")
        self.max_seeds = max_seeds
        self.max_wait = float(max_wait)
        self.clock = clock
        self._lock = threading.Lock()
        self._cond = threading.Condition(self._lock)
        self._pending: List[ServeRequest] = []
        self._pending_seeds = 0           # running sum — O(1) ripeness check
        self._pending_deadlined = 0       # how many pending carry a deadline
        self.n_submitted = 0
        self.n_batches = 0
        self.n_expired = 0
        self.t_ripe = 0.0                 # when the last batch taken ripened

    def __len__(self) -> int:
        with self._lock:
            return len(self._pending)

    def submit(self, req: ServeRequest):
        """Enqueue a sampled request (called by the data plane)."""
        if req.n_seeds > self.max_seeds:
            raise ValueError(
                f"request {req.rid} carries {req.n_seeds} seeds but the "
                f"batcher's bucket capacity is {self.max_seeds}")
        # t_ready re-stamps on every (re-)enqueue, so a retried request's
        # queue_wait trace span measures the *current* wait, not the first
        req.t_ready = self.clock()
        with self._cond:
            self._pending.append(req)
            self._pending_seeds += req.n_seeds
            self._pending_deadlined += int(req.deadline is not None)
            self.n_submitted += 1
            self._cond.notify()

    def reap_expired(self, now: float) -> List[ServeRequest]:
        """Remove and return every pending request whose deadline passed —
        the engine fails them with a typed ``DeadlineExceeded`` instead of
        spending a dispatch slot on an answer nobody is waiting for.  O(1)
        when no pending request carries a deadline (the common case)."""
        with self._lock:
            if self._pending_deadlined == 0:
                return []
            expired = [r for r in self._pending if r.expired(now)]
            if not expired:
                return []
            self._pending = [r for r in self._pending if not r.expired(now)]
            self._pending_seeds -= sum(r.n_seeds for r in expired)
            self._pending_deadlined -= sum(int(r.deadline is not None)
                                           for r in expired)
            self.n_expired += len(expired)
            return expired

    # -- trigger logic (lock held) ------------------------------------------
    def _ripe(self, now: float) -> bool:
        if not self._pending:
            return False
        if self._pending_seeds >= self.max_seeds:
            return True                                   # size trigger
        return now - self._pending[0].t_ready >= self.max_wait  # deadline

    def _ripe_at(self) -> float:
        """When the pending queue became ripe: the oldest request's
        ``t_ready + max_wait``, or the moment the pending seeds reached
        ``max_seeds`` if that came first (lock held, queue non-empty)."""
        t = self._pending[0].t_ready + self.max_wait
        if self._pending_seeds >= self.max_seeds:
            seeds = 0
            for r in self._pending:
                seeds += r.n_seeds
                if seeds >= self.max_seeds:
                    return min(t, r.t_ready)
        return t

    def _take(self, now: float) -> List[ServeRequest]:
        # a flush takes batches that never ripened: they ripen as taken
        self.t_ripe = min(self._ripe_at(), now)
        taken, self._pending, used = pack_fifo(
            self._pending, self.max_seeds, size_of=lambda r: r.n_seeds)
        self._pending_seeds -= used
        self._pending_deadlined -= sum(int(r.deadline is not None)
                                       for r in taken)
        self.n_batches += 1
        return taken

    # -- consumers ----------------------------------------------------------
    def poll(self) -> Optional[List[ServeRequest]]:
        """Non-blocking: a batch if a trigger has fired, else ``None``."""
        with self._lock:
            now = self.clock()
            if self._ripe(now):
                return self._take(now)
            return None

    def take(self, timeout: Optional[float] = None
             ) -> Optional[List[ServeRequest]]:
        """Block until a trigger fires (or ``timeout``); the engine loop's
        entry point.  Returns ``None`` on timeout with nothing ripe."""
        deadline = None if timeout is None else self.clock() + timeout
        with self._cond:
            while True:
                now = self.clock()
                if self._ripe(now):
                    return self._take(now)
                # sleep until the oldest request's deadline or the caller's
                waits = []
                if self._pending:
                    waits.append(
                        self._pending[0].t_ready + self.max_wait - now)
                if deadline is not None:
                    if now >= deadline and not waits:
                        return None
                    waits.append(deadline - now)
                if not waits:
                    self._cond.wait()
                    continue
                wait = max(min(waits), 0.0)
                if wait == 0.0 and deadline is not None and now >= deadline:
                    return None
                self._cond.wait(timeout=wait if wait > 0 else 1e-4)

    def flush(self) -> List[List[ServeRequest]]:
        """Drain everything pending into batches (shutdown path)."""
        out = []
        with self._lock:
            while self._pending:
                out.append(self._take(self.clock()))
        return out

    def oldest_ready(self) -> Optional[float]:
        """``t_ready`` of the oldest pending request; None if none."""
        with self._lock:
            return self._pending[0].t_ready if self._pending else None

    def info(self) -> dict:
        """Queue counters as one observable (engine/cluster ``stats()``)."""
        with self._lock:
            return {"submitted": self.n_submitted,
                    "batches": self.n_batches,
                    "expired": self.n_expired,
                    "depth": len(self._pending),
                    "depth_seeds": self._pending_seeds}
