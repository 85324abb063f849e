"""Zero-downtime live mutation plane (DESIGN.md §16).

Two arms over a *running* :class:`~repro.serve.cluster.ClusterServer`:

* :func:`hot_swap` — versioned weight hot-swap from the checkpoint store.
  State machine: **validate** (commit marker + manifest vs the live tree,
  ``checkpoint.store.validate_step``) → **warm** (the candidate weights run
  a full dummy round on the shadow lane, off the serving path) → **flip**
  (one atomic reference swap + DRHM router epoch bump between dispatch
  rounds) → **drain** (rounds dispatched on the old version settle on the
  weights they ran on; the last one GCs the old reference).  Any failure
  before the flip raises a typed :class:`HotSwapError` and traffic never
  sees the candidate.  ``blackout_ms`` — first post-flip dispatch minus the
  flip time — is the record proving the router never stalls.

* :class:`GraphStream` — streaming edge inserts/deletes over a
  :class:`~repro.sparse.delta.DeltaGraphState` with a bounded-staleness
  window (``max_pending`` mutations or ``max_age_s`` seconds, whichever
  trips first).  Each flush delta-re-packs the CSR + dedup-chunk layouts
  (clean blocks untouched), optionally proves bitwise/1e-5 parity against a
  cold re-pack *before* installing, then swaps the serving CSR atomically
  through ``SamplerPool.set_graph``.  Requests sampled pre-flip drain on
  the old adjacency and carry its ``graph_epoch`` stamp.  Feature-row
  updates re-home through the existing DRHM shard plan (sharded) or a
  fetch-step rebuild (replicated).
"""
from __future__ import annotations

import dataclasses
import time
from typing import List, Optional

import numpy as np

from repro.checkpoint import store as ckpt_store
from repro.serve.errors import GraphMutationError, HotSwapError
from repro.sparse.delta import DeltaGraphState, chunks_match


# ---------------------------------------------------------------------------
# Weight hot-swap
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class SwapReport:
    """One hot-swap, end to end."""

    step: int                  # checkpoint step that was installed
    old_version: int
    version: int               # new serving params_version
    router_epoch: int          # DRHM epoch after the flip
    validate_s: float
    warm_s: float
    t_flip: float              # server clock at the atomic flip
    blackout_ms: float         # first post-flip dispatch − flip (NaN if the
    #                            server saw no traffic inside the wait)
    drained_old: bool          # old version fully settled + GCed
    metadata: dict             # checkpoint manifest metadata


def hot_swap(server, ckpt_dir, step: Optional[int] = None, *,
             wait_for_dispatch: float = 5.0,
             drain_timeout: float = 30.0,
             poll_s: float = 0.0005) -> SwapReport:
    """Swap a running server onto checkpoint ``step`` with zero downtime.

    ``step=None`` takes the newest committed step.  Raises
    :class:`HotSwapError` if validation, restore, or the shadow warm-up
    fails — the serving version is unchanged in every abort path.
    """
    clock = server.clock
    if step is None:
        step = ckpt_store.latest_step(ckpt_dir)
        if step is None:
            raise HotSwapError("resolve", ckpt_store.CheckpointError(
                f"no committed checkpoint step under {ckpt_dir}"))
    t0 = clock()
    try:
        new_params, metadata = ckpt_store.restore(ckpt_dir, step,
                                                  like_tree=server.params)
    except ckpt_store.CheckpointError as exc:
        raise HotSwapError("validate", exc) from exc
    t1 = clock()
    try:
        server._shadow_warmup(params=new_params)
    except Exception as exc:  # noqa: BLE001 — typed abort, server untouched
        raise HotSwapError("warmup", exc) from exc
    t2 = clock()
    old_ver = server.params_version
    t_flip = clock()
    new_ver = server.install_params(new_params)
    # blackout: how long until the engine dispatches on the new version —
    # under load this is sub-round-trip (the flip is between rounds); with
    # no traffic there is nothing to measure and it reports NaN
    blackout_ms = float("nan")
    deadline = time.monotonic() + float(wait_for_dispatch)  # wall-clock
    while time.monotonic() < deadline:       # (server.clock may be virtual)
        t_first = server.first_dispatch_at(new_ver)
        if t_first is not None:
            blackout_ms = (t_first - t_flip) * 1e3
            break
        time.sleep(poll_s)
    # drain: the old version disappears from the retired set once its last
    # in-flight round settles (immediately, if none were in flight)
    drained = False
    deadline = time.monotonic() + float(drain_timeout)
    while time.monotonic() < deadline:
        if old_ver not in server.retired_versions():
            drained = True
            break
        time.sleep(poll_s)
    report = SwapReport(step=int(step), old_version=old_ver, version=new_ver,
                        router_epoch=server.router.epoch,
                        validate_s=t1 - t0, warm_s=t2 - t1, t_flip=t_flip,
                        blackout_ms=blackout_ms, drained_old=drained,
                        metadata=dict(metadata or {}))
    server.telemetry.event("hot_swap", step=int(step), version=new_ver,
                           old_version=old_ver,
                           blackout_ms=blackout_ms, drained=drained)
    return report


# ---------------------------------------------------------------------------
# Streaming graph mutation
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class FlushReport:
    """One epoch boundary of the mutation stream."""

    epoch: int
    inserted: int
    deleted: int
    dirty_blocks: int
    clean_blocks: int
    n_edges: int
    staleness_s: float         # age of the oldest buffered mutation
    repack_s: float            # incremental re-pack (+ parity, if checked)
    parity_ok: Optional[bool]  # None when the parity check was skipped


class GraphStream:
    """Bounded-staleness edge stream feeding a running cluster server.

    Mutations buffer on a :class:`DeltaGraphState`; a flush (explicit, or
    automatic when the buffer hits ``max_pending`` mutations or the oldest
    one ages past ``max_age_s``) applies them as one epoch: delta CSR +
    chunk re-pack, optional parity proof vs the cold pack (every
    ``parity_every``-th epoch; 0 disables), then one atomic sampler swap.
    A failed parity proof raises :class:`GraphMutationError` *before* the
    swap — the serving graph never installs an unproven layout.
    """

    def __init__(self, server, delta: Optional[DeltaGraphState] = None, *,
                 max_pending: int = 256, max_age_s: Optional[float] = None,
                 parity_every: int = 0, tol: float = 1e-5):
        if delta is None:
            delta = DeltaGraphState(
                *_csr_to_coo(server.indptr, server.indices),
                server.indptr.shape[0] - 1)
        if delta.n_nodes != server.indptr.shape[0] - 1:
            raise GraphMutationError(
                f"delta graph has {delta.n_nodes} nodes, server "
                f"{server.indptr.shape[0] - 1} — node count is immutable")
        self.server = server
        self.delta = delta
        self.max_pending = int(max_pending)
        self.max_age_s = None if max_age_s is None else float(max_age_s)
        self.parity_every = int(parity_every)
        self.tol = float(tol)
        self._t_oldest: Optional[float] = None
        self.flushes: List[FlushReport] = []

    # -- mutation ingress ---------------------------------------------------
    @property
    def pending(self) -> int:
        return self.delta.pending

    def staleness(self) -> float:
        """Seconds the oldest buffered mutation has waited (0 if none) —
        the bounded-staleness observable."""
        if self._t_oldest is None:
            return 0.0
        return max(self.server.clock() - self._t_oldest, 0.0)

    def insert(self, sender: int, receiver: int, weight: float = 1.0):
        self.delta.insert_edge(sender, receiver, weight)
        self._stamp()
        self._maybe_flush()

    def delete(self, sender: int, receiver: int):
        self.delta.delete_edge(sender, receiver)
        self._stamp()
        self._maybe_flush()

    def update_features(self, row_ids, rows):
        """Feature-row refresh rides the same plane: rows re-home through
        the server's resident layout immediately (no epoch buffering —
        features carry no structural layout to re-pack)."""
        self.server.update_feature_rows(row_ids, rows)

    def _stamp(self):
        if self._t_oldest is None and self.delta.pending > 0:
            self._t_oldest = self.server.clock()

    def _maybe_flush(self):
        if self.delta.pending >= self.max_pending:
            self.flush()
        elif (self.max_age_s is not None
              and self.staleness() >= self.max_age_s):
            self.flush()

    # -- epoch boundary -----------------------------------------------------
    def flush(self) -> Optional[FlushReport]:
        """Apply the buffered batch as one epoch; no-op on an empty buffer."""
        if self.delta.pending == 0:
            return None
        clock = self.server.clock
        staleness = self.staleness()
        self._t_oldest = None
        t0 = clock()
        res = self.delta.flush()
        parity_ok: Optional[bool] = None
        if self.parity_every > 0 and res.epoch % self.parity_every == 0:
            parity_ok = True
            for inc, cold in zip(self.delta.repack(),
                                 self.delta.cold_repack()):
                ok, detail = chunks_match(inc, cold, tol=self.tol)
                if not ok:
                    raise GraphMutationError(
                        f"epoch {res.epoch}: incremental re-pack failed "
                        f"parity vs cold pack ({detail}) — not installing")
        t1 = clock()
        indptr, indices = self.delta.csr()
        self.server.apply_graph_update(indptr, indices, epoch=res.epoch)
        report = FlushReport(epoch=res.epoch, inserted=res.inserted,
                             deleted=res.deleted,
                             dirty_blocks=res.dirty_blocks,
                             clean_blocks=res.clean_blocks,
                             n_edges=res.n_edges, staleness_s=staleness,
                             repack_s=t1 - t0, parity_ok=parity_ok)
        self.flushes.append(report)
        self.server.telemetry.event(
            "graph_flush", epoch=res.epoch, inserted=res.inserted,
            deleted=res.deleted, dirty_blocks=res.dirty_blocks,
            n_edges=res.n_edges, staleness_s=staleness,
            parity_ok=parity_ok)
        return report

    def info(self) -> dict:
        return {"epoch": self.delta.epoch, "pending": self.delta.pending,
                "n_edges": self.delta.n_edges,
                "flushes": len(self.flushes),
                "staleness_s": self.staleness(),
                "chunk_stats": self.delta.chunk_stats()}


def _csr_to_coo(indptr: np.ndarray, indices: np.ndarray):
    """Server CSR (receiver-major) back to (senders, receivers) COO."""
    indptr = np.asarray(indptr)
    receivers = np.repeat(np.arange(indptr.shape[0] - 1, dtype=np.int64),
                          np.diff(indptr))
    return np.asarray(indices, np.int64), receivers
