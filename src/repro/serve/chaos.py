"""Deterministic, seed-driven fault injection for the serving tier.

The control plane's failover claims (zero lost requests on a lane kill,
bounded p99 spike, typed sampler-failure isolation) are only worth stating
if they are *measured under injected faults* — this module is the fault
source (DESIGN.md §13).  Design constraints:

* **deterministic** — faults trigger on counters the serving stack already
  owns (dispatch round numbers, request ids), not on wall-clock dice, so a
  chaos test fails reproducibly or not at all.  Probabilistic modes hash
  ``(seed, site, counter)`` through the same splitmix64 the DRHM router
  uses — reproducible for a fixed seed and arrival order.
* **zero happy-path cost** — the engines consult the injector only through
  ``if self.chaos is not None`` guards; a server built without one carries
  no chaos branches in its hot loop beyond that single ``None`` test.

Fault vocabulary (what real clusters actually do):

* ``kill``  — the lane goes silent mid-stream and *stays* silent, like a
  crashed worker process: the engine can no longer dispatch it, its queue
  strands, and nothing recovers until the supervisor declares it dead
  (``on_lane_dead`` acknowledges the crash and spends the fault, modelling
  a process restart).
* ``stall`` — the lane goes silent for ``duration`` seconds, then recovers
  by itself (GC pause / straggling device stream).  If the supervisor's
  stall timeout is shorter than the stall, it is treated as a death.
* ``sampler`` — a data-plane worker exception on specific request ids
  (the ``SamplerPool`` isolation audit's trigger).
* ``step`` — a transient device-step failure on specific dispatch rounds
  (the retry-once path's trigger).
"""
from __future__ import annotations

import dataclasses
import math
import threading
import time
from typing import Callable, Dict, List, Optional, Sequence

import numpy as np

from repro.core.drhm import mix64


class InjectedSamplerFault(RuntimeError):
    """Raised inside a sampler worker by the chaos hook."""

    def __init__(self, rid: int):
        super().__init__(f"chaos: injected sampler fault for request {rid}")
        self.rid = rid


@dataclasses.dataclass
class LaneFault:
    """One scripted lane fault: ``lane`` goes silent once the engine has
    dispatched ``at_round`` rounds; ``kind`` is ``"kill"`` (silent until
    the supervisor acknowledges the death) or ``"stall"`` (silent for
    ``duration`` wall seconds, then self-recovers)."""

    lane: int
    at_round: int = 0
    kind: str = "kill"
    duration: float = math.inf

    def __post_init__(self):
        if self.kind not in ("kill", "stall"):
            raise ValueError(f"unknown lane-fault kind {self.kind!r}")


def _hash_p(seed: int, site: int, counter: int, p: float) -> bool:
    if p <= 0.0:
        return False
    z = ((seed & 0xFFFFFFFF) * 0x9E37_79B9
         ^ (site << 40) ^ (counter & 0xFF_FFFF_FFFF))
    h = mix64(np.uint64(z & 0xFFFF_FFFF_FFFF_FFFF))
    return float(h) / float(1 << 64) < p


class ChaosInjector:
    """Scripted + hash-probabilistic fault schedule for one server."""

    def __init__(self, seed: int = 0, *,
                 lane_faults: Sequence[LaneFault] = (),
                 step_fault_rounds: Sequence[int] = (),
                 p_step_fault: float = 0.0,
                 sampler_fault_rids: Sequence[int] = (),
                 p_sampler_fault: float = 0.0,
                 clock: Callable[[], float] = time.monotonic):
        self.seed = int(seed)
        self.clock = clock
        self._lock = threading.Lock()
        self._lane_faults: List[LaneFault] = list(lane_faults)
        self._triggered_at: Dict[int, float] = {}   # fault idx → wall time
        self._spent: set = set()                    # fault idx acknowledged
        self.step_fault_rounds = set(int(r) for r in step_fault_rounds)
        self.p_step_fault = float(p_step_fault)
        self.sampler_fault_rids = set(int(r) for r in sampler_fault_rids)
        self.p_sampler_fault = float(p_sampler_fault)
        # what actually fired, for tests and the flight recorder
        self.injected: Dict[str, int] = {"kill": 0, "stall": 0,
                                         "step": 0, "sampler": 0}

    # -- lane faults (engine consults when assembling a round) --------------
    def blocked(self, lane: int, round_no: int) -> bool:
        """True while ``lane`` is wedged — the engine must not dispatch it
        (the lane looks exactly like a hung device stream)."""
        now = self.clock()
        with self._lock:
            for i, f in enumerate(self._lane_faults):
                if f.lane != lane or i in self._spent:
                    continue
                if round_no < f.at_round and i not in self._triggered_at:
                    continue
                if i not in self._triggered_at:
                    self._triggered_at[i] = now
                    self.injected[f.kind] += 1
                if f.kind == "kill":
                    return True
                if now - self._triggered_at[i] < f.duration:
                    return True
                self._spent.add(i)           # stall elapsed: self-recovered
        return False

    def on_lane_dead(self, lane: int):
        """Supervisor acknowledged the death: the crash is spent (the lane
        that restarts is a fresh process, not the wedged one)."""
        with self._lock:
            for i, f in enumerate(self._lane_faults):
                if f.lane == lane and i in self._triggered_at:
                    self._spent.add(i)

    # -- transient device-step faults ---------------------------------------
    def step_fault(self, round_no: int) -> bool:
        fire = (round_no in self.step_fault_rounds
                or _hash_p(self.seed, 1, round_no, self.p_step_fault))
        if fire:
            with self._lock:
                self.injected["step"] += 1
        return fire

    # -- sampler-worker faults (SamplerPool fault hook) ---------------------
    def sampler_hook(self, req) -> None:
        """Passed as ``SamplerPool(fault_hook=...)``; raises inside the
        worker for scheduled request ids — the isolation path must fail
        exactly that request and keep the worker alive."""
        if (req.rid in self.sampler_fault_rids
                or _hash_p(self.seed, 2, req.rid, self.p_sampler_fault)):
            with self._lock:
                self.injected["sampler"] += 1
            raise InjectedSamplerFault(req.rid)

    def info(self) -> dict:
        with self._lock:
            return {"seed": self.seed, "injected": dict(self.injected),
                    "lane_faults": [dataclasses.asdict(f)
                                    for f in self._lane_faults]}
