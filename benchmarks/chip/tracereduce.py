"""From a profiler trace to the numbers the per-layer metrics read.

``load_xspace`` turns JAX's ``.xplane.pb`` into plain events; ``summarize``
reduces them over the measured window, which the benchmark marks with its
own host annotation (``bench.window``), so device and host share one clock:

* busy: the union of the intervals in which an operation runs on a device
  (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane), averaged over
  the devices; idle is the rest of the window;
* per operation name: count and total device time (what the kernels'
  rooflines read), and per program (``XLA Modules`` line): count and time;
* idle gaps: each stretch of the window in which no operation runs,
  attributed to the host activity that covers most of it.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import heapq
import os
from typing import Dict, List, Optional, Tuple

WINDOW_MARK = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
# host events too broad to say what the host was doing in a gap
HOST_IGNORE = (WINDOW_MARK,)


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float


# {plane name: {line name: [Event]}}
Planes = Dict[str, Dict[str, List[Event]]]


def find_xspace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def load_xspace(path: str) -> Planes:
    from jax.profiler import ProfileData
    out: Planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = out.setdefault(plane.name, {})
        device = plane.name.startswith(DEVICE_PREFIX)
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            lines.setdefault(line.name, []).extend(
                Event(e.name, e.start_ns, e.end_ns) for e in line.events)
    return out


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(ev: Event, lo: float, hi: float) -> Optional[Tuple[float, float]]:
    a, b = max(ev.start_ns, lo), min(ev.end_ns, hi)
    return (a, b) if b > a else None


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                     # mean over the devices
    n_devices: int
    ops: Dict[str, List[float]]       # name -> [count, seconds], all devices
    modules: Dict[str, List[float]]   # name -> [count, seconds], all devices
    idle_gaps: List[Tuple[str, float]]  # host activity -> idle seconds

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, k: int = 10, width: int = 160) -> List[List]:
        """The ``k`` operations that took most device time; a name is the
        op's HLO text (name, shape, operands), cut to ``width``."""
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:k]
        return [[name[:width], sec] for name, (_, sec) in top]


def window_of(planes: Planes) -> Tuple[float, float]:
    """The span of the benchmark's ``bench.window`` annotation."""
    for name, lines in planes.items():
        if name.startswith(DEVICE_PREFIX):
            continue
        for events in lines.values():
            for ev in events:
                if ev.name == WINDOW_MARK:
                    return ev.start_ns, ev.end_ns
    raise ValueError(f"no {WINDOW_MARK!r} annotation in the trace")


def summarize(planes: Planes, max_gaps: int = 10) -> Summary:
    lo, hi = window_of(planes)
    ops: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    mods: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    busy_ns, n_dev, first_busy = 0.0, 0, None
    for pname, lines in sorted(planes.items()):
        if not pname.startswith(DEVICE_PREFIX):
            continue
        n_dev += 1
        spans = []
        for ev in lines.get(OPS_LINE, []):
            c = _clip(ev, lo, hi)
            if c:
                spans.append(c)
                ops[ev.name][0] += 1
                ops[ev.name][1] += (c[1] - c[0]) / 1e9
        for ev in lines.get(MODULES_LINE, []):
            c = _clip(ev, lo, hi)
            if c:
                mods[ev.name][0] += 1
                mods[ev.name][1] += (c[1] - c[0]) / 1e9
        merged = _union(spans)
        busy_ns += sum(b - a for a, b in merged)
        if first_busy is None:
            first_busy = merged
    if n_dev == 0:
        raise ValueError("no TPU device plane in the trace")
    gaps = _gaps(first_busy or [], lo, hi)
    return Summary(window_s=(hi - lo) / 1e9, busy_s=busy_ns / n_dev / 1e9,
                   n_devices=n_dev, ops=dict(ops), modules=dict(mods),
                   idle_gaps=_attribute(gaps, planes)[:max_gaps])


def _gaps(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in busy:
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def _attribute(gaps: List[Tuple[float, float]], planes: Planes
               ) -> List[Tuple[str, float]]:
    """Idle seconds per host activity: each gap goes to the host event
    (any thread, the benchmark's annotations included) that overlaps it
    most; a gap that no host event overlaps is ``host idle``."""
    host = sorted((ev.start_ns, ev.end_ns, ev.name)
                  for pname, lines in planes.items()
                  if not pname.startswith(DEVICE_PREFIX)
                  for events in lines.values() for ev in events
                  if ev.name not in HOST_IGNORE and ev.end_ns > ev.start_ns)
    total: Dict[str, float] = collections.defaultdict(float)
    active: List[Tuple[float, float, str]] = []     # heap by end time
    i = 0
    for a, b in gaps:                                # gaps are in order
        while i < len(host) and host[i][0] < b:
            s, e, name = host[i]
            heapq.heappush(active, (e, s, name))
            i += 1
        while active and active[0][0] <= a:
            heapq.heappop(active)
        best, best_ov = "host idle", 0.0
        for e, s, name in active:
            ov = min(e, b) - max(s, a)
            if ov > best_ov:
                best, best_ov = name, ov
        total[best] += (b - a) / 1e9
    return sorted(total.items(), key=lambda kv: -kv[1])
