"""Reduction of a ``jax.profiler`` trace to device busy time, idle gaps and
the operations that took the most time.

The harness brackets its measured window with a ``TraceAnnotation`` named
:data:`WINDOW`; every number here is taken inside that bracket, on the
trace's own clock.  A device's busy time is the union of the intervals of
its operation events (overlapping events count once); its idle time is
the rest of the window.  Which planes are devices and which of their
lines hold operations is set by :data:`DEVICE_PLANE` and
:data:`OP_LINES`, read off a TPU v5e trace (see ``PERF.md``).
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
#: device planes of a TPU trace are named ``/device:TPU:<id>``
DEVICE_PLANE = "/device:TPU:"
#: the lines of a device plane whose events are operations on the device
#: (names matched as prefixes)
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"

Interval = Tuple[float, float]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under ``log_dir``."""
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def merge(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted, disjoint union of ``intervals`` (touching ones join)."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: float, hi: float
         ) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def union_length(intervals: Iterable[Interval], lo: float, hi: float
                 ) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(busy: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``[lo, hi]`` that ``busy`` (merged) leaves idle."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


class Trace:
    """Events of one trace, grouped as the reduction needs them."""

    def __init__(self, path: str, *, device_plane: str = DEVICE_PLANE,
                 op_lines: Sequence[str] = OP_LINES,
                 host_plane: str = HOST_PLANE):
        from jax.profiler import ProfileData
        pd = ProfileData.from_file(path)
        #: device plane name -> [(start_ns, end_ns, op name)]
        self.ops: Dict[str, List[Tuple[float, float, str]]] = {}
        #: host events: [(start_ns, end_ns, name)]
        self.host: List[Tuple[float, float, str]] = []
        self.window: Optional[Interval] = None
        for plane in pd.planes:
            if plane.name.startswith(device_plane):
                evs = self.ops.setdefault(plane.name, [])
                for line in plane.lines:
                    if line.name.startswith(tuple(op_lines)):
                        evs.extend((e.start_ns, e.start_ns + e.duration_ns,
                                    e.name) for e in line.events)
            if plane.name == host_plane:
                for line in plane.lines:
                    for e in line.events:
                        span = (e.start_ns, e.start_ns + e.duration_ns,
                                e.name)
                        if e.name == WINDOW:
                            self.window = span[:2]
                        elif e.duration_ns > 0:
                            self.host.append(span)
        if self.window is None:
            raise ValueError(f"no {WINDOW!r} annotation in {path}")

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) / 1e9

    def busy_s(self) -> Dict[str, float]:
        """Busy seconds of each device plane inside the window."""
        lo, hi = self.window
        return {name: union_length(((s, e) for s, e, _ in evs), lo, hi) / 1e9
                for name, evs in self.ops.items()}

    def device_ops(self, top: int = 10) -> List[Tuple[str, float]]:
        """Operations by total device seconds inside the window, summed
        over devices."""
        lo, hi = self.window
        total: Dict[str, float] = defaultdict(float)
        for evs in self.ops.values():
            for s, e, name in evs:
                if e > s and e > lo and s < hi:
                    total[name] += (min(e, hi) - max(s, lo)) / 1e9
        return sorted(total.items(), key=lambda kv: -kv[1])[:top]

    def idle_gaps(self, top: int = 10) -> List[Tuple[str, float]]:
        """The longest idle gaps of any device, each named by the host
        event that overlaps it most (``device: host event``)."""
        lo, hi = self.window
        found = []
        for dev, evs in self.ops.items():
            busy = merge(clip(((s, e) for s, e, _ in evs), lo, hi))
            found.extend((dev, g) for g in gaps(busy, lo, hi))
        found.sort(key=lambda dg: -(dg[1][1] - dg[1][0]))
        out = []
        for dev, (s, e) in found[:top]:
            out.append((f"{dev.rsplit(':', 1)[-1]}: {self._host_at(s, e)}",
                        (e - s) / 1e9))
        return out

    def _host_at(self, s: float, e: float) -> str:
        """Name of the host event, shorter than the gap's window, that
        overlaps ``[s, e]`` most."""
        best, name = 0.0, "no host event"
        for hs, he, hn in self.host:
            if he - hs >= (self.window[1] - self.window[0]):
                continue
            ov = min(he, e) - max(hs, s)
            if ov > best:
                best, name = ov, hn
        return name
