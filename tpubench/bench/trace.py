"""Reduction of a profiler trace to what the per-layer metrics read.

Device operations are the events of each device plane's ``XLA Ops`` line;
the traced window is the host span ``bench:window``; host spans are the
other ``bench:*`` annotations.  Busy time is the union of operation
intervals inside the window, averaged over the devices used; an idle gap
is named by the innermost host span open at its midpoint.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]  # seconds

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
WINDOW_SPAN = "window"
#: what an idle gap is called when no harness span was open
NO_SPAN = "outside spans"
#: operations that only contain others, which the trace lists as well
CONTAINERS = ("while", "conditional", "call")

_HLO = re.compile(r"^(%?[\w.\-]+) = (\S*?)[{ ].*? ([a-z][a-z0-9\-]*)\(")


def short_name(name: str) -> str:
    """``%fusion.12 fusion f32[8,1024]`` for an HLO instruction's text."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(3)} {m.group(2)}" if m else name


def opcode(name: str) -> str:
    m = _HLO.match(name)
    return m.group(3) if m else ""


@dataclass
class Op:
    name: str
    start: float
    dur: float
    device: str


@dataclass
class Trace:
    window: Interval
    ops: List[Op]
    devices: List[str]
    spans: List[Tuple[str, float, float]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_s(self) -> float:
        """Union of operation intervals in the window, mean over devices."""
        if not self.devices:
            return 0.0
        per = [_union_len(_clip([(o.start, o.start + o.dur) for o in self.ops
                                 if o.device == d], self.window))
               for d in self.devices]
        return sum(per) / len(per)

    def op_time(self, match) -> Tuple[float, int]:
        """(seconds, count) of in-window operations whose name ``match``
        accepts, summed over devices."""
        t, n = 0.0, 0
        for o in self.ops:
            if match(o.name) and self.window[0] <= o.start < self.window[1]:
                t += o.dur
                n += 1
        return t, n

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` operations with the most device time in the window
        (per device), leaving out loops and calls whose contents the trace
        also lists."""
        tot: Dict[str, float] = {}
        for o in self.ops:
            if self.window[0] <= o.start < self.window[1] \
                    and opcode(o.name) not in CONTAINERS:
                n = short_name(o.name)
                tot[n] = tot.get(n, 0.0) + o.dur
        per_dev = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t / per_dev] for n, t in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps of the first device, each named by
        the innermost host span open at its midpoint."""
        if not self.devices:
            return []
        busy = _merge(_clip([(o.start, o.start + o.dur) for o in self.ops
                             if o.device == self.devices[0]], self.window))
        gaps, t = [], self.window[0]
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        gaps.sort(key=lambda g: g[0] - g[1])
        return [[self.span_at((a + b) / 2), b - a] for a, b in gaps[:k]]

    def span_at(self, t: float) -> str:
        best, best_len = NO_SPAN, float("inf")
        for n, a, b in self.spans:
            if n != WINDOW_SPAN and a <= t < b and b - a < best_len:
                best, best_len = n, b - a
        return best


def _clip(iv: Iterable[Interval], w: Interval) -> List[Interval]:
    return [(max(a, w[0]), min(b, w[1])) for a, b in iv
            if b > w[0] and a < w[1]]


def _merge(iv: Sequence[Interval]) -> List[Interval]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _union_len(iv: Sequence[Interval]) -> float:
    return sum(b - a for a, b in _merge(iv))


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def from_events(device_events: Dict[str, List[Tuple[str, float, float]]],
                host_spans: List[Tuple[str, float, float]]) -> Trace:
    """Build a Trace from plain events: per device (name, start_s, dur_s),
    and host spans (name without the prefix, start_s, end_s)."""
    windows = [(a, b) for n, a, b in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError("the trace holds no bench:window span")
    ops = [Op(n, s, d, dev) for dev, evs in device_events.items()
           for n, s, d in evs]
    return Trace(window=windows[-1], ops=ops, devices=sorted(device_events),
                 spans=host_spans)


def load(trace_dir: str, devices: Optional[int] = None) -> Trace:
    """Read the newest trace under ``trace_dir``; keep the first
    ``devices`` device planes (all when None)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(trace_dir))
    device_events: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_events[plane.name] = [
                        (e.name, e.start_ns * 1e-9, e.duration_ns * 1e-9)
                        for e in line.events]
        else:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        spans.append((e.name[len(SPAN_PREFIX):], s,
                                      s + e.duration_ns * 1e-9))
    if devices is not None:
        keep = sorted(device_events)[:devices]
        device_events = {k: device_events[k] for k in keep}
    return from_events(device_events, spans)
