"""The program's own spans (``repro:<name>`` annotations) in a traced run.

The program writes its spans into the profiler's trace (``repro.spans``):
each has a name, a start and an end on the device trace's clock, the host
line (thread) it ran on, and its metadata (``step``, ``bytes``, session
counters) as event stats.  A span's parent is the innermost span that
contains it on the same thread; work handed to another thread is matched
by its ``step`` key.  A trace of a program that records no spans yields an
empty list, so every reader built on this returns nothing there.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

from .trace import _clip, _merge, find_xplane

PREFIX = "repro:"


@dataclass(eq=False)
class Span:
    name: str                       # without the prefix
    start: float                    # seconds, the trace's clock
    end: float
    thread: str                     # "<plane>/<line index>"
    meta: Dict[str, Any] = field(default_factory=dict)
    parent: Optional["Span"] = None

    @property
    def dur(self) -> float:
        return self.end - self.start

    def under(self, other: "Span") -> bool:
        """True if ``other`` is this span's parent or an ancestor of it."""
        p = self.parent
        while p is not None:
            if p is other:
                return True
            p = p.parent
        return False


Event = Tuple[str, float, float, str, Dict[str, Any]]


def nest(events: Iterable[Event], window: Optional[Tuple[float, float]] = None
         ) -> List[Span]:
    """Spans from (name, start, end, thread, meta) events, clipped to
    ``window`` (events wholly outside it dropped), each linked to its
    parent; sorted by start."""
    spans: List[Tuple[Tuple[float, float], Span]] = []
    for name, a, b, thread, meta in events:
        if window is not None:
            if b <= window[0] or a >= window[1]:
                continue
            lo, hi = max(a, window[0]), min(b, window[1])
        else:
            lo, hi = a, b
        # the unclipped interval breaks ties between spans clipped alike
        spans.append(((a, b), Span(name, lo, hi, thread, dict(meta))))
    spans.sort(key=lambda p: (p[1].thread, p[1].start, -p[1].end,
                              p[0][0], -p[0][1]))
    stack: List[Span] = []
    for _, s in spans:
        while stack and not (stack[-1].thread == s.thread
                             and s.end <= stack[-1].end):
            stack.pop()
        s.parent = stack[-1] if stack else None
        stack.append(s)
    return sorted((s for _, s in spans), key=lambda s: s.start)


_CACHE: Dict[str, List[Event]] = {}


def read_events(trace_dir: str) -> List[Event]:
    """The ``repro:`` events of the newest trace under ``trace_dir``
    (cached per file: several readers use one run's trace)."""
    path = find_xplane(trace_dir)
    if path not in _CACHE:
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        events: List[Event] = []
        for plane in pd.planes:
            for li, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        s = e.start_ns * 1e-9
                        events.append((e.name[len(PREFIX):], s,
                                       s + e.duration_ns * 1e-9,
                                       f"{plane.name}/{li}", dict(e.stats)))
        _CACHE[path] = events
    return _CACHE[path]


def window_spans(ctx) -> List[Span]:
    """The program's spans of a traced run, clipped to its window (the
    harness's ``window`` span, on the same clock)."""
    if ctx.trace_dir is None or ctx.trace_data is None:
        return []
    return nest(read_events(ctx.trace_dir), ctx.trace_data.window)


def named(spans: Sequence[Span], name: str) -> List[Span]:
    return [s for s in spans if s.name == name]


def within(spans: Sequence[Span], outer: Span, name: str) -> List[Span]:
    """The spans called ``name`` nested under ``outer``."""
    return [s for s in spans if s.name == name and s.under(outer)]


def handed_off(spans: Sequence[Span], name: str, caller: str) -> List[Span]:
    """Spans called ``name`` that run on another thread than a ``caller``
    span with the same ``step``: work the caller handed over (the
    background ``ckpt.save`` of a ``ckpt.save_async``)."""
    calls = {(s.meta.get("step"), s.thread) for s in named(spans, caller)}
    steps = {step for step, _ in calls}
    return [s for s in named(spans, name) if s.meta.get("step") in steps
            and (s.meta.get("step"), s.thread) not in calls]


def idle_s(ops: Sequence[Tuple[float, float]], lo: float, hi: float,
           minus: Sequence[Tuple[float, float]] = ()) -> float:
    """Seconds of [lo, hi) outside ``minus`` in which no interval of
    ``ops`` (device operations) runs."""
    keep = _subtract([(lo, hi)], _merge(_clip(minus, (lo, hi))))
    busy = _merge(_clip(ops, (lo, hi)))
    total = sum(b - a for a, b in keep)
    for a, b in keep:
        total -= sum(b2 - a2 for a2, b2 in _clip(busy, (a, b)))
    return total


def _subtract(iv: Sequence[Tuple[float, float]],
              cut: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """``iv`` less the sorted, disjoint intervals ``cut``."""
    out = []
    for a, b in iv:
        t = a
        for c, d in cut:
            if d <= t or c >= b:
                continue
            if c > t:
                out.append((t, c))
            t = max(t, d)
        if t < b:
            out.append((t, b))
    return out
