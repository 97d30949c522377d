"""Host spans around the calls into each layer, kept in memory.

In a traced run each span is also written into the profiler's trace as a
``TraceAnnotation`` named ``bench:<name>``, on the same clock as the
device's operations, so idle gaps can be named by what the host was doing.
"""

from __future__ import annotations

import time
from contextlib import contextmanager
from typing import Dict, Iterator, List, Optional, Tuple


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.records: List[Tuple[str, float, float]] = []

    @contextmanager
    def span(self, name: str) -> Iterator[None]:
        ann = None
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench:{name}")
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.records.append((name, t0, t1))

    def durations(self, name: str, t0: Optional[float] = None,
                  t1: Optional[float] = None) -> List[float]:
        """Durations of the spans called ``name`` that began in [t0, t1)."""
        return [b - a for n, a, b in self.records
                if n == name and (t0 is None or a >= t0)
                and (t1 is None or a < t1)]

    def by_name(self) -> Dict[str, List[Tuple[float, float]]]:
        out: Dict[str, List[Tuple[float, float]]] = {}
        for n, a, b in self.records:
            out.setdefault(n, []).append((a, b))
        return out
