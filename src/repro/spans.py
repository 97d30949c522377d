"""Host spans of the program, written into the JAX profiler's own trace.

``span(name, **meta)`` returns a ``jax.profiler.TraceAnnotation`` named
``repro:<name>``.  While a profiler records (``jax.profiler.trace``,
``start_trace``, or a capture from TensorBoard) the span lands in its trace
on the same clock as the device's ``XLA Ops``, with ``meta`` as the event's
stats; otherwise it costs one object and one check.  Nesting is by time on
the recording thread; work handed to another thread carries a ``step`` key
that matches the span that handed it over.

Metadata that costs something to compute is attached only while recording::

    with span("ckpt.save", step=step) as sp:
        ...
        if sp.is_enabled():
            sp.set_metadata(bytes=nbytes)

Nothing here imports jax: until jax is loaded no profiler can be recording,
so a span is a no-op and ``repro.core`` stays importable without jax.
"""

from __future__ import annotations

import sys

PREFIX = "repro:"


class _Off:
    """The span while jax is not loaded."""

    @staticmethod
    def is_enabled() -> bool:
        return False

    def set_metadata(self, **meta) -> None:
        pass

    def __enter__(self) -> "_Off":
        return self

    def __exit__(self, *exc) -> bool:
        return False


_OFF = _Off()


def _profiler():
    return sys.modules.get("jax.profiler")


def span(name: str, **meta):
    """A ``repro:<name>`` annotation carrying ``meta`` (a context manager)."""
    prof = _profiler()
    if prof is None:
        return _OFF
    return prof.TraceAnnotation(PREFIX + name, **meta)


def step_span(name: str, step: int):
    """``span`` as a ``StepTraceAnnotation``, so that the profiler's step
    view splits the trace at each training step."""
    prof = _profiler()
    if prof is None:
        return _OFF
    return prof.StepTraceAnnotation(PREFIX + name, step_num=step, step=step)
