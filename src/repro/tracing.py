"""Host spans at the program's layer boundaries, on the profiler's clock.

``span(name, **attrs)`` times a block of host code with
``time.perf_counter()`` and keeps it as a :class:`Span`: its name, start
and end, the index of the span that encloses it on the same thread, and
its attributes.  Spans go into one bounded in-memory ring per process
(:data:`CAPACITY` entries; the oldest go first and are counted in
:func:`dropped`), read with :func:`spans`.  Recording is always on.

Each span is also a ``jax.profiler.TraceAnnotation`` of the same name
with the attributes as its stats, so a profile taken with
``jax.profiler.start_trace`` holds it on the host plane, on the same
clock as the device's operations.  With no profile running the
annotation costs about a microsecond.

Spans of one request share an identifier: a request's spans carry
``rid``, a batch's ``batch`` (a serial number per server), and each
response's ``metrics["batch"]`` names the batch that finished it.
"""
from __future__ import annotations

import collections
import contextlib
import itertools
import threading
import time
from typing import Dict, Iterable, Iterator, List, NamedTuple, Optional

import jax

__all__ = ["CAPACITY", "Span", "Recorder", "RECORDER", "span", "spans",
           "dropped", "self_seconds"]

CAPACITY = 65_536


class Span(NamedTuple):
    index: int                # serial number, in the order spans opened
    name: str
    start: float              # time.perf_counter() seconds
    end: float
    parent: Optional[int]     # index of the enclosing span on this thread
    attrs: Dict[str, object]

    @property
    def seconds(self) -> float:
        return self.end - self.start


class Recorder:
    """A ring of the last ``capacity`` closed spans; ``dropped`` counts
    the spans it has let go."""

    def __init__(self, capacity: int = CAPACITY):
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._serial = itertools.count()
        self._lock = threading.Lock()
        self._local = threading.local()
        self.dropped = 0

    @contextlib.contextmanager
    def span(self, name: str, **attrs) -> Iterator[None]:
        stack = self._local.__dict__.setdefault("stack", [])
        index = next(self._serial)
        parent = stack[-1] if stack else None
        stack.append(index)
        with jax.profiler.TraceAnnotation(name, **attrs):
            start = time.perf_counter()
            try:
                yield
            finally:
                end = time.perf_counter()
                stack.pop()
                with self._lock:
                    if len(self._ring) == self._ring.maxlen:
                        self.dropped += 1
                    self._ring.append(
                        Span(index, name, start, end, parent, attrs))

    def spans(self) -> List[Span]:
        """The ring's spans, oldest closed first."""
        with self._lock:
            return list(self._ring)


RECORDER = Recorder()


def span(name: str, **attrs):
    """Time the enclosed block as a span of the process's recorder."""
    return RECORDER.span(name, **attrs)


def spans() -> List[Span]:
    """The process's recorded spans, oldest closed first."""
    return RECORDER.spans()


def dropped() -> int:
    """How many spans the process's ring has let go."""
    return RECORDER.dropped


def self_seconds(records: Iterable[Span]) -> Dict[int, float]:
    """Each span's seconds less those of its children among ``records``,
    by index."""
    records = list(records)
    own = {r.index: r.seconds for r in records}
    for r in records:
        if r.parent in own:
            own[r.parent] -= r.seconds
    return own
