"""Small pieces every entry and reader shares."""
from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Dict, List, Optional

import numpy as np

from bench import trace as bench_trace


def derive(seed: int, *keys: int) -> int:
    """A 32-bit seed drawn from the run's ``--seed`` and ``keys``."""
    return int(np.random.SeedSequence([int(seed) % 2**64, *keys])
               .generate_state(1)[0])


class Spans:
    """The benchmark's own host spans around its calls into the program.

    Each span is kept as (name, start, end) on the host clock; while a
    trace is being taken it is also a profiler annotation
    ``bench:<name>``, so the trace can say what the host did in a gap."""

    def __init__(self):
        self.spans: List[tuple] = []
        self.annotate = False

    @contextlib.contextmanager
    def __call__(self, name: str):
        t0 = time.perf_counter()
        if self.annotate:
            import jax
            with jax.profiler.TraceAnnotation(bench_trace.SPAN_PREFIX + name):
                yield
        else:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def total(self, name: str) -> float:
        """Seconds spent in spans ``name``."""
        return sum(e - s for n, s, e in self.spans if n == name)


class Tracer:
    """Profiles the first ``seconds`` of the window (``--trace 1``).

    Entries call ``poll`` between items; the trace stops at the first
    poll after ``seconds``, so it holds whole items only.  Writing the
    trace out stalls the host for tens of seconds on a TPU, so entries
    time their window by ``clock``, which stands still while the trace
    is written: the rest of the window runs as an untraced one would."""

    def __init__(self, directory: Optional[str], seconds: float,
                 spans: Spans):
        self.directory = directory
        self.seconds = seconds
        self.spans = spans
        self.active = False
        self.t0 = self.t1 = None
        self.paused = 0.0
        self._window = None

    def clock(self) -> float:
        """Host seconds, less the time spent writing the trace out."""
        return time.perf_counter() - self.paused

    def start(self) -> None:
        if self.directory is None:
            return
        import jax
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._window = jax.profiler.TraceAnnotation(bench_trace.WINDOW)
        self._window.__enter__()
        self.spans.annotate = True
        self.active = True
        self.t0 = self.clock()

    def poll(self) -> None:
        if self.active and self.clock() - self.t0 >= self.seconds:
            self.stop()

    def stop(self) -> None:
        if not self.active:
            return
        import jax
        self.t1 = self.clock()
        self._window.__exit__(None, None, None)
        self.spans.annotate = False
        self.active = False
        jax.profiler.stop_trace()
        self.paused += self.clock() - self.t1

    def traced(self, t: float) -> bool:
        """Did ``clock`` time ``t`` fall inside the traced window?"""
        return self.t0 is not None and self.t0 <= t <= (self.t1 or t)


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader may read of one run."""
    cfg: dict
    peaks: dict
    spans: Spans
    summary: Optional[bench_trace.Summary]
    counters: Dict[str, float]
