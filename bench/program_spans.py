"""The program's own host spans (``repro.tracing``) of a run's window.

A program span counts when it lies inside one of the benchmark's own
spans of the window (``view.spans``: ``tick``, ``submit``,
``telemetry``, ...), which leaves set-up out; both are timed by
``time.perf_counter()``.  Nothing here raises on a program that keeps
no spans: the readers then return ``None`` and the result line leaves
their metrics out.  ``None`` too when the program's ring let go of
spans that may have been the window's.
"""
from __future__ import annotations

import bisect
from typing import Optional


def window_spans(view) -> Optional[list]:
    """The program's spans inside the window's benchmark spans, or None."""
    try:
        from repro import tracing
    except ImportError:
        return None
    outer = sorted((s, e) for _n, s, e in view.spans.spans)
    if not outer:
        return None
    recs = tracing.spans()
    # the ring lets its oldest go first: none of the window's went if the
    # oldest kept span closed before the window's first benchmark span
    if tracing.dropped() and (not recs or recs[0].end >= outer[0][0]):
        return None
    starts = [s for s, _e in outer]
    inside = []
    for r in recs:
        i = bisect.bisect_right(starts, r.start) - 1
        if i >= 0 and r.end <= outer[i][1]:
            inside.append(r)
    return inside


def mean_ms(view, name: str, own: bool = False) -> Optional[float]:
    """Mean milliseconds of the window's spans ``name``; with ``own``,
    of each span less its children.  None where there are none."""
    recs = window_spans(view)
    if not recs:
        return None
    picked = [r for r in recs if r.name == name]
    if not picked:
        return None
    if own:
        from repro.tracing import self_seconds
        secs = self_seconds(recs)
        total = sum(secs[r.index] for r in picked)
    else:
        total = sum(r.seconds for r in picked)
    return total / len(picked) * 1e3
