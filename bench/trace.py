"""Reduce a profiler trace of a traced window to device busy and idle time.

The benchmark wraps the traced part of its window in a host annotation
named ``WINDOW`` and each call it makes into the program in an
annotation ``bench:<span>``.  Device time comes from the device planes
(``/device:TPU:<n>``) of the same trace, on the same clock.

* busy: the union of the intervals in which an operation ran on a device,
  clipped to the window; averaged over the devices used;
* idle gaps: the complement of busy inside the window, each labelled by
  the innermost host span that covers its midpoint, or ``host (no span)``;
* device ops: total device time by operation, named by its HLO
  instruction (``%fusion.135``); loops and calls are left out of the
  totals, since the operations inside them are counted.

``load`` turns an ``.xplane.pb`` into plain lists (the form a recorded
trace keeps under ``bench/tests/data``); ``summarize`` does the rest.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

WINDOW = "bench:traced"
SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINES = ("XLA Ops",)
CONTAINERS = ("%while", "%call", "%conditional")


def load(path: Path) -> dict:
    """Device op events and the benchmark's host spans of one trace:
    ``{"devices": {plane: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(path))
    devices: Dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = devices.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in OP_LINES:
                    ops.extend([e.name.split(" = ")[0], e.start_ns,
                                e.duration_ns] for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events
                            if e.name.startswith(SPAN_PREFIX))
    return {"devices": devices, "host": host}


def find_xplane(trace_dir: Path) -> Path:
    found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged, sorted intervals: (starts, ends) arrays."""
    order = np.argsort(starts, kind="stable")
    s, reach = starts[order], np.maximum.accumulate(ends[order])
    first = np.ones(len(s), bool)
    first[1:] = s[1:] > reach[:-1]
    idx = np.flatnonzero(first)
    return s[idx], reach[np.r_[idx[1:] - 1, len(s) - 1]]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: List[float]                 # per device, in plane order
    device_ops: List[Tuple[str, float]]  # the 10 largest, seconds
    idle_gaps: List[Tuple[str, float]]   # the 10 longest, seconds

    @property
    def mean_busy_s(self) -> float:
        return float(np.mean(self.busy_s)) if self.busy_s else 0.0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.mean_busy_s / self.window_s


def summarize(events: dict, top: int = 10) -> Optional[Summary]:
    """Busy time per device, the largest ops and the longest idle gaps
    inside the host annotation ``WINDOW``; None when the trace has no
    device plane (a run on the CPU)."""
    if not events["devices"]:
        return None
    windows = [(s, s + d) for n, s, d in events["host"] if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} span, found {len(windows)}")
    w0, w1 = windows[0]
    spans = [(n, s, s + d) for n, s, d in events["host"] if n != WINDOW]
    busy, per_op, gaps = [], {}, []
    for plane in sorted(events["devices"],
                        key=lambda p: int(DEVICE_PLANE.match(p).group(1))):
        ops = events["devices"][plane]
        if not ops:
            continue
        names = np.asarray([o[0] for o in ops], object)
        s = np.asarray([o[1] for o in ops], float)
        e = s + np.asarray([o[2] for o in ops], float)
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        keep = e > s
        if not keep.any():
            busy.append(0.0)
            continue
        names, s, e = names[keep], s[keep], e[keep]
        bs, be = _union(s, e)
        busy.append(float((be - bs).sum()) * 1e-9)
        uniq, inv = np.unique(names, return_inverse=True)
        for name, t in zip(uniq, np.bincount(inv, weights=e - s)):
            if not str(name).startswith(CONTAINERS):
                per_op[name] = per_op.get(name, 0.0) + t * 1e-9
        ga, gb = np.r_[w0, be], np.r_[bs, w1]
        for i in np.argsort(ga - gb)[:top]:
            if gb[i] > ga[i]:
                gaps.append((_label(spans, (ga[i] + gb[i]) / 2),
                             (gb[i] - ga[i]) * 1e-9))
    if not busy:
        raise ValueError("the trace holds no device operation in the window")
    return Summary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy,
        device_ops=sorted(per_op.items(), key=lambda kv: -kv[1])[:top],
        idle_gaps=sorted(gaps, key=lambda g: -g[1])[:top])


def _label(spans, t: float) -> str:
    """The innermost benchmark span that covers time ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1]):
            best = (name, e - s)
    return best[0][len(SPAN_PREFIX):] if best else "host (no span)"
