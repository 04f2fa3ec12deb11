"""The comparisons that decide ``correct``: program against reference.

Every number compared is exact except ``lat_mean_gap``: a mean latency
is a float32 sum over the histogram, whose rounding depends on the
order the device adds in.  The integers behind each PhaseStats float
(packets offered, accepted, delivered, link crossings) are recovered
from it exactly: each float is a count over a known denominator.
"""
from __future__ import annotations

import numpy as np

from bench.reference import LAT_BINS, TELEMETRY_FIELDS

QUANTILES = (("lat_p50", 0.50), ("lat_p95", 0.95), ("lat_p99", 0.99))


def drain_numbers(pairs) -> dict:
    """``pairs`` of (program, reference) outputs, each (drain cycle,
    telemetry dict).  A job mismatches when its drain cycle or any
    telemetry field differs."""
    mismatched, gap = 0, 0
    for (c_got, t_got), (c_want, t_want) in pairs:
        gap = max(gap, abs(int(c_got) - int(c_want)))
        same = int(c_got) == int(c_want) and all(
            np.array_equal(np.asarray(t_got[f]), np.asarray(t_want[f]))
            for f in TELEMETRY_FIELDS + ("cycles",))
        mismatched += not same
    return {"mismatched_jobs": mismatched, "drain_cycle_gap": gap}


def _quantile(hist: np.ndarray, q: float) -> int:
    """Smallest bin whose cumulative count reaches ceil(q * total), with
    the product taken in float32 as the program's reduction takes it."""
    total = int(hist.sum())
    if total == 0:
        return 0
    target = int(np.ceil(np.float32(q) * np.float32(total)))
    idx = int(np.searchsorted(np.cumsum(hist), max(target, 1)))
    return min(idx, LAT_BINS - 1)


def expected_counts(raw: dict) -> dict:
    """The exact integers a window's PhaseStats encode, from the
    reference's raw counts."""
    hist = np.asarray(raw["hist"], np.int64)
    util = np.asarray(raw["d_util"], np.int64)
    nz = np.flatnonzero(hist)
    out = {"offered": int(raw["d_inj"]), "accepted": int(raw["d_comp"]),
           "delivered": int(hist.sum()),
           "peak_link_util": int(util[0, ..., 1:].max()),
           "hops": int(util[..., 1:].sum()),
           "lat_max": int(nz[-1]) if nz.size else 0}
    out.update({k: _quantile(hist, q) for k, q in QUANTILES})
    return out


def exact_lat_mean(raw: dict) -> float:
    hist = np.asarray(raw["hist"], np.int64)
    return float((np.arange(LAT_BINS) * hist).sum()) / max(int(hist.sum()), 1)


def stats_from_raw(raw: dict, ntiles: int, measure: int) -> dict:
    """PhaseStats as a float32 reduction of raw counts yields them: how
    the control's output is put in the program's place."""
    c = expected_counts(raw)
    f32 = np.float32
    denom = f32(measure * ntiles)
    hist = np.asarray(raw["hist"], np.int64)
    out = {k: float(f32(c[k]) / denom)
           for k in ("offered", "accepted", "delivered")}
    out["peak_link_util"] = float(f32(c["peak_link_util"]) / f32(measure))
    out["hops"] = float(f32(c["hops"]))
    out.update({k: float(c[k]) for k, _ in QUANTILES})
    out["lat_max"] = float(c["lat_max"])
    out["lat_mean"] = float(f32(exact_lat_mean(raw)))
    out["hist"] = hist
    return out


def phase_numbers(pairs, ntiles: int, measure: int, noun: str) -> dict:
    """``pairs`` of (program PhaseStats as a dict of floats, with
    ``hist`` where the program returns it; reference raw counts)."""
    mismatched, gap = 0, 0.0
    per_tile_cycle = measure * ntiles
    scale = {"offered": per_tile_cycle, "accepted": per_tile_cycle,
             "delivered": per_tile_cycle, "peak_link_util": measure}
    for got, raw in pairs:
        want = expected_counts(raw)
        same = True
        for k, v in want.items():
            if k in scale:
                same &= int(round(float(got[k]) * scale[k])) == v
            elif k == "hops":
                same &= float(got[k]) == float(np.float32(v))
            else:
                same &= float(got[k]) == v
        if "hist" in got:
            same &= np.array_equal(np.asarray(got["hist"], np.int64),
                                   np.asarray(raw["hist"], np.int64))
        mismatched += not same
        mean = exact_lat_mean(raw)
        gap = max(gap, abs(float(got["lat_mean"]) - mean) / max(mean, 1.0))
    return {f"mismatched_{noun}": mismatched, "lat_mean_gap": gap}
