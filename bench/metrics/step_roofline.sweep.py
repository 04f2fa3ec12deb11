"""The sweep's router step against its roofline: the least time a
point-cycle can take over the device busy time per point-cycle.

The least time reads and writes the mesh state once at peak HBM
bandwidth, with the tile memory counted as the one word per tile that a
cycle can read and the one it can write (an endpoint serves one request
per cycle), not as the whole memory: ``bench/roofline.py`` counts all of
it, which at 16,384 words per tile would be 68.3 MB a point-cycle,
almost none of it touched."""
from bench.roofline import least_seconds_per_cycle


def read(view):
    cycles = view.counters.get("traced_point_cycles_per_chip")
    bandwidth = view.peaks.get("hbm_bytes_per_s")
    if view.summary is None or not cycles or not bandwidth \
            or view.summary.mean_busy_s <= 0:
        return None
    per_cycle = view.summary.mean_busy_s / cycles
    least = least_seconds_per_cycle(dict(view.cfg, mem_words=1), bandwidth)
    return 100 * least / per_cycle
