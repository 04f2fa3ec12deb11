"""The router step's share of its roofline: the least time a cycle can
take (the mesh state read and written once at peak HBM bandwidth) over
the device busy time per cycle of the traced drain jobs."""
from bench.roofline import least_seconds_per_cycle


def read(view):
    cycles = view.counters.get("traced_cycles")
    bandwidth = view.peaks.get("hbm_bytes_per_s")
    if view.summary is None or not cycles or not bandwidth \
            or view.summary.mean_busy_s <= 0:
        return None
    per_cycle = view.summary.mean_busy_s / cycles
    return 100 * least_seconds_per_cycle(view.cfg, bandwidth) / per_cycle
