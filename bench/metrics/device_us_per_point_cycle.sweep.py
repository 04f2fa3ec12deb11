"""Device busy microseconds per point-cycle: each chip's mean busy time
over the point-cycles it simulated (its rows of the sweeps that ran
whole inside the traced window, times the phases' cycles)."""


def read(view):
    cycles = view.counters.get("traced_point_cycles_per_chip")
    if view.summary is None or not cycles:
        return None
    return view.summary.mean_busy_s / cycles * 1e6
