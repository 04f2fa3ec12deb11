"""Device busy microseconds per simulated cycle, over the drain jobs
that ran whole inside the traced window."""


def read(view):
    cycles = view.counters.get("traced_cycles")
    if view.summary is None or not cycles:
        return None
    return view.summary.mean_busy_s / cycles * 1e6
