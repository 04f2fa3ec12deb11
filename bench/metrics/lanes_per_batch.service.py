"""Lanes admitted per batch formed in the window (ServiceMetrics)."""


def read(view):
    batches = view.counters.get("batches")
    if not batches:
        return None
    return view.counters["lanes"] / batches
