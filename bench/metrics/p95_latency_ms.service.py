"""The 95th percentile of the window's request latencies, each from its
due time to its response, a refused or unanswered request counting as
answered when the run stopped waiting."""


def read(view):
    return view.counters.get("latency_p95_ms")
