"""Host milliseconds per drain job in the benchmark's own spans around
the program build, the JaxMeshSim's construction and program load,
and the telemetry pull."""


def read(view):
    jobs = view.counters.get("jobs")
    if not jobs:
        return None
    host = sum(view.spans.total(n) for n in ("build", "attach", "telemetry"))
    return host / jobs * 1e3
