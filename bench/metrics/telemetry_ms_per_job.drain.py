"""Milliseconds per drain job in ``Telemetry.of`` (``mesh.telemetry.of``
spans): the device-to-host copies of the job's telemetry record."""
from bench.program_spans import window_spans


def read(view):
    spans = window_spans(view)
    jobs = view.counters.get("jobs")
    pulls = [r.seconds for r in spans or () if r.name == "mesh.telemetry.of"]
    if not pulls or not jobs:
        return None
    return sum(pulls) / jobs * 1e3
