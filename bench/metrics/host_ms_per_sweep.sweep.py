"""Host milliseconds per sweep: each ``dse.sweep`` span less its waits on
the device (``dse.bucket.wait``): expansion, the programs' build and
upload, dispatch, the read-back and the records."""
from bench.program_spans import window_spans


def read(view):
    spans = window_spans(view) or ()
    sweeps = [r.seconds for r in spans if r.name == "dse.sweep"]
    if not sweeps:
        return None
    wait = sum(r.seconds for r in spans if r.name == "dse.bucket.wait")
    return (sum(sweeps) - wait) / len(sweeps) * 1e3
