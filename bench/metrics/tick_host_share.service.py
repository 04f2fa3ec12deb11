"""Share of the service's ticks (``sim_service.tick`` spans) the host
spent on anything but waiting for a block on the device
(``sim_service.block.wait``, each inside its tick)."""
from bench.program_spans import window_spans


def read(view):
    spans = window_spans(view)
    if not spans:
        return None
    tick = sum(r.seconds for r in spans if r.name == "sim_service.tick")
    wait = sum(r.seconds for r in spans
               if r.name == "sim_service.block.wait")
    if tick <= 0:
        return None
    return 100 * (tick - wait) / tick
