"""Mean host time of one fence block (``sim_service.block``) less its
wait on the device: dispatch, the read-backs and the chunk deltas."""
from bench.program_spans import mean_ms


def read(view):
    return mean_ms(view, "sim_service.block", own=True)
