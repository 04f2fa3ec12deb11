"""Mean wait for one fence block of a batch on the device
(``sim_service.block.wait``): the block's device time and its launch."""
from bench.program_spans import mean_ms


def read(view):
    return mean_ms(view, "sim_service.block.wait")
