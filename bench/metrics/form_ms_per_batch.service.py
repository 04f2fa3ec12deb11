"""Mean time to form a batch (``sim_service.batch.form``): taking the
waiting lanes, stacking and padding their programs, the state's init
and the first read-back."""
from bench.program_spans import mean_ms


def read(view):
    return mean_ms(view, "sim_service.batch.form")
