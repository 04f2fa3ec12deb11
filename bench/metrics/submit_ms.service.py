"""Mean time of one ``SimServer.submit`` (``sim_service.submit``):
building the request's program on the host, its upload, the enqueue."""
from bench.program_spans import mean_ms


def read(view):
    return mean_ms(view, "sim_service.submit")
