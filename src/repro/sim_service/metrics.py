"""Service-level accounting: what the server did, and what it cost.

One :class:`ServiceMetrics` per server instance.  Compile counts come
from the execution engine's honest executed-shape registry
(:mod:`repro.sim_service.streaming`) — ``sim_compiles`` is the headline
(simulator-block executables), ``aux_compiles`` the tiny state-init and
stats-reduce programs.  The persistent on-disk cache keeps its own
counters (:func:`repro.compat.compilation_cache_stats`); where the time
of a tick goes is in the host spans of :mod:`repro.tracing`.
"""
from __future__ import annotations

import dataclasses

__all__ = ["ServiceMetrics"]


@dataclasses.dataclass
class ServiceMetrics:
    submitted: int = 0        # requests accepted into the queue
    rejected: int = 0         # requests refused by backpressure
    completed: int = 0        # requests finished (response built)
    lanes: int = 0            # batch lanes admitted (sweeps count per rate)
    ticks: int = 0            # scheduler ticks executed
    batches: int = 0          # batch runners formed
    blocks: int = 0           # vmapped fence-block calls executed
    sim_compiles: int = 0     # fresh simulator-block executables
    aux_compiles: int = 0     # fresh init/reduce executables
    peak_pending: int = 0     # max lanes waiting in the bounded queue
