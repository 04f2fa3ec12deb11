"""Simulation-as-a-service: the continuous-batched, compile-cached,
streaming front end over the JAX mesh simulator.

The ROADMAP's serving story, closed: concurrent phased-measurement
requests (:class:`SimRequest`) and saturation-curve sweeps
(:class:`SweepRequest`) are queued, bucketed by compiled shape
(:class:`~repro.netsim_jax.measure.SweepKey` + padded program length +
streaming cadence), executed as ONE vmapped call per bucket per tick,
and streamed back per fence block — with results **bit-identical** to
direct :func:`repro.netsim_jax.measure.phased_stats` runs (asserted in
``tests/test_sim_service.py``).

Entry points:

* :class:`SimService` — synchronous facade (``run`` / ``run_one`` /
  ``stream``);
* :class:`SimServer` — the async server (``submit`` + a ``serve()``
  task; consume ``Ticket.stream()`` / ``Ticket.result()``);
* :func:`repro.compat.enable_persistent_compilation_cache` arms JAX's
  persistent on-disk compilation cache for the process (shared with
  :func:`repro.dse.run_sweep`), making process-cold starts on known
  shapes ~0 recompiles.
"""
from .bucketing import BucketKey, bucket_key, next_pow2  # noqa: F401
from .metrics import ServiceMetrics  # noqa: F401
from .request import (LaneSpec, ServiceOverloaded, SimRequest,  # noqa: F401
                      SimResponse, SweepRequest, SweepResponse)
from .server import (SimServer, SimService, TelemetryChunk,  # noqa: F401
                     Ticket)
from .streaming import BatchRunner, clear_service_cache  # noqa: F401

__all__ = ["SimRequest", "SweepRequest", "SimResponse", "SweepResponse",
           "LaneSpec", "ServiceOverloaded", "BucketKey", "bucket_key",
           "next_pow2", "ServiceMetrics", "SimServer", "SimService",
           "TelemetryChunk", "Ticket", "BatchRunner",
           "clear_service_cache"]
