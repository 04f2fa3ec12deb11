"""Simulation-service amortization benchmark + acceptance gates.

The service's reason to exist is fixed-cost amortization: ONE XLA
compile and ONE vmapped dispatch should serve every concurrent request
of the same compiled shape.  This suite measures exactly that story and
writes ``experiments/service_latency.json``:

* **sequential baseline** — N same-shape requests served as N
  *independent cold client sessions*: every jit/service cache (and the
  persistent on-disk cache) cleared between requests, so each pays the
  full XLA compile a fresh process would.  This leg runs FIRST — its
  per-request ``jax.clear_caches()`` would wipe the service's compiled
  programs.
* **cold service** — the same N requests submitted concurrently to one
  freshly-cleared :class:`~repro.sim_service.SimService`: they ride one
  batch, compile exactly ONCE (asserted via the service's executed-shape
  accounting), and must beat the sequential leg by >= 3x wall-clock
  throughput.
* **warm service** — a second service instance re-serves the shape with
  ZERO fresh executables (asserted), giving the steady-state per-request
  latency.
* **persistent compile cache** — the same shape re-compiled after
  ``jax.clear_caches()`` with the on-disk cache armed: XLA deserializes
  the executable (disk hits > 0) instead of re-running the compiler,
  the cross-*process* warm-start story.

Every service response is checked bit-identical to its sequential
direct-run twin before any timing is reported.
"""
from __future__ import annotations

import time
from typing import Dict, List

import numpy as np

import jax

from repro.compat import (compilation_cache_stats,
                          disable_persistent_compilation_cache,
                          enable_persistent_compilation_cache,
                          reset_compilation_cache_stats)
from repro.mesh import MeshConfig
from repro.netsim_jax.measure import (clear_sweep_cache, phased_stats,
                                      _as_simconfig)
from repro.netsim_jax.sim import init_state, load_program
from repro.netsim_jax.traffic import make_traffic
from repro.sim_service import SimRequest, SimService, clear_service_cache

__all__ = ["bench_service_amortization", "run"]

N_REQUESTS = 8
THROUGHPUT_FLOOR = 3.0       # cold service must beat sequential by this
PHASES = dict(warmup=100, measure=200, drain=200, check_every=100)


def _requests(cfg: MeshConfig) -> List[SimRequest]:
    return [SimRequest(cfg=cfg, pattern="uniform", load=0.3, seed=s,
                       **PHASES) for s in range(N_REQUESTS)]


def _clear_all_caches() -> None:
    """What a fresh client process looks like, in-process: every jitted
    program and executed-shape registry dropped."""
    jax.clear_caches()
    clear_sweep_cache()
    clear_service_cache()


def _direct(req: SimRequest):
    """One cold direct run (the per-request work a non-batching client
    performs); returns host PhaseStats."""
    cfg = _as_simconfig(req.cfg)
    length = int(np.ceil(req.load * req.horizon)) + 1
    prog = load_program(make_traffic(req.pattern, req.cfg.nx, req.cfg.ny,
                                     length, rate=req.load, seed=req.seed,
                                     topology=req.cfg.topology))
    stats = phased_stats(cfg, prog, init_state(cfg), req.warmup,
                         req.measure, req.drain)
    return type(stats)(*(np.asarray(f) for f in stats))


def bench_service_amortization(n: int = N_REQUESTS) -> Dict:
    cfg = MeshConfig(nx=4, ny=4)
    reqs = _requests(cfg)
    checks: Dict[str, bool] = {}
    prior_cache_dir = compilation_cache_stats()["dir"]
    disable_persistent_compilation_cache()

    # -- leg 1: sequential cold sessions (must run first: it clears the
    # caches the service legs then warm up) ------------------------------
    seq_lat: List[float] = []
    direct_stats = []
    for r in reqs:
        _clear_all_caches()
        t0 = time.perf_counter()
        direct_stats.append(_direct(r))
        seq_lat.append(time.perf_counter() - t0)
    seq_wall = sum(seq_lat)

    # -- leg 2: cold service, one concurrent batch -----------------------
    _clear_all_caches()
    svc = SimService(max_batch=n)
    t0 = time.perf_counter()
    cold_resp = svc.run(reqs)
    cold_wall = time.perf_counter() - t0
    checks["one_batch"] = svc.metrics.batches == 1
    checks["compiles_once"] = svc.metrics.sim_compiles == 1
    checks["bit_identical"] = all(
        all((np.asarray(getattr(d, f)) == np.asarray(getattr(r.stats, f)))
            .all() for f in d._fields)
        for d, r in zip(direct_stats, cold_resp))
    ratio = seq_wall / max(cold_wall, 1e-9)
    checks["throughput_3x"] = ratio >= THROUGHPUT_FLOOR

    # -- leg 3: warm service (same process, new instance) ----------------
    warm = SimService(max_batch=n)
    t0 = time.perf_counter()
    warm_resp = warm.run(reqs)
    warm_wall = time.perf_counter() - t0
    checks["warm_zero_recompiles"] = (
        warm.metrics.sim_compiles == 0 and warm.metrics.aux_compiles == 0
        and all(r.metrics["new_sim_compiles"] == 0 for r in warm_resp))

    # -- leg 4: persistent on-disk compile cache (cross-process story) ---
    import os
    import tempfile
    prior_env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    try:
        with tempfile.TemporaryDirectory() as td:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = td
            enable_persistent_compilation_cache()
            reset_compilation_cache_stats()
            _clear_all_caches()
            SimService(max_batch=n).run(reqs)   # populates the disk cache
            misses = compilation_cache_stats()["misses"]
            _clear_all_caches()                 # drop in-process programs
            t0 = time.perf_counter()
            SimService(max_batch=n).run(reqs)   # reloads from disk
            disk_wall = time.perf_counter() - t0
            disk = compilation_cache_stats()
            checks["disk_cache_hits"] = disk["hits"] > 0 and misses > 0
    finally:
        disable_persistent_compilation_cache()
        if prior_env is None:
            os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
        else:
            os.environ["JAX_COMPILATION_CACHE_DIR"] = prior_env
        if prior_cache_dir:                      # restore run.py's wiring
            enable_persistent_compilation_cache()

    print(f"  sequential cold x{n}: {seq_wall:.1f}s "
          f"(mean {np.mean(seq_lat):.2f}s/req)", flush=True)
    print(f"  cold service batch:  {cold_wall:.1f}s -> {ratio:.1f}x "
          f"({svc.metrics.sim_compiles} compile)", flush=True)
    print(f"  warm service batch:  {warm_wall:.2f}s "
          f"({warm.metrics.sim_compiles} compiles)", flush=True)
    print(f"  disk-cache restart:  {disk_wall:.1f}s "
          f"(hits {disk['hits']}, misses {disk['misses']})", flush=True)

    return {
        "name": "service_latency_4x4",
        "ok": all(checks.values()),
        "wall_s": round(seq_wall + cold_wall + warm_wall, 2),
        "n_requests": n,
        "sequential": {"total_s": round(seq_wall, 3),
                       "per_request_s": [round(x, 3) for x in seq_lat]},
        "cold_service": {"total_s": round(cold_wall, 3),
                         "batches": svc.metrics.batches,
                         "sim_compiles": svc.metrics.sim_compiles,
                         "aux_compiles": svc.metrics.aux_compiles,
                         "throughput_vs_sequential": round(ratio, 2)},
        "warm_service": {"total_s": round(warm_wall, 3),
                         "sim_compiles": warm.metrics.sim_compiles,
                         "aux_compiles": warm.metrics.aux_compiles},
        "disk_cache_restart": {"total_s": round(disk_wall, 3),
                               "hits": disk["hits"],
                               "misses": disk["misses"],
                               "entries": disk["entries"]},
        "checks": checks,
    }


def run() -> List[Dict]:
    return [bench_service_amortization()]


if __name__ == "__main__":
    import json
    print(json.dumps(run(), indent=1, default=str))
