"""The design-space-exploration runner: spec in, Pareto frontiers out.

One :func:`run_sweep` submission absorbs an arbitrarily large point
count by composing the repo's existing scaling machinery:

1. **Cache probe** — every expanded point is looked up in the
   :class:`~repro.dse.cache.ResultCache` first; re-runs simulate (and
   compile) nothing for known points.
2. **Bucketing** — cache misses group by
   (:class:`~repro.netsim_jax.measure.SweepKey`, program length): one
   static shape, ONE compilation per bucket, regardless of how many
   depth x credits x pattern x load points it holds.
3. **Batching** — each bucket stacks its injection programs and rides
   the vmapped :func:`~repro.netsim_jax.measure.batch_stats_fn` with
   per-point dynamic FIFO depths/credit allowances, chunked through
   ``lax.map`` so peak memory is one chunk of simulator states, not the
   whole bucket.
4. **Sharding** — with ``devices=N`` the chunked program is wrapped in
   ``jax.shard_map`` over a 1-D device mesh and each device simulates
   its slice of the bucket.  Requesting more
   devices than the host has degrades gracefully: one warning, then the
   single-device chunked-vmap path (so a spec written for a fleet still
   runs on a laptop).

Frontier extraction (:func:`frontier_artifact`) is a pure post-pass over
the cached telemetry: per topology, each (fifo_depth, credits)
configuration's load–latency curve is reduced to (saturation rate,
saturation throughput), priced with the
:class:`~repro.dse.cost.CostModel`, and the undominated
area-vs-throughput set is emitted as JSON + an ASCII figure.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import time
import warnings
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import tracing
from repro.compat import device_mesh_1d
from repro.mesh.traffic import make_traffic
from repro.netsim_jax.measure import (PhaseStats, SweepKey, batch_stats_fn,
                                      saturation_point)
from repro.netsim_jax.sim import I32, Program, load_program

from .cache import ResultCache, config_hash
from .cost import CostModel
from .pareto import ascii_frontier, frontier_is_monotone, pareto_front
from .spec import SweepPoint, SweepSpec, workload_entries

__all__ = ["SweepResult", "run_sweep", "frontier_artifact",
           "frontier_ascii", "write_frontier"]

# the PhaseStats scalars persisted per point (hist stays in-memory only:
# 512 bins x 500+ points of JSON would dwarf the numbers anyone reads)
STAT_FIELDS = ("offered", "accepted", "delivered", "lat_mean", "lat_p50",
               "lat_p95", "lat_p99", "lat_max", "peak_link_util", "hops")

# (SweepKey, ndev, chunk, padded batch, program length) shapes executed
# by this process — distinguishes a genuinely fresh XLA compilation from
# a jit-cache hit, so SweepResult.compiles reports honest numbers
_EXECUTED_SHAPES: set = set()


@dataclasses.dataclass
class SweepResult:
    """What one submission did: the per-point records (spec order) plus
    the service accounting the acceptance gates read."""
    spec: SweepSpec
    records: List[Dict]
    n_points: int
    simulated: int
    cache_hits: int
    infeasible: List[str]
    buckets: int
    compiles: int
    devices: int
    wall_s: float
    padded_rows: int    # rows added to fill whole per-device chunks

    def by_point(self) -> Dict[SweepPoint, Dict]:
        return {_point_from_record(r): r for r in self.records}


def _resolve_devices(requested: Optional[int]) -> int:
    """The device-axis width actually used.  ``None`` means single-device
    chunked vmap; asking for more devices than the host has falls back
    to the same path with one warning instead of a shard_map crash."""
    if requested is None or requested <= 1:
        return 1
    avail = jax.device_count()
    if requested > avail:
        warnings.warn(
            f"sweep requested a {requested}-device axis but only {avail} "
            f"device(s) are visible; falling back to single-device "
            f"chunked vmap", stacklevel=3)
        return 1
    return int(requested)


@functools.lru_cache(maxsize=None)
def _bucket_jit(key: SweepKey, ndev: int, chunk: int):
    """The jitted bucket program: chunked (``lax.map`` over ``chunk``-row
    vmapped slices) and, for ``ndev > 1``, sharded over a 1-D device
    mesh.  Cached per (key, fan-out) like every other sweep program —
    :func:`repro.netsim_jax.measure.clear_sweep_cache` clears it too."""
    base = batch_stats_fn(key, digest=True)

    def chunked(progs: Program, depths: jax.Array,
                credits: jax.Array) -> Tuple[PhaseStats, jax.Array]:
        def split(x):
            return x.reshape((-1, chunk) + x.shape[1:])

        def join(x):
            return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

        args = jax.tree_util.tree_map(split, (progs, depths, credits))
        out = jax.lax.map(lambda a: base(*a), args)
        return jax.tree_util.tree_map(join, out)

    if ndev == 1:
        return jax.jit(chunked)
    mesh = device_mesh_1d(ndev, "dse")
    spec = jax.sharding.PartitionSpec("dse")
    # check_vma=False: each device simulates its own rows with no
    # collective, and every output is sharded over "dse".  The check would
    # only reject the scan carry, whose fresh state (init_state's zeros)
    # is typed device-invariant while the stepped state varies over "dse".
    return jax.jit(jax.shard_map(chunked, mesh=mesh,
                                 in_specs=(spec, spec, spec), out_specs=spec,
                                 check_vma=False))


def _pad_rows(n: int, ndev: int, chunk: int) -> Tuple[int, int]:
    """(padded batch, effective chunk): the batch must split evenly into
    ``ndev`` device rows of whole ``chunk``-row ``lax.map`` slices."""
    per_dev = math.ceil(n / ndev)
    eff = max(1, min(chunk, per_dev))
    per_dev = math.ceil(per_dev / eff) * eff
    return per_dev * ndev, eff


def _bucket_programs(spec: SweepSpec, pts: Sequence[SweepPoint],
                     length: int) -> Program:
    progs = []
    wl_cache: Dict[str, Dict[str, np.ndarray]] = {}
    for p in pts:
        if p.is_workload:
            ent = wl_cache.get(p.family)
            if ent is None:
                ent = wl_cache[p.family] = workload_entries(
                    p.family, p.nx, p.ny, p.seed)
            progs.append(load_program(ent))
        else:
            progs.append(load_program(make_traffic(
                p.traffic, p.nx, p.ny, length, rate=p.load, seed=p.seed,
                topology=p.topology, mem_words=p.mem_words)))
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *progs)


def _run_bucket(spec: SweepSpec, key: SweepKey, length: int,
                pts: Sequence[SweepPoint], ndev: int,
                chunk: int) -> Tuple[List[Dict], List[List[int]], int, int]:
    """Simulate one bucket; returns (per-point stat dicts, per-point
    memory digests, new compiles, padded rows)."""
    n = len(pts)
    padded, eff = _pad_rows(n, ndev, chunk)
    with tracing.span("dse.bucket.build", points=n):
        progs = _bucket_programs(spec, pts, length)
        depths = np.fromiter((p.fifo_depth for p in pts), np.int32, n)
        credits = np.fromiter((p.credits for p in pts), np.int32, n)
        if padded > n:  # repeat the first point; its rows are dropped below
            pad = padded - n

            def grow(x):
                return jnp.concatenate(
                    [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])
            progs = jax.tree_util.tree_map(grow, progs)
            depths = np.concatenate([depths, np.repeat(depths[:1], pad)])
            credits = np.concatenate([credits, np.repeat(credits[:1], pad)])
        depths, credits = jnp.asarray(depths, I32), jnp.asarray(credits, I32)
    shape_id = (key, ndev, eff, padded, length)
    fresh = shape_id not in _EXECUTED_SHAPES
    _EXECUTED_SHAPES.add(shape_id)
    stats, digests = _bucket_jit(key, ndev, eff)(progs, depths, credits)
    with tracing.span("dse.bucket.wait"):
        jax.block_until_ready((stats, digests))
    with tracing.span("dse.bucket.pull"):
        host = {f: np.asarray(getattr(stats, f))[:n] for f in STAT_FIELDS}
        digests = np.asarray(digests)[:n].tolist()
    return ([{f: float(host[f][i]) for f in STAT_FIELDS} for i in range(n)],
            digests, int(fresh), padded - n)


def _point_record(point: SweepPoint, stats: Dict[str, float],
                  digest: List[int]) -> Dict:
    """The persisted record of one point; ``mem_digest`` sums up its
    final tile memory (:func:`repro.netsim_jax.sim.memory_digest`)."""
    return {
        "point": {"nx": point.nx, "ny": point.ny,
                  "topology": point.topology.spec,
                  "fifo_depth": point.fifo_depth, "credits": point.credits,
                  "traffic": point.traffic, "load": point.load,
                  "seed": point.seed, "ep_fifo": point.ep_fifo,
                  "mem_words": point.mem_words,
                  "resp_latency": point.resp_latency},
        "stats": {k: round(v, 6) for k, v in stats.items()},
        "mem_digest": digest,
    }


def _point_from_record(record: Dict) -> SweepPoint:
    p = record["point"]
    from repro.mesh.topology import Topology
    return SweepPoint(nx=p["nx"], ny=p["ny"],
                      topology=Topology.parse(p["topology"]),
                      fifo_depth=p["fifo_depth"], credits=p["credits"],
                      traffic=p["traffic"], load=p["load"],
                      seed=p.get("seed", 0), ep_fifo=p.get("ep_fifo", 4),
                      mem_words=p.get("mem_words", 64),
                      resp_latency=p.get("resp_latency", 1))


def run_sweep(spec: SweepSpec, *, cache_dir=None,
              devices: Optional[int] = None, chunk: int = 16,
              progress: Optional[Callable[[str], None]] = None
              ) -> SweepResult:
    """Run (the uncached remainder of) a sweep spec; see the module
    docstring for the pipeline.  ``cache_dir`` may be a directory path
    or a :class:`ResultCache` (None disables caching); ``devices``
    requests the shard_map fan-out width; ``chunk`` bounds how many
    simulator states are live per device at once.  A process that armed
    the persistent compile cache
    (:func:`repro.compat.enable_persistent_compilation_cache`) reuses
    its bucket executables across restarts."""
    with tracing.span("dse.sweep", sweep=spec.name):
        t0 = time.perf_counter()
        log = progress if progress is not None else (lambda msg: None)
        cache = cache_dir if isinstance(cache_dir, ResultCache) \
            else ResultCache(cache_dir)
        points = spec.points()
        infeasible = [f"skipped {t.spec} fifo_depth={d}: {why}"
                      for t, d, why in spec.infeasible()]
        for line in infeasible:
            log(line)
        log(spec.describe())

        done: Dict[SweepPoint, Dict] = {}
        misses: List[SweepPoint] = []
        for p in points:
            rec = cache.get(spec.point_key(p))
            if rec is not None:
                done[p] = rec
            else:
                misses.append(p)
        ndev = _resolve_devices(devices)

        buckets: Dict[Tuple[SweepKey, int], List[SweepPoint]] = {}
        for p in misses:
            length = workload_entries(p.family, p.nx, p.ny, p.seed)[
                "op"].shape[-1] if p.is_workload else spec.traffic_length()
            buckets.setdefault((spec.sweep_key(p.topology), int(length)),
                               []).append(p)

        compiles = padded = 0
        for (key, length), pts in buckets.items():
            log(f"bucket {key.cfg.topology.spec} L={length}: {len(pts)} "
                f"points ({ndev} device(s), chunk {chunk})")
            stats, digests, fresh, pad = _run_bucket(spec, key, length, pts,
                                                     ndev, chunk)
            compiles += fresh
            padded += pad
            for p, s, d in zip(pts, stats, digests):
                rec = _point_record(p, s, d)
                cache.put(spec.point_key(p), rec)
                done[p] = rec

        return SweepResult(
            spec=spec, records=[done[p] for p in points], n_points=len(points),
            simulated=len(misses), cache_hits=len(points) - len(misses),
            infeasible=infeasible, buckets=len(buckets), compiles=compiles,
            devices=ndev, wall_s=round(time.perf_counter() - t0, 2),
            padded_rows=padded)


# -- frontier extraction -----------------------------------------------

def _config_points(spec: SweepSpec, records: Sequence[Dict], topology: str,
                   pattern: str, cost: CostModel) -> List[Dict]:
    """Reduce one topology's traffic records to per-(depth, credits)
    configuration points: saturation rate/throughput from the load
    curve, area/energy from the cost model."""
    groups: Dict[Tuple[int, int], List[Dict]] = {}
    for r in records:
        p = r["point"]
        if p["topology"] == topology and p["traffic"] == pattern:
            groups.setdefault((p["fifo_depth"], p["credits"]),
                              []).append(r)
    ntiles = spec.nx * spec.ny
    out = []
    for (depth, cred), recs in sorted(groups.items()):
        recs = sorted(recs, key=lambda r: r["point"]["load"])
        loads = [r["point"]["load"] for r in recs]
        lat = [r["stats"]["lat_mean"] for r in recs]
        acc = [r["stats"]["accepted"] for r in recs]
        sat = saturation_point(np.asarray(lat))
        peak = int(np.argmax(acc))
        packets = acc[peak] * ntiles * spec.measure
        cfg = dataclasses.replace(
            _point_from_record(recs[0]), fifo_depth=depth,
            credits=cred).mesh_config()
        out.append({
            "fifo_depth": depth, "credits": cred,
            "area_mm2": round(cost.buffer_area_mm2(cfg), 4),
            "throughput": round(float(max(acc)), 4),
            "saturation_rate": None if sat is None else float(loads[sat]),
            "zero_load_latency": round(float(lat[0]), 2),
            "energy_pj_per_packet": round(cost.energy_per_packet_pj(
                recs[peak]["stats"]["hops"], packets), 2),
            "loads": [round(float(x), 3) for x in loads],
        })
    return out


def frontier_artifact(result: SweepResult, cost: Optional[CostModel] = None,
                      pattern: Optional[str] = None) -> Dict:
    """The persisted JSON artifact: per-topology configuration points +
    Pareto frontier over (buffer area, saturation throughput).

    ``pattern`` picks the traffic pattern the frontier is computed from
    (default: ``"uniform"`` when swept, else the spec's first pattern —
    the standard saturation methodology)."""
    spec = result.spec
    cost = cost if cost is not None else CostModel()
    if pattern is None:
        pattern = "uniform" if "uniform" in spec.patterns else (
            spec.patterns[0] if spec.patterns else None)
    if pattern is None:
        raise ValueError(
            "frontier extraction needs a synthetic traffic pattern; this "
            "sweep spec only ran workload families")
    frontiers = {}
    for topo in spec.topologies:
        pts = _config_points(spec, result.records, topo.spec, pattern, cost)
        front = pareto_front(pts)
        frontiers[topo.spec] = {
            "points": pts,
            "frontier": front,
            "monotone": frontier_is_monotone(front),
        }
    return {
        "name": f"dse_frontier_{spec.name}",
        "mesh": f"{spec.nx}x{spec.ny}",
        "pattern": pattern,
        "config_hash": config_hash(),
        "cost_model": cost.to_json(),
        "spec": spec.describe(),
        "n_points": result.n_points,
        "frontiers": frontiers,
    }


def frontier_ascii(artifact: Dict) -> str:
    """Terminal rendering of every topology's frontier figure."""
    blocks = []
    for topo, f in artifact["frontiers"].items():
        blocks.append(f"  -- {topo} ({artifact['pattern']}, "
                      f"{artifact['mesh']}) --")
        blocks.append(ascii_frontier(f["points"], f["frontier"]))
    return "\n".join(blocks)


def write_frontier(path, artifact: Dict) -> Path:
    import json
    path = Path(path)
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(json.dumps(artifact, indent=1, default=str))
    return path
