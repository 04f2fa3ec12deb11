#!/usr/bin/env python3
"""Run the mesh simulator's device path on a TPU and check what it gives.

    python chip_smoke.py             # one chip: every phase but the last
    python chip_smoke.py --chips 4   # only the sweep sharded over 4 chips

Phases, in one process; the first failure ends the run with a non-zero
exit and no result line:

  device   a TPU is the default device (there is no CPU fallback)
  cache    the persistent compile cache is armed
           ($JAX_COMPILATION_CACHE_DIR, else experiments/xla_cache)
  fused    uniform traffic on the 16x32 (512-tile, Celerity-scale) mesh
           drained through Simulator(backend="jax", impl="fused"):
           telemetry and drain cycle bit-identical to the numpy oracle
  pallas   a drain through the compiled Pallas router kernel
           (impl="pallas") on 16x32, or on 16x16 where the kernel's
           VMEM check refuses 16x32: whole state bit-identical to fused
  service  8 concurrent SimRequests on 16x16 through SimService: every
           PhaseStats field equal to a direct phased_stats run
  sweep    a 12-point 16x16 SweepSpec through run_sweep on one device
  sharded  (--chips 4 only) a 16-point 16x16 sweep on 4 devices: records
           identical to the one-device sweep

The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.compat import (compilation_cache_stats,  # noqa: E402
                          enable_persistent_compilation_cache)
from repro.dse import SweepSpec, run_sweep  # noqa: E402
from repro.kernels.backend import resolve_interpret  # noqa: E402
from repro.kernels.router_step import (VMEM_LIMIT_BYTES,  # noqa: E402
                                       vmem_bytes)
from repro.mesh import MeshConfig, Simulator, make_traffic  # noqa: E402
from repro.netsim_jax.measure import phased_stats  # noqa: E402
from repro.netsim_jax.sim import (init_state, load_program,  # noqa: E402
                                  run_until_drained_traced)
from repro.sim_service import SimRequest, SimService  # noqa: E402

CELERITY = (16, 32)      # (nx, ny) of benchmarks' bench_bisection_16x32
SMALL = (16, 16)         # service and sweep mesh; the kernel's fallback
KERNEL_CYCLES = 8        # kernel cycles per launch = its drain-fence cadence
MAX_CYCLES = 20_000


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    sys.exit(f"chip_smoke: FAILED: {msg}")


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


def phase_device(want_chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    log(f"[device] {dev}")
    check(dev["platform"] == "tpu",
          f"the default device is {dev['platform']!r}, not a TPU")
    check(dev["count"] >= want_chips,
          f"{want_chips} chips wanted, {dev['count']} visible")
    return dev


def _program(nx: int, ny: int, length: int, rate: float, seed: int):
    return make_traffic("uniform", nx, ny, length, rate=rate, seed=seed)


def _drain(cfg: MeshConfig, entries, impl: str, check_every: int = 1):
    """Drain ``entries`` through the jax facade: the facade's drain
    program is compiled ahead of time (timed, and its text returned), the
    first drain then finds it in the persistent cache, and a second
    facade on the same program times the run alone.  The drain cycle is
    exact for any ``check_every``; the state may step up to
    ``check_every - 1`` idle cycles past it."""
    def facade():
        sim = Simulator(cfg, backend="jax", impl=impl,
                        check_every=check_every,
                        cycles_per_call=check_every if impl == "pallas" else 1)
        sim.attach({k: v.copy() for k, v in entries.items()})
        return sim

    sim = facade()
    t0 = time.perf_counter()
    compiled = run_until_drained_traced.lower(
        cfg.to_sim(), sim.program, sim.state, MAX_CYCLES, check_every, impl,
        sim.cycles_per_call).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    drain = sim.run_until_drained(MAX_CYCLES)
    jax.block_until_ready(sim.state)
    first_s = time.perf_counter() - t0
    again = facade()
    t0 = time.perf_counter()
    drain2 = again.run_until_drained(MAX_CYCLES)
    jax.block_until_ready(again.state)
    run_s = time.perf_counter() - t0
    check(drain2 == drain, f"{impl}: a repeated drain moved {drain} -> "
                           f"{drain2}")
    return sim, drain, compiled, compile_s, first_s, run_s


def phase_fused(nx: int, ny: int, length: int) -> None:
    cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=64, router_fifo=4)
    entries = _program(nx, ny, length, 0.5, 0)
    sim, drain, _, compile_s, first_s, run_s = _drain(cfg, entries, "fused")
    t0 = time.perf_counter()
    oracle = Simulator(cfg, backend="numpy")
    oracle.attach({k: v.copy() for k, v in entries.items()})
    want = oracle.run_until_drained(MAX_CYCLES)
    oracle_s = time.perf_counter() - t0
    check(drain == want, f"fused drain cycle {drain} != oracle {want}")
    sim.telemetry().assert_bit_identical(oracle.telemetry())
    done = int(sim.telemetry().completed.sum())
    check(done == int((entries["op"] >= 0).sum()),
          f"fused: {done} completions for {int((entries['op'] >= 0).sum())}"
          f" program entries")
    log(f"[fused] {nx}x{ny} uniform, {length} entries/tile: drained at "
        f"cycle {drain}, {done} requests; telemetry bit-identical to the "
        f"oracle; compile {compile_s:.2f} s, first drain {first_s:.3f} s, "
        f"run {run_s:.3f} s (oracle on the host {oracle_s:.1f} s)")


def _assert_kernel_compiled(compiled) -> None:
    check(resolve_interpret(None) is False,
          "Pallas would run in interpret mode on this backend")
    check("tpu_custom_call" in compiled.as_text(),
          "the pallas drain program holds no tpu_custom_call")


def _kernel_mesh(length: int):
    """CELERITY if the compiled kernel's VMEM check admits it, else
    SMALL; with the bytes each needs, for the log."""
    need = {}
    for nx, ny in (CELERITY, SMALL):
        cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=64,
                         router_fifo=4).to_sim()
        prog = load_program(_program(nx, ny, length, 0.5, 1))
        state = jax.eval_shape(lambda: init_state(cfg))
        need[nx, ny] = vmem_bytes(prog, state, KERNEL_CYCLES)
        if need[nx, ny] <= VMEM_LIMIT_BYTES:
            return (nx, ny), need
    fail(f"the kernel fits VMEM at no smoke mesh: {need}")


def phase_pallas(length: int) -> None:
    (nx, ny), need = _kernel_mesh(length)
    sizes = ", ".join(f"{x}x{y} needs at least {b} B"
                      for (x, y), b in need.items())
    cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=64, router_fifo=4)
    entries = _program(nx, ny, length, 0.5, 1)
    ker, drain, compiled, compile_s, first_s, run_s = _drain(
        cfg, entries, "pallas", KERNEL_CYCLES)
    _assert_kernel_compiled(compiled)
    ref, want, _, _, _, ref_run_s = _drain(cfg, entries, "fused",
                                            KERNEL_CYCLES)
    check(drain == want, f"pallas drain cycle {drain} != fused {want}")
    la, ta = jax.tree_util.tree_flatten(ker.state)
    lb, tb = jax.tree_util.tree_flatten(ref.state)
    check(ta == tb, "pallas and fused state trees differ")
    for i, (a, b) in enumerate(zip(la, lb)):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              f"pallas state leaf {i} differs from fused")
    ker.telemetry().assert_bit_identical(ref.telemetry())
    log(f"[pallas] {nx}x{ny} uniform, {length} entries/tile (VMEM: "
        f"{sizes} of {VMEM_LIMIT_BYTES}): compiled kernel "
        f"(tpu_custom_call, not interpreted), drained at cycle {drain}; "
        f"state bit-identical to fused; compile {compile_s:.2f} s, first "
        f"drain {first_s:.3f} s, run {run_s:.3f} s (fused run "
        f"{ref_run_s:.3f} s)")


def _direct(req: SimRequest):
    """The request's exact program, one-shot phased_stats, no service."""
    cfg = req.cfg.to_sim()
    length = int(np.ceil(req.load * req.horizon)) + 1
    prog = load_program(make_traffic(req.pattern, cfg.nx, cfg.ny, length,
                                     rate=req.load, seed=req.seed))
    return phased_stats(cfg, prog, init_state(cfg, req.fifo_depth,
                                              req.max_credits),
                        req.warmup, req.measure, req.drain)


def phase_service(nx: int, ny: int) -> None:
    cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=64, router_fifo=4)
    reqs = [SimRequest(cfg=cfg, load=load, seed=seed, fifo_depth=depth)
            for load in (0.1, 0.3) for depth in (2, 4) for seed in (0, 1)]
    svc = SimService(max_batch=8)
    t0 = time.perf_counter()
    tickets = [svc.submit(r) for r in reqs]
    svc.server.run_until_idle()
    wall = time.perf_counter() - t0
    for r, t in zip(reqs, tickets):
        want = _direct(r)
        for f in want._fields:
            a = np.asarray(getattr(want, f))
            b = np.asarray(getattr(t.response.stats, f))
            check(a.shape == b.shape and (a == b).all(),
                  f"service load={r.load} depth={r.fifo_depth} "
                  f"seed={r.seed}: PhaseStats.{f} {b} != direct {a}")
    m = svc.metrics
    log(f"[service] {nx}x{ny}: {len(reqs)} requests, every PhaseStats "
        f"field equal to direct phased_stats; {m.batches} batches, "
        f"{m.sim_compiles} sim + {m.aux_compiles} aux compiles, "
        f"{wall:.2f} s")


def _sweep_spec(nx: int, ny: int, loads) -> SweepSpec:
    return SweepSpec(nx=nx, ny=ny, fifo_depths=(2, 4), credits=(8, 32),
                     patterns=("uniform",), loads=loads, name="chip_smoke")


def phase_sweep(nx: int, ny: int) -> None:
    spec = _sweep_spec(nx, ny, (0.05, 0.15, 0.3))
    t0 = time.perf_counter()
    res = run_sweep(spec, devices=None)
    wall = time.perf_counter() - t0
    check(res.devices == 1 and len(res.records) == len(spec.points()) == 12,
          f"sweep: {len(res.records)} records on {res.devices} device(s)")
    # one point against a direct phased_stats run of the same program
    p = spec.points()[0]
    cfg = p.mesh_config().to_sim()
    prog = load_program(make_traffic(p.traffic, nx, ny,
                                     spec.traffic_length(), rate=p.load,
                                     seed=p.seed, topology=p.topology))
    want = phased_stats(cfg, prog, init_state(cfg, p.fifo_depth, p.credits),
                        spec.warmup, spec.measure, spec.drain)
    got = res.records[0]["stats"]
    for f, v in got.items():
        check(v == round(float(getattr(want, f)), 6),
              f"sweep point {p.label()}: {f} {v} != direct "
              f"{float(getattr(want, f))}")
    log(f"[sweep] {nx}x{ny}: {res.n_points} points, {res.buckets} bucket, "
        f"{res.compiles} compile, 1 device; first point equal to direct "
        f"phased_stats; {wall:.2f} s")


def phase_sharded(nx: int, ny: int, chips: int) -> None:
    spec = _sweep_spec(nx, ny, (0.05, 0.1, 0.2, 0.3))
    check(len(spec.points()) >= 16, "the sharded sweep needs >= 16 points")
    check(jax.device_count() >= chips,
          f"{chips} devices wanted, {jax.device_count()} visible")
    t0 = time.perf_counter()
    sharded = run_sweep(spec, devices=chips)
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = run_sweep(spec, devices=None)
    t_single = time.perf_counter() - t0
    check(sharded.devices == chips,
          f"sharded sweep ran on {sharded.devices} device(s), not {chips}")
    check(sharded.records == single.records,
          "sharded sweep records differ from the one-device sweep")
    log(f"[sharded] {nx}x{ny}: {sharded.n_points} points on "
        f"{sharded.devices} devices, records identical to one device; "
        f"{t_sharded:.2f} s sharded, {t_single:.2f} s one device "
        f"(compiles included)")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep sharded over four chips")
    args = ap.parse_args(argv)
    dev = phase_device(args.chips)
    cache_dir = enable_persistent_compilation_cache()
    log(f"[cache] {cache_dir}")
    if args.chips == 4:
        phase_sharded(*SMALL, chips=4)
    else:
        phase_fused(*CELERITY, length=128)
        phase_pallas(length=32)
        phase_service(*SMALL)
        phase_sweep(*SMALL)
    cc = compilation_cache_stats()
    log(f"[cache] {cc['hits']} hits, {cc['misses']} misses, "
        f"{cc['entries']} entries in {cc['dir']}")
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
