"""Benchmark aggregator: one suite per paper table/claim + system harnesses.

  PYTHONPATH=src python -m benchmarks.run            # everything
  PYTHONPATH=src python -m benchmarks.run --suite netsim

Writes a JSON summary to experiments/bench_results.json; the netsim_jax
load–latency saturation curves are additionally written to
experiments/load_latency.json, the cross-topology saturation records
to experiments/topology_saturation.json, the design-space Pareto
frontiers (buffer area vs. saturation throughput) to
experiments/dse_frontier.json, and the simulation-service amortization
record to experiments/service_latency.json (uploaded as CI artifacts).

Every run arms JAX's persistent on-disk compilation cache in
$JAX_COMPILATION_CACHE_DIR, or experiments/xla_cache/ when that is not
set (shared with the sim service and repro.dse), and the summary reports
its hit/miss/entry counts.

Every run also APPENDS a trajectory entry to experiments/BENCH_netsim.json
— per-benchmark wall seconds with compile time and run time recorded
separately (the jax suites AOT-compile via ``jitted.lower(...).compile()``
and time the two phases independently) — so speedups and compile-time
regressions are tracked PR-over-PR.

Exit status: nonzero if any benchmark reports ``ok: false`` OR any suite
crashes outright — a crashed suite still gets a failure record and the
JSON artifacts are still written, but the process must not report
success.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Dict, List

SUITES = ("netsim", "netsim_jax", "topology", "workloads", "collectives",
          "kernels", "train", "dse", "service")

# trajectory entries keep only the timing/health fields, not full payloads
_TRAJECTORY_KEYS = ("wall_s", "compile_s", "run_s", "wall_s_incl_compile",
                    "speedup_vs_baseline", "ok")

# step-throughput regression gate: post-compile cycles/s per mesh must
# stay above this fraction of the frozen bench_baseline.json snapshot
# (generous enough for shared-runner noise, tight enough to catch a
# datapath regression, which shows up as an integer-factor slowdown)
STEP_THROUGHPUT_FLOOR = 0.5


def gate_step_throughput(results: Dict[str, List[Dict]],
                         floor: float = STEP_THROUGHPUT_FLOOR) -> bool:
    """Compare this run's step-throughput microbench against the frozen
    ``experiments/bench_baseline.json`` snapshot, mesh by mesh; False (and
    a [FAIL] line) when any mesh's post-compile cycles/s fell below
    ``floor`` x baseline.  Vacuously True when either side lacks the
    record (fresh checkout, suite not selected, or a crashed suite)."""
    from benchmarks.bench_netsim_jax import load_baseline
    base = load_baseline().get("step_throughput_microbench", {})
    recs = [r for r in results.get("netsim_jax", [])
            if r.get("name") == "step_throughput_microbench"]
    if not base.get("meshes") or not recs:
        return True
    ok = True
    for mesh, brec in base["meshes"].items():
        want = brec.get("jax_cycles_per_s")
        got = recs[0].get("meshes", {}).get(mesh, {}).get("jax_cycles_per_s")
        if not want or got is None:
            continue
        if float(got) < floor * float(want):
            print(f"[FAIL] step-throughput regression on {mesh}: "
                  f"{float(got):.1f} cycles/s < {floor} x baseline "
                  f"{float(want):.1f}", flush=True)
            ok = False
    if ok:
        print(f"[OK ] step-throughput gate: every mesh >= {floor} x "
              f"baseline cycles/s", flush=True)
    return ok


def gate_topology_saturation(results: Dict[str, List[Dict]],
                             floor: float = STEP_THROUGHPUT_FLOOR) -> bool:
    """Gate the cross-topology saturation sweep's MESH row against the
    frozen baseline: the plain-mesh saturation rate must not fall below
    ``floor`` x the snapshot's (the other topologies have no pre-topology
    baseline to compare to, and their cross-checks live in the suite's
    own ``checks``).  Vacuously True when either side lacks the record —
    in particular when ``bench_baseline.json`` predates topology
    support."""
    from benchmarks.bench_netsim_jax import load_baseline
    base = load_baseline().get("topology_saturation_16x16", {})
    recs = [r for r in results.get("topology", [])
            if r.get("name") == "topology_saturation_16x16"]
    want = base.get("topologies", {}).get("mesh", {}).get("saturation_rate")
    got = (recs[0].get("topologies", {}).get("mesh", {})
           .get("saturation_rate")) if recs else None
    if want is None or got is None:
        return True
    if float(got) < floor * float(want):
        print(f"[FAIL] mesh saturation regression: {float(got):.3f} < "
              f"{floor} x baseline {float(want):.3f}", flush=True)
        return False
    print(f"[OK ] topology gate: mesh saturation {float(got):.3f} >= "
          f"{floor} x baseline {float(want):.3f}", flush=True)
    return True


def gate_dse_frontier(results: Dict[str, List[Dict]]) -> bool:
    """Gate the design-space sweep's emitted Pareto frontier: the MESH
    frontier must be non-empty and monotone (strictly more saturation
    throughput for every extra mm² of buffer area) — an empty or
    non-monotone frontier means the sweep, the cost model, or the
    extractor regressed.  Vacuously True when the dse suite did not run
    or crashed (the crash is already a failure on its own)."""
    recs = [r for r in results.get("dse", [])
            if r.get("name") == "dse_frontier_16x16" and "artifact" in r]
    if not recs:
        return True
    mesh = recs[0]["artifact"]["frontiers"].get("mesh", {})
    front = mesh.get("frontier") or []
    if front and mesh.get("monotone"):
        print(f"[OK ] dse gate: mesh frontier has {len(front)} "
              f"configuration(s), monotone", flush=True)
        return True
    print(f"[FAIL] dse frontier gate: mesh frontier "
          f"{'empty' if not front else 'not monotone'}", flush=True)
    return False


def trajectory_entry(results: Dict[str, List[Dict]], wall: float) -> Dict:
    """One PR-over-PR record: per-benchmark timing split + suite walls."""
    from repro.compat import compilation_cache_stats
    return {
        "when": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "total_wall_s": round(wall, 1),
        "compile_cache": compilation_cache_stats(),
        "suites": {
            name: {
                "wall_s": round(sum(float(r.get("wall_s", 0) or 0)
                                    for r in recs), 2),
                "compile_s": round(sum(float(r.get("compile_s", 0) or 0)
                                       for r in recs), 2),
                "ok": all(bool(r.get("ok")) for r in recs),
            } for name, recs in results.items()},
        "benchmarks": {
            r["name"]: {k: r[k] for k in _TRAJECTORY_KEYS if k in r}
            for recs in results.values() for r in recs if "name" in r},
    }


def append_trajectory(out_dir: Path, entry: Dict) -> Path:
    path = out_dir / "BENCH_netsim.json"
    try:
        history = json.loads(path.read_text())
        if not isinstance(history, list):
            history = []
    except (OSError, json.JSONDecodeError):
        history = []
    history.append(entry)
    with open(path, "w") as f:
        json.dump(history, f, indent=1, default=str)
    return path


def run_suite(name: str) -> List[Dict]:
    """Import and execute one benchmark suite (separated out so tests can
    stub it when exercising the aggregator's crash handling)."""
    mod = __import__(f"benchmarks.bench_{name}", fromlist=["run"])
    return mod.run()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--suite", choices=SUITES, default=None)
    ap.add_argument("--out", type=Path,
                    default=Path(__file__).resolve().parents[1] / "experiments",
                    help="directory for the JSON artifacts")
    args = ap.parse_args(argv)
    picked = [args.suite] if args.suite else list(SUITES)

    # the collectives/train suites exercise a 2x4 device mesh; must be set
    # before the first jax backend use
    from repro.compat import set_host_device_count
    set_host_device_count(8)

    # persistent on-disk XLA compilation cache: repeat bench runs
    # deserialize executables instead of re-compiling
    from repro.compat import enable_persistent_compilation_cache
    enable_persistent_compilation_cache()

    results: Dict[str, List[Dict]] = {}
    crashed: List[str] = []
    t0 = time.perf_counter()
    for name in picked:
        print(f"\n=== suite: {name} ===", flush=True)
        try:
            results[name] = run_suite(name)
        except Exception as e:  # still write the JSON for the other suites
            print(f"[FAIL] suite {name} crashed: {e!r}", flush=True)
            results[name] = [{"name": f"{name} (crashed)", "ok": False,
                              "error": repr(e)}]
            crashed.append(name)
    wall = time.perf_counter() - t0

    flat = [r for rs in results.values() for r in rs]
    n_ok = sum(1 for r in flat if r.get("ok"))
    print(f"\n{n_ok}/{len(flat)} benchmarks OK in {wall:.1f}s")
    out = args.out
    out.mkdir(parents=True, exist_ok=True)
    with open(out / "bench_results.json", "w") as f:
        json.dump(results, f, indent=1, default=str)
    print(f"wrote {out / 'bench_results.json'}")
    # standalone artifact: the load–latency saturation curves
    sweeps = [r for r in results.get("netsim_jax", [])
              if r.get("name") == "load_latency_curves_8x8"]
    if sweeps:
        with open(out / "load_latency.json", "w") as f:
            json.dump(sweeps[0], f, indent=1, default=str)
        print(f"wrote {out / 'load_latency.json'}")
    # standalone artifact: the cross-topology saturation records (the
    # acceptance artifact for the torus-vs-mesh wraparound claim)
    topo = [r for r in results.get("topology", [])
            if r.get("name") == "topology_saturation_16x16"]
    if topo:
        with open(out / "topology_saturation.json", "w") as f:
            json.dump(topo[0], f, indent=1, default=str)
        print(f"wrote {out / 'topology_saturation.json'}")
    # standalone artifact: the parity-checked workload reports + fitted
    # congestion model from the workloads suite
    wl = [r for r in results.get("workloads", [])
          if "report" in r or "congestion_model" in r]
    if wl:
        with open(out / "workload_reports.json", "w") as f:
            json.dump(wl, f, indent=1, default=str)
        print(f"wrote {out / 'workload_reports.json'}")
    # standalone artifact: the design-space Pareto frontiers (buffer
    # area vs. saturation throughput per topology) from the dse suite
    dse = [r for r in results.get("dse", []) if "artifact" in r]
    if dse:
        with open(out / "dse_frontier.json", "w") as f:
            json.dump(dse[0]["artifact"], f, indent=1, default=str)
        print(f"wrote {out / 'dse_frontier.json'}")
    # standalone artifact: the simulation-service amortization record
    # (sequential vs batched vs warm vs disk-cache-restart latencies)
    svc = [r for r in results.get("service", [])
           if r.get("name") == "service_latency_4x4"]
    if svc:
        with open(out / "service_latency.json", "w") as f:
            json.dump(svc[0], f, indent=1, default=str)
        print(f"wrote {out / 'service_latency.json'}")
    # persistent compile-cache accounting for the whole run (also stored
    # per-entry in the trajectory): a warm CI cache shows hits > 0 here
    from repro.compat import compilation_cache_stats
    cc = compilation_cache_stats()
    print(f"compile cache: {cc['hits']} hits, {cc['misses']} misses, "
          f"{cc['entries']} entries ({cc['dir']})")
    # PR-over-PR timing trajectory (appended, never overwritten)
    print(f"appended {append_trajectory(out, trajectory_entry(results, wall))}")
    gate_ok = gate_step_throughput(results)
    gate_ok &= gate_topology_saturation(results)
    gate_ok &= gate_dse_frontier(results)
    if crashed:
        print(f"FAILED: suite(s) crashed: {', '.join(crashed)}")
        return 1
    if n_ok != len(flat) or not gate_ok:
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
