"""Closed loop of back-to-back drain jobs through ``repro.netsim_jax.JaxMeshSim``.

Each job is a fresh seeded injection program (``pattern``,
``entries_per_tile``, ``rate``), built on the host, loaded into a new
``JaxMeshSim``, run until the mesh drains and read back as the
``Telemetry`` record ``repro.mesh.Simulator.telemetry()`` returns.  The
mix names the step (``impl``, ``cycles_per_call``) and the drain fence's
cadence (``check_every``).  The check replays a seeded sample of
``check_jobs`` of the window's jobs on the reference.

The drain is ``JaxMeshSim.run_until_drained`` with its completion trace
cut to the drain length on the host: the program cuts it on the device,
which builds one slice executable per distinct drain length.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

from bench import checks, patterns
from bench.common import Spans, Tracer, derive
from bench.entry import MESH_KEYS, Entry, mesh_config


class Drain(Entry):
    noun = "jobs"

    def __init__(self, *a):
        super().__init__(*a)
        self.mesh = mesh_config(self.cfg)
        self.jobs: List[dict] = []

    def case(self, i: int, stream: int = 0) -> dict:
        m = self.mix
        return dict({k: self.cfg[k] for k in MESH_KEYS},
                    pattern=m["pattern"], length=m["entries_per_tile"],
                    rate=m["rate"], seed=derive(self.seed, stream, i),
                    max_cycles=m["max_cycles"])

    def _job(self, case: dict, spans: Spans):
        from repro.mesh.telemetry import Telemetry
        from repro.netsim_jax import JaxMeshSim
        from repro.netsim_jax.sim import run_until_drained_traced
        m = self.mix
        with spans("build"):
            entries = patterns.make_traffic(
                case["pattern"], case["nx"], case["ny"], case["length"],
                rate=case["rate"], seed=case["seed"],
                mem_words=case["mem_words"])
        with spans("attach"):
            sim = JaxMeshSim(self.mesh.to_sim(), check_every=m["check_every"],
                             impl=m["impl"],
                             cycles_per_call=m["cycles_per_call"])
            sim.load_program(entries)
        with spans("drain"):
            sim.state, steps, trace = run_until_drained_traced(
                sim.cfg, sim.program, sim.state, case["max_cycles"],
                sim.check_every, sim.impl, sim.cycles_per_call)
            cycle = int(steps)
            sim.completed_per_cycle.extend(
                np.asarray(trace)[:cycle].tolist())
            if cycle >= case["max_cycles"]:
                raise RuntimeError(f"network did not drain in "
                                   f"{case['max_cycles']} cycles")
        with spans("telemetry"):
            tel = Telemetry.of(sim)
        out = {f: getattr(tel, f) for f in checks.TELEMETRY_FIELDS}
        out["cycles"] = np.asarray(tel.cycles)
        return cycle, out

    def setup(self) -> None:
        self._job(self.case(0, stream=1), Spans())

    def window(self, seconds: float, spans: Spans, tracer: Tracer) -> None:
        t0 = tracer.clock()
        tracer.start()
        i = 0
        while tracer.clock() - t0 < seconds:
            job = {"case": self.case(i), "out": None}
            self.attempted += 1
            try:
                job["out"] = self._job(job["case"], spans)
            except Exception as e:  # a job that never answers is counted
                self.failed += 1
                self.errors.append(f"job {i}: {e!r}")
            job["end"] = tracer.clock()
            self.jobs.append(job)
            tracer.poll()
            i += 1
        tracer.stop()
        self.wall = self.jobs[-1]["end"] - t0

    def end_to_end(self) -> Dict[str, float]:
        cycles = sum(int(j["out"][0]) for j in self.jobs if j["out"])
        return {"cycles_per_s": cycles / self.wall}

    def counters(self, tracer: Tracer) -> Dict[str, float]:
        done = [int(j["out"][0]) for j in self.jobs if j["out"]]
        traced = [j for j in self.jobs if j["out"] and tracer.traced(j["end"])]
        return {"jobs": len(self.jobs),
                "drain_cycles_min": min(done, default=0),
                "drain_cycles_max": max(done, default=0),
                "traced_jobs": len(traced),
                "traced_cycles": sum(int(j["out"][0]) for j in traced)}

    def _pick(self, n: int) -> list:
        rng = np.random.default_rng(derive(self.seed, 2))
        k = min(self.mix["check_jobs"], n)
        return sorted(rng.choice(n, k, replace=False).tolist())

    def sample(self):
        done = [j for j in self.jobs if j["out"]]
        chosen = [done[i] for i in self._pick(len(done))]
        return [j["case"] for j in chosen], [j["out"] for j in chosen]

    def cases(self, n: int) -> list:
        return [self.case(i) for i in self._pick(n)]

    def numbers(self, got, want) -> Dict[str, float]:
        return checks.drain_numbers(list(zip(got, want)))

    def control_outputs(self, raws) -> list:
        return list(raws)


ENTRY = Drain
