"""Closed loop of back-to-back design-space sweeps through ``run_sweep``.

Each sweep is the product of the mix's ``patterns``, ``loads``,
``fifo_depths`` and ``max_credits`` on the configuration's mesh, with
its endpoint FIFO, memory and response latency, through the three
phases; its seed is drawn from ``--seed`` and the sweep's index, so no
two sweeps are alike.  It runs with ``devices`` the cell's chips, no
result cache and the mix's ``chunk``: the runner's bucketing, chunked
``lax.map`` and ``shard_map`` fan-out over the chips do the work.  The
check replays a seeded sample of ``check_points`` of the window's points
on the reference, one of them at the heaviest load, and compares each
point's statistics and the tile memory its phases leave
(``bench/tile_memory.py``).
"""
from __future__ import annotations

import math
import os
from typing import Dict, List

from bench import tile_memory
from bench.common import Spans, Tracer, derive
from bench.entry import Entry, mesh_config, pick

# the latency histogram's last bin, where longer round trips are counted
LAST_BIN = 511


class Sweep(Entry):
    noun = "points"

    def __init__(self, *a):
        super().__init__(*a)
        self.mesh = mesh_config(self.cfg)
        # a program whose sweeps cannot take the deployment's sizes
        # fails here, before anything compiles
        self.spec(0, stream=1)
        self.sweeps: List[dict] = []
        self.checked: List[dict] = []   # the cases of the last sample

    def spec(self, i: int, stream: int = 0):
        from repro.dse import SweepSpec
        m, c = self.mix, self.cfg
        return SweepSpec(
            nx=c["nx"], ny=c["ny"], fifo_depths=tuple(m["fifo_depths"]),
            credits=tuple(m["max_credits"]), patterns=tuple(m["patterns"]),
            loads=tuple(m["loads"]), warmup=m["warmup"],
            measure=m["measure"], drain=m["drain"],
            seed=derive(self.seed, stream, i), ep_fifo=c["ep_fifo"],
            mem_words=c["mem_words"], resp_latency=c["resp_latency"])

    def _sweep(self, spec):
        from repro.dse import run_sweep
        return run_sweep(spec, cache_dir=None, devices=self.chips,
                         chunk=self.mix["chunk"])

    def setup(self) -> None:
        """One sweep: every sweep of the window has its one bucket shape."""
        self._sweep(self.spec(0, stream=1))

    def window(self, seconds: float, spans: Spans, tracer: Tracer) -> None:
        t0 = tracer.clock()
        tracer.start()
        i = 0
        while tracer.clock() - t0 < seconds:
            sweep = {"spec": self.spec(i), "result": None}
            n = len(sweep["spec"].points())
            self.attempted += n
            try:
                with spans("sweep"):
                    sweep["result"] = self._sweep(sweep["spec"])
            except Exception as e:  # a sweep that never answers is counted
                self.failed += n
                self.errors.append(f"sweep {i}: {e!r}")
            sweep["end"] = tracer.clock()
            self.sweeps.append(sweep)
            tracer.poll()
            i += 1
        tracer.stop()
        self.wall = self.sweeps[-1]["end"] - t0

    def _done(self) -> List[dict]:
        return [s for s in self.sweeps if s["result"] is not None]

    def end_to_end(self) -> Dict[str, float]:
        cycles = sum(s["result"].n_points * s["spec"].horizon
                     for s in self._done())
        return {"cycles_per_s": cycles / self.wall}

    def counters(self, tracer: Tracer) -> Dict[str, float]:
        done = self._done()
        traced = [s for s in done if tracer.traced(s["end"])]
        lat_max = [r["stats"]["lat_max"] for s in done
                   for r in s["result"].records]
        return {"sweeps": len(done),
                "points": sum(s["result"].n_points for s in done),
                "padded_rows": sum(s["result"].padded_rows for s in done),
                "clipped_points": sum(v >= LAST_BIN for v in lat_max),
                "traced_sweeps": len(traced),
                # every row a chip simulated, padding included
                "traced_point_cycles_per_chip": sum(
                    (s["result"].n_points + s["result"].padded_rows)
                    * s["spec"].horizon / s["result"].devices
                    for s in traced)}

    def _case(self, spec, point) -> dict:
        return self._phase_case(point.fifo_depth, point.credits,
                                point.traffic, point.load,
                                spec.traffic_length(), point.seed)

    def _pick(self, points: list) -> list:
        return pick(self.seed, self.mix["check_points"],
                    [p.load for _s, p in points], max(self.mix["loads"]))

    def sample(self):
        done = [(s["spec"], p, r) for s in self._done()
                for p, r in zip(s["spec"].points(), s["result"].records)]
        chosen = [done[i] for i in self._pick([(s, p) for s, p, _ in done])]
        self.checked = [self._case(s, p) for s, p, _ in chosen]
        return (self.checked,
                [dict(r["stats"], mem_digest=r["mem_digest"])
                 for _s, _p, r in chosen])

    def cases(self, n: int) -> list:
        per = len(self.spec(0).points())
        points = [(spec, p) for spec in
                  (self.spec(i) for i in range(math.ceil(n / per)))
                  for p in spec.points()][:n]
        self.checked = [self._case(*points[i]) for i in self._pick(points)]
        return self.checked

    def numbers(self, got, want) -> Dict[str, float]:
        """A point mismatches when its statistics differ from the
        reference's or the tile memory its phases leave does; one the
        program gave no memory digest for mismatches too."""
        mem = tile_memory.final_digests(
            self.checked, max(1, (os.cpu_count() or 2) - 1))
        out = super().numbers(got, want)
        key = f"mismatched_{self.noun}"
        out[key] = sum(
            Entry.numbers(self, [g], [w])[key] > 0
            or g.get("mem_digest") != m
            for g, w, m in zip(got, want, mem))
        return out


ENTRY = Sweep
