"""Open-loop requests to ``repro.sim_service``'s ``SimServer`` at a fixed rate.

``rate_per_s * seconds`` requests arrive in the window with exponential
gaps, as a Poisson process's are; every seed sends the same set of gaps
and the same multiset of request kinds (the product of the mix's
``patterns``, ``loads``, ``fifo_depths`` and ``max_credits``), each in
its own order.  The loop
submits each request when it is due and ticks the server while it has
work; a request is timed from its due time to the tick that answers it.
"""
from __future__ import annotations

import itertools
import time
from typing import Dict, List

import numpy as np

from bench import patterns
from bench.common import Spans, Tracer, derive
from bench.entry import Entry, mesh_config, pick


class Service(Entry):
    """Open-loop requests to the simulation service at a fixed rate."""
    noun = "requests"

    def __init__(self, *a):
        super().__init__(*a)
        self.mesh = mesh_config(self.cfg)
        self.requests: List[dict] = []
        self.server = None

    def schedule(self, seconds: float) -> List[dict]:
        """``rate * seconds`` requests.  Their gaps are the ``n``
        quantiles of an exponential distribution, scaled to fill the
        window, so every seed offers the same load over the same span;
        the seed orders the gaps and the request kinds."""
        m = self.mix
        n = int(round(m["rate_per_s"] * seconds))
        rng = np.random.default_rng(derive(self.seed, 3))
        gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
        gaps = rng.permutation(gaps * (seconds / gaps.sum()))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        kinds = list(itertools.product(m["patterns"], m["loads"],
                                       m["fifo_depths"], m["max_credits"]))
        kinds = (kinds * (n // len(kinds) + 1))[:n]
        order = rng.permutation(n)
        return [{"due": float(due[j]), "pattern": kinds[order[j]][0],
                 "load": kinds[order[j]][1], "fifo_depth": kinds[order[j]][2],
                 "max_credits": kinds[order[j]][3],
                 "seed": derive(self.seed, 0, j)} for j in range(n)]

    def _request(self, r: dict):
        from repro.sim_service import SimRequest
        m = self.mix
        return SimRequest(cfg=self.mesh, pattern=r["pattern"], load=r["load"],
                          seed=r["seed"], fifo_depth=r["fifo_depth"],
                          max_credits=r["max_credits"], warmup=m["warmup"],
                          measure=m["measure"], drain=m["drain"],
                          check_every=m["check_every"])

    def _server(self):
        from repro.sim_service import SimServer
        return SimServer(max_batch=self.mix["max_batch"],
                         queue_limit=self.mix["queue_limit"])

    def setup(self) -> None:
        """One batch of each width at each load: every block, init and
        reduce program the window can meet."""
        server = self._server()
        m = self.mix
        kinds = itertools.cycle(itertools.product(
            m["patterns"], m["fifo_depths"], m["max_credits"]))
        width = 1
        while width <= server.max_batch:
            for load in m["loads"]:
                for _ in range(width):
                    p, d, c = next(kinds)
                    server.submit(self._request(
                        {"pattern": p, "load": load, "fifo_depth": d,
                         "max_credits": c,
                         "seed": derive(self.seed, 1, width)}))
                server.run_until_idle()
            width *= 2

    def window(self, seconds: float, spans: Spans, tracer: Tracer) -> None:
        from repro.sim_service import ServiceOverloaded
        self.server = server = self._server()
        reqs = self.requests = self.schedule(seconds)
        self.attempted = len(reqs)
        t0 = tracer.clock()
        deadline = t0 + seconds + self.mix["late_wait_s"]
        tracer.start()
        i, inflight = 0, []
        while True:
            now = tracer.clock()
            while i < len(reqs) and t0 + reqs[i]["due"] <= now:
                r = reqs[i]
                r["submitted"] = tracer.clock() - t0
                try:
                    with spans("submit"):
                        inflight.append((r, server.submit(self._request(r))))
                except ServiceOverloaded:
                    r["refused"] = True
                i += 1
            if not server.idle:
                with spans("tick"):
                    server.tick()
                t = tracer.clock() - t0
                still = []
                for r, ticket in inflight:
                    if ticket.done:
                        r["done"], r["response"] = t, ticket.response
                    else:
                        still.append((r, ticket))
                inflight = still
            elif i < len(reqs):
                time.sleep(max(0.0, t0 + reqs[i]["due"] - tracer.clock()))
            else:
                break
            tracer.poll()
            if tracer.clock() > deadline:
                break
        tracer.stop()
        self.closed = tracer.clock() - t0
        self.failed = sum("done" not in r for r in reqs)
        unanswered = [r for r in reqs
                      if "done" not in r and not r.get("refused")]
        if unanswered:
            self.errors.append(f"{len(unanswered)} requests never answered "
                               f"within {self.mix['late_wait_s']} s of the "
                               f"window's close")
        done = [r["done"] for r in reqs if "done" in r]
        self.wall = max(done) if done else self.closed

    def latencies(self) -> np.ndarray:
        """Seconds from due to response; a request that was refused or
        never answered counts as answered when the run stopped waiting."""
        return np.asarray([r.get("done", self.closed) - r["due"]
                           for r in self.requests])

    def end_to_end(self) -> Dict[str, float]:
        done = sum("done" in r for r in self.requests)
        return {"requests_per_s": done / self.wall}

    def counters(self, tracer: Tracer) -> Dict[str, float]:
        m = self.server.metrics
        late = np.asarray([r["submitted"] - r["due"] for r in self.requests
                           if "submitted" in r])
        waits = [r["response"].metrics["queue_wait_s"] for r in self.requests
                 if "response" in r]
        lat = self.latencies()
        return {"latency_p50_ms": float(np.median(lat)) * 1e3,
                "latency_p95_ms": float(np.percentile(lat, 95)) * 1e3,
                "lanes": m.lanes, "batches": m.batches, "ticks": m.ticks,
                "blocks": m.blocks, "rejected": m.rejected,
                "queue_wait_median_s": float(np.median(waits)) if waits
                else float("nan"),
                "generator_late_p50_s": float(np.median(late)),
                "generator_late_max_s": float(late.max())}

    def _case(self, r: dict) -> dict:
        return self._phase_case(r["fifo_depth"], r["max_credits"],
                                r["pattern"], r["load"],
                                patterns.program_length(r["load"],
                                                        self.horizon),
                                r["seed"])

    def _pick(self, reqs: list) -> list:
        return pick(self.seed, self.mix["check_requests"],
                     [r["load"] for r in reqs], max(self.mix["loads"]))

    def sample(self):
        done = [r for r in self.requests if "response" in r]
        chosen = [done[i] for i in self._pick(done)]
        outs = [{**{f: float(v) for f, v in r["response"].stats._asdict()
                    .items() if f != "hist"},
                 "hist": np.asarray(r["response"].stats.hist)} for r in chosen]
        return [self._case(r) for r in chosen], outs

    def cases(self, n: int) -> list:
        reqs = self.schedule(n / self.mix["rate_per_s"])
        return [self._case(reqs[i]) for i in self._pick(reqs)]

    def release(self) -> None:
        self.server = None



ENTRY = Service
