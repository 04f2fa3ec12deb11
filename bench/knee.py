#!/usr/bin/env python3
"""Find the highest rate the service cell's mix sustains, by a sweep.

    python3 bench/knee.py --workload <service cell> --seconds 10 --rates 20 40 60

One process, one set-up; then one window per rate, each with its own
seed.  For each rate it prints the offered and completed rates, the
median and 95th percentile latency, and the latency of the window's
last quarter of requests against its first: a backlog that grows all
through the window shows as a last quarter far slower than the first.
The cell's ``rate_per_s`` is set at about four fifths of the highest
rate that keeps up.  Nothing here is run by the benchmark itself.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    import numpy as np
    from bench import entry, harness
    from bench.common import Spans, Tracer
    _, cell, cfg, mix = harness.find_cell(args.workload, ROOT)
    harness.find_devices(cell["chips"], require_tpu=True)
    harness.arm_compile_cache(ROOT)
    service = entry.load(mix["entry"])
    service(cfg, mix, args.seed, cell["chips"]).setup()
    for i, rate in enumerate(args.rates):
        drv = service(cfg, dict(mix, rate_per_s=rate), args.seed + 1 + i,
                      cell["chips"])
        drv.window(args.seconds, Spans(), Tracer(None, 0, Spans()))
        lat = drv.latencies()
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_per_s": rate, **drv.end_to_end(),
            "p50_latency_ms": float(np.median(lat)) * 1e3,
            "p95_latency_ms": float(np.percentile(lat, 95)) * 1e3,
            "first_quarter_ms": float(np.mean(lat[:q])) * 1e3,
            "last_quarter_ms": float(np.mean(lat[-q:])) * 1e3,
            "failed": drv.failed, **drv.counters(None)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
