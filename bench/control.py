#!/usr/bin/env python3
"""Read the output check's numbers with the control in the program's place.

    python3 bench/control.py --workload <cell> --items <n> --seeds 1 2 3

The control is the plain reference with one stated guarantee broken: no
registered response port, so every response counts one cycle early (see
``bench/reference.py``).  For each seed it takes the items a run of the
cell would check when its window produced ``--items`` of them, at the
cell's own sizes, replays them on the reference and on the control, and
prints the numbers the check compares: the upper readings the check's
limits are set below.  It needs no accelerator; the benchmark's own
runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def readings(name: str, seed: int, items: int, workers: int,
             root: Path = ROOT, shrink=None) -> dict:
    from bench import entry, harness, reference
    _, cell, cfg, mix = harness.find_cell(name, root)
    if shrink is not None:
        cfg, mix = shrink(dict(cfg), dict(mix))
    drv = entry.load(mix["entry"])(cfg, mix, seed, cell["chips"])
    cases = drv.cases(items)
    want = reference.replay_all(cases, True, workers)
    got = drv.control_outputs(reference.replay_all(cases, False, workers))
    return drv.numbers(got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--items", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[0:1] = [str(ROOT), str(ROOT / "src")]
    workers = max(1, (os.cpu_count() or 2) - 1)
    for seed in args.seeds:
        t = time.perf_counter()
        out = readings(args.workload, seed, args.items, workers)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": out,
                          "seconds": time.perf_counter() - t}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
