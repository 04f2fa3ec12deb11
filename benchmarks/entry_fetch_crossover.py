#!/usr/bin/env python3
"""Time the fused drain step's two program-entry fetches on the chip.

    python benchmarks/entry_fetch_crossover.py [--lp 128 512 ...]

For each program length ``Lp`` (entries per tile) and each fetch form
(``onehot``: exact select-sum over the program axis; ``gather``:
``take_along_axis``), runs ``--cycles`` cycles of the fused drain loop
(fence every cycle) on the 16x32 mesh under uniform traffic at rate
0.5, and prints each pair's wall time per cycle, the best of
``--reps`` timed calls after a compiling one.  The form is forced by
setting ``sim.ENTRY_ONEHOT_MAX_LP`` before each trace; the crossover
is the largest ``Lp`` at which ``onehot`` is still the faster.  The
last line of standard output is one JSON object.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

from repro.mesh import make_traffic  # noqa: E402
from repro.netsim_jax import sim  # noqa: E402

FORMS = {"onehot": 1 << 30, "gather": 0}


def per_cycle_us(cfg, prog, cycles: int, reps: int) -> float:
    run = jax.jit(lambda p, s: sim._drain_loop(cfg, p, s, cycles, 1,
                                               False)[1])
    jax.block_until_ready(run(prog, sim.init_state(cfg)))  # compile
    best = float("inf")
    for _ in range(reps):
        st = jax.block_until_ready(sim.init_state(cfg))
        t0 = time.perf_counter()
        steps = int(jax.block_until_ready(run(prog, st)))
        best = min(best, time.perf_counter() - t0)
    assert steps == cycles, f"drained after {steps} of {cycles} cycles"
    return best / cycles * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--lp", type=int, nargs="+",
                    default=[128, 512, 1024, 2048, 4096])
    ap.add_argument("--cycles", type=int, default=2000)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    cfg = sim.SimConfig(nx=16, ny=32, max_out_credits=64, router_fifo=4,
                        ep_fifo=4, mem_words=64)
    rows = []
    for lp in args.lp:
        prog = sim.load_program(make_traffic("uniform", cfg.nx, cfg.ny, lp,
                                             rate=0.5, seed=lp,
                                             mem_words=cfg.mem_words))
        row = {"lp": lp}
        for form, limit in FORMS.items():
            sim.ENTRY_ONEHOT_MAX_LP = limit
            row[form] = per_cycle_us(cfg, prog, args.cycles, args.reps)
        print(f"Lp {lp:5d}: onehot {row['onehot']:8.3f} us/cycle, "
              f"gather {row['gather']:8.3f} us/cycle", flush=True)
        rows.append(row)
    print(json.dumps({"device": dev.device_kind, "cycles": args.cycles,
                      "rows": rows}))


if __name__ == "__main__":
    main()
