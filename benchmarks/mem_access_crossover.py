#!/usr/bin/env python3
"""Time the fused step's tile-memory access forms on the chip.

    python benchmarks/mem_access_crossover.py [--mem 64 1024 ...]

For each tile memory size ``mem_words`` and each access form
(``onehot``: select-sum read and masked select write over the whole
memory; ``scatter``: the memory stored in rows of ``sim.MEM_ROW`` words,
one word gathered and one scattered per tile; ``store_kernel``: the
same rows and gather, the storing tiles' words written in place by the
``tile_mem_store`` Pallas kernel), runs ``--cycles`` cycles of the
fused step vmapped over a batch of ``--batch`` points, as a
design-space sweep runs them, on a 32x32 mesh under uniform store
traffic at rate 0.5 whose addresses cover the memory, and prints each
form's wall time per point-cycle, the best of ``--reps`` timed calls
after a compiling one, beside the form the step takes at that size
(``sim.mem_access_form``).  The form and the memory's layout are forced
by setting ``sim.MEM_ONEHOT_MAX_WORDS`` and ``sim._mem_store`` before
each trace; the crossover is the largest size at which ``onehot`` is
still the faster.  The last line of standard output is one JSON
object.  Needs a TPU.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.mesh import make_traffic  # noqa: E402
from repro.netsim_jax import sim  # noqa: E402

# form: (MEM_ONEHOT_MAX_WORDS, the step's write of a memory in rows)
FORMS = {"onehot": (1 << 30, sim._mem_store),
         "scatter": (0, sim._mem_scatter),
         "store_kernel": (0, sim._mem_store)}


def per_point_cycle_us(cfg, progs, batch: int, cycles: int,
                       reps: int) -> float:
    # a plain scan over the step, not the jitted ``simulate``, so that
    # each form is traced anew
    def one(p):
        st, done = jax.lax.scan(lambda s, _: sim._step_core(cfg, p, s),
                                sim.init_state(cfg), None, length=cycles)
        return done.sum(), st.mem.sum()
    run = jax.jit(jax.vmap(one))
    jax.block_until_ready(run(progs))  # compile
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        done, _ = jax.block_until_ready(run(progs))
        best = min(best, time.perf_counter() - t0)
    assert int(np.asarray(done).min()) > 0, "no request completed"
    return best / (batch * cycles) * 1e6


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--mem", type=int, nargs="+",
                    default=[64, 256, 1024, 4096, 16384])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--cycles", type=int, default=500)
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"needs a TPU, found {dev.platform}")
    rows = []
    for words in args.mem:
        cfg = sim.SimConfig(nx=32, ny=32, max_out_credits=32, router_fifo=4,
                            ep_fifo=4, mem_words=words)
        # each tile's entries address words 0, s, 2s, ... across the memory
        length = args.cycles // 2 + 1
        stride = max(words // length, 1)
        progs = []
        for b in range(args.batch):
            ent = make_traffic("uniform", cfg.nx, cfg.ny, length, rate=0.5,
                               seed=b, mem_words=words)
            ent["addr"] = (ent["addr"] * stride) % words
            progs.append(sim.load_program(ent))
        progs = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *progs)
        row = {"mem_words": words, "form": sim.mem_access_form(cfg)}
        limit0, store0 = sim.MEM_ONEHOT_MAX_WORDS, sim._mem_store
        for form, (limit, store) in FORMS.items():
            sim.MEM_ONEHOT_MAX_WORDS, sim._mem_store = limit, store
            try:
                row[form] = per_point_cycle_us(cfg, progs, args.batch,
                                               args.cycles, args.reps)
            finally:
                sim.MEM_ONEHOT_MAX_WORDS, sim._mem_store = limit0, store0
        print(f"mem_words {words:6d} ({row['form']}): " + ", ".join(
            f"{form} {row[form]:8.3f}" for form in FORMS)
            + " us per point-cycle", flush=True)
        rows.append(row)
    print(json.dumps({"device": dev.device_kind, "batch": args.batch,
                      "cycles": args.cycles, "rows": rows}))


if __name__ == "__main__":
    main()
