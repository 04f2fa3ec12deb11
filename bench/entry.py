"""What every entry shares.

An entry drives one way into the program.  It is a module of its own,
``bench/entries/<entry>.py``, whose ``ENTRY`` is a subclass of
:class:`Entry`; a traffic mix (``bench/traffic/<mix>.json``) names its
``entry`` and gives every size, rate and count the entry reads, and a
configuration (``bench/configs/<config>.json``) gives the mesh.  The
harness finds the entry by that name, so a new way into the program is
a new file and a new cell on an existing entry is new data.

An entry warms every shape its window uses in ``setup``, runs the
window, reports its end-to-end metrics and counters, and hands the
check the cases to replay on the reference with the program's outputs
for them.
"""
from __future__ import annotations

import importlib.util
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import checks
from bench.common import derive

ENTRIES = Path(__file__).resolve().parent / "entries"

MESH_KEYS = ("nx", "ny", "router_fifo", "ep_fifo", "max_out_credits",
             "mem_words")


def load(name: str) -> type:
    """The entry class of ``bench/entries/<name>.py``."""
    path = ENTRIES / f"{name}.py"
    if not path.is_file():
        known = sorted(p.stem for p in ENTRIES.glob("*.py"))
        raise KeyError(f"no entry {name!r}; known: {known}")
    spec = importlib.util.spec_from_file_location("bench_entry_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.ENTRY


def mesh_config(cfg: dict):
    from repro.mesh import MeshConfig
    if cfg.get("topology", "mesh") != "mesh":
        raise ValueError(f"unsupported topology {cfg['topology']!r}")
    return MeshConfig(**{k: cfg[k] for k in MESH_KEYS})


class Entry:
    noun = "items"

    def __init__(self, cfg: dict, mix: dict, seed: int, chips: int):
        self.cfg, self.mix, self.seed, self.chips = cfg, mix, seed, chips
        self.attempted = self.failed = 0
        self.errors: List[str] = []
        self.wall = 0.0

    def _phase_case(self, depth, credits, pattern, load, length, seed):
        return dict({k: self.cfg[k] for k in MESH_KEYS},
                    router_fifo=depth, max_out_credits=credits,
                    pattern=pattern, length=length, rate=load, seed=seed,
                    warmup=self.mix["warmup"], measure=self.mix["measure"],
                    drain=self.mix["drain"])

    @property
    def horizon(self) -> int:
        return self.mix["warmup"] + self.mix["measure"] + self.mix["drain"]

    def numbers(self, got, want) -> Dict[str, float]:
        return checks.phase_numbers(
            list(zip(got, want)), self.cfg["nx"] * self.cfg["ny"],
            self.mix["measure"], self.noun)

    def control_outputs(self, raws) -> list:
        """The control's replays, in the form the program returns."""
        return [checks.stats_from_raw(r, self.cfg["nx"] * self.cfg["ny"],
                                      self.mix["measure"]) for r in raws]

    def release(self) -> None:
        """Drop what holds the program's device state."""


def pick(seed: int, k: int, loads: list, heaviest: float) -> list:
    """Indices of a seeded sample of ``k`` items, one of them at the
    heaviest load (the most work) where the window made one."""
    rng = np.random.default_rng(derive(seed, 2))
    k = min(k, len(loads))
    heavy = [i for i, ld in enumerate(loads) if ld == heaviest]
    first = [int(rng.choice(heavy))] if heavy else []
    rest = [i for i in range(len(loads)) if i not in first]
    return first + sorted(
        rng.choice(rest, k - len(first), replace=False).tolist())
