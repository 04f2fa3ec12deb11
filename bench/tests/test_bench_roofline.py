"""The mesh state the roofline counts, at the configuration's mesh and at
a 32x32 mesh of the same buffers."""
import json

import jax
import pytest

from bench.roofline import least_seconds_per_cycle, state_bytes
from bench.harness import ROOT


def _cfg(name):
    if name == "mesh-32x32":
        return {"nx": 32, "ny": 32, "router_fifo": 4, "ep_fifo": 4,
                "max_out_credits": 32, "mem_words": 64}
    path = ROOT / "bench" / "configs" / f"{name}.json"
    return json.loads(path.read_text())


@pytest.mark.parametrize("name,want", [("celerity-16x32", 723_988),
                                       ("mesh-32x32", 1_445_908)])
def test_state_bytes(name, want):
    assert state_bytes(_cfg(name)) == want


@pytest.mark.parametrize("name", ["celerity-16x32", "mesh-32x32"])
def test_state_bytes_match_the_programs_state_today(name):
    """The count is the model's; today's SimState holds exactly it."""
    from repro.mesh import MeshConfig
    from repro.netsim_jax.sim import init_state
    cfg = _cfg(name)
    sim = MeshConfig(**{k: cfg[k] for k in (
        "nx", "ny", "router_fifo", "ep_fifo", "max_out_credits",
        "mem_words")}).to_sim()
    leaves = jax.tree_util.tree_leaves(jax.eval_shape(lambda: init_state(sim)))
    assert sum(x.size * x.dtype.itemsize for x in leaves) == state_bytes(cfg)


def test_least_time_per_cycle_at_celerity_scale():
    t = least_seconds_per_cycle(_cfg("celerity-16x32"), 8.19e11)
    assert t == pytest.approx(1.768e-6, rel=1e-3)
