"""Serving: continuous batching correctness (slot isolation)."""
import numpy as np
import pytest

from repro.configs import get_config, reduced_config
from repro.launch.serve import Request, Server


@pytest.fixture(scope="module")
def cfg():
    return reduced_config(get_config("stablelm-3b"),
                          num_layers=2, d_model=64, num_heads=2,
                          num_kv_heads=2, head_dim=32, d_ff=128,
                          vocab_size=128)


def _serve(cfg, mesh, prompts, slots, max_new=6):
    server = Server(cfg, mesh, slots=slots, max_seq=64)
    for i, p in enumerate(prompts):
        server.submit(Request(rid=i, prompt=np.asarray(p, np.int32),
                              max_new=max_new))
    server.run(tick_limit=500)
    done = sorted(server.completed, key=lambda r: r.rid)
    return [r.out for r in done], server


def test_all_requests_complete(cfg, mesh_dm):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 128, size=5) for _ in range(6)]
    outs, server = _serve(cfg, mesh_dm, prompts, slots=2)
    assert len(outs) == 6
    assert all(len(o) == 6 for o in outs)


def test_continuous_batching_matches_isolated(cfg, mesh_dm):
    """Outputs must be identical whether a request runs alone (1 slot) or
    packed with others (2 slots, staggered admission) — proves slot/cache
    isolation under continuous batching."""
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 128, size=4) for _ in range(4)]
    outs_iso = []
    for p in prompts:
        o, _ = _serve(cfg, mesh_dm, [p], slots=1)
        outs_iso.append(o[0])
    outs_packed, _ = _serve(cfg, mesh_dm, prompts, slots=2)
    assert outs_packed == outs_iso


def test_slot_reuse_after_completion(cfg, mesh_dm):
    """More requests than slots: slots recycle (credits return)."""
    rng = np.random.default_rng(2)
    prompts = [rng.integers(0, 128, size=3) for _ in range(5)]
    outs, server = _serve(cfg, mesh_dm, prompts, slots=2, max_new=4)
    assert len(outs) == 5
    assert server.ticks < 500
