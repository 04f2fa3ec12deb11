"""The fused step's program-entry fetch and memory read.

Programs of up to ``ENTRY_ONEHOT_MAX_LP`` entries per tile fetch each
tile's next entry by an exact one-hot select; longer ones keep XLA's
gather, and the memory read of the 64 words here is a one-hot select
(``tests/test_mem_access.py`` covers larger memories).  Both
fetch forms must drain bit-identically to the numpy oracle, including a
tile whose pointer reaches the end of the program (the clipped index)
and tiles padded with ``op < 0`` entries.
"""
import jax
import numpy as np
import pytest

from repro.mesh import MeshConfig, Simulator, make_traffic
from repro.netsim_jax.sim import (ENTRY_ONEHOT_MAX_LP, I32, PROG_FIELDS,
                                  Program, SimConfig, _entry_fetch_onehot,
                                  _step_core, init_state)
from repro.netsim_jax.testing import assert_state_equal

LONGEST_ONEHOT = ENTRY_ONEHOT_MAX_LP
SHORTEST_GATHER = ENTRY_ONEHOT_MAX_LP + 1


def _gather_scopes(jaxpr, scope: str = "") -> list:
    """The name scope of every ``gather`` in ``jaxpr``, nested calls
    included (an inner equation reports its outermost caller's scope)."""
    found = []
    for eqn in jaxpr.eqns:
        here = scope or str(eqn.source_info.name_stack)
        if eqn.primitive.name == "gather":
            found.append(here)
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    found += _gather_scopes(sub, here)
    return found


def _step_gathers(nx: int, ny: int, lp: int) -> list:
    cfg = SimConfig(nx=nx, ny=ny, max_out_credits=64, router_fifo=4)
    prog = Program(
        buf=jax.ShapeDtypeStruct((len(PROG_FIELDS), ny, nx, lp), I32),
        length=jax.ShapeDtypeStruct((ny, nx), I32))
    state = jax.eval_shape(lambda: init_state(cfg))
    closed = jax.make_jaxpr(lambda p, s: _step_core(cfg, p, s))(prog, state)
    return _gather_scopes(closed.jaxpr)


def test_form_follows_program_length():
    assert _entry_fetch_onehot(LONGEST_ONEHOT, kernel_safe=False)
    assert not _entry_fetch_onehot(SHORTEST_GATHER, kernel_safe=False)
    assert _entry_fetch_onehot(SHORTEST_GATHER, kernel_safe=True)


def test_drain_cell_step_has_no_gather():
    """The benchmark's drain shapes (16x32, 128 entries per tile): both
    the entry fetch and the memory read are one-hot selects."""
    assert _step_gathers(16, 32, 128) == []


def test_long_program_step_gathers_only_its_entry():
    """Above the crossover the one gather is the entry fetch."""
    assert _step_gathers(16, 32, SHORTEST_GATHER) == ["step/inject/fetch"]


@pytest.mark.parametrize("lp", [8, LONGEST_ONEHOT, SHORTEST_GATHER])
def test_fused_drain_matches_oracle_on_both_forms(lp):
    """A 4x4 drain, bit for bit against the oracle: every tile but one
    holds six entries and ``lp - 6`` padding entries (``op < 0``); tile
    (x=2, y=1) holds ``lp`` entries, so its pointer reaches the end of
    the program and the fetch index is clipped."""
    nx = ny = 4
    entries = make_traffic("uniform", nx, ny, lp, rate=1.0, seed=lp)
    full = entries["op"][1, 2].copy()
    assert (full >= 0).all()
    entries["op"][..., 6:] = -1
    entries["op"][1, 2] = full
    cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=16)
    oracle = Simulator(cfg, backend="numpy")
    oracle.attach({k: v.copy() for k, v in entries.items()})
    fused = Simulator(cfg, backend="jax")
    fused.attach(entries)
    assert oracle.run_until_drained() == fused.run_until_drained()
    assert_state_equal(oracle, fused)
    oracle.telemetry().assert_bit_identical(fused.telemetry())
    assert int(fused.completed.sum()) == 6 * (nx * ny - 1) + lp
