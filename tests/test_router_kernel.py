"""Parity suite for the Pallas router-step kernel (``impl="pallas"``).

The kernel's contract is *bit-identity* with the fused-XLA step: both
paths trace the same ``_step_core`` cycle function, so any divergence —
one-hot rewrites of the scatter/gather ops, the multi-cycle launch
decomposition, the drain-fence bookkeeping — is a bug.  The suite checks
the **entire** ``SimState`` pytree leaf-for-leaf (FIFO ring buffers and
all), not just the externally visible contract, because the kernel is an
implementation swap: internal layout must match too.

Covered, per the acceptance criteria:

* mid-flight state parity fused-vs-pallas across the 3-shape x 6-pattern
  grid and the 10-seed randomized-program corpus (telemetry and drain
  cycles included);
* ``cycles_per_call`` bit-identity for {1, 4, remainder launches} and
  for unroll-mismatched drain fences (``check_every % cycles_per_call
  != 0`` and ``cycles_per_call > check_every``);
* ``check_every`` interaction: the exact drain cycle is invariant;
* the single-``step`` entry point and the measurement layer
  (:func:`load_latency_sweep`) under ``impl="pallas"``;
* facade-boundary telemetry snapshots stay immutable after further runs
  (the donation/aliasing regression: ``simulate`` donates its state and
  the kernel aliases inputs to outputs, so a zero-copy snapshot would
  silently mutate).

On hosts without a compiled Pallas backend the kernel runs in interpret
mode (see :mod:`repro.kernels.backend`) — same semantics, so this suite
is the correctness gate CI runs on CPU.
"""
import jax
import numpy as np
import pytest

from repro.core.netsim import OP_CAS, OP_LOAD, OP_STORE
from repro.mesh import MeshConfig, PATTERNS, Simulator, make_traffic
from repro.netsim_jax import (init_state, load_latency_sweep, load_program,
                              simulate, step)
from repro.netsim_jax.testing import assert_state_equal

MESHES = [(2, 2), (4, 4), (3, 5)]          # (nx, ny); incl. non-square


def _pair_impls(cfg, entries, *, cycles_per_call=1, check_every=1,
                fifo_depth=None, max_credits=None):
    """Two jax-backend facades over the same program: the fused-XLA
    reference and the Pallas kernel under test."""
    kw = dict(backend="jax", fifo_depth=fifo_depth, max_credits=max_credits,
              check_every=check_every)
    a = Simulator(cfg, **kw)
    a.attach({k: v.copy() for k, v in entries.items()})
    b = Simulator(cfg, impl="pallas", cycles_per_call=cycles_per_call, **kw)
    b.attach(entries)
    return a, b


def _assert_states_identical(a, b):
    """Every leaf of the packed SimState pytree is bit-identical (plus the
    unified telemetry record, which also pins the cycle counter)."""
    sa, sb = a._sim.state, b._sim.state
    la, ta = jax.tree_util.tree_flatten(sa)
    lb, tb = jax.tree_util.tree_flatten(sb)
    assert ta == tb, "SimState tree structure diverged"
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"SimState leaf {i} (fused vs pallas)")
    a.telemetry().assert_bit_identical(b.telemetry())


# ----------------------------------------------------------------------
# mid-flight parity grid: 3 shapes x 6 patterns
# ----------------------------------------------------------------------
@pytest.mark.parametrize("pattern", sorted(PATTERNS))
@pytest.mark.parametrize("nx,ny", MESHES)
def test_parity_grid_midflight(pattern, nx, ny):
    """Fused vs Pallas, stopped mid-flight (packets still in FIFOs, in
    the response delay line, waiting on credits): the full state pytree
    must match leaf-for-leaf, not just after drain."""
    if pattern == "transpose" and nx != ny:
        pytest.skip("transpose is undefined on non-square meshes")
    cfg = MeshConfig(nx=nx, ny=ny, max_out_credits=6)
    entries = make_traffic(pattern, nx, ny, 8, rate=0.7, seed=11)
    a, b = _pair_impls(cfg, entries)
    a.run(40)
    b.run(40)
    _assert_states_identical(a, b)


# ----------------------------------------------------------------------
# randomized-program corpus (the properties-suite generator, pallas side)
# ----------------------------------------------------------------------
def _random_prog(rng, ny, nx, L, ops):
    prog = {k: np.zeros((ny, nx, L), np.int64)
            for k in ("dst_x", "dst_y", "addr", "data", "cmp", "op",
                      "not_before")}
    prog["op"][:] = rng.choice(ops, size=(ny, nx, L))
    lens = rng.integers(0, L + 1, size=(ny, nx))
    prog["op"][np.arange(L)[None, None, :] >= lens[..., None]] = -1
    prog["dst_x"][:] = rng.integers(0, nx, (ny, nx, L))
    prog["dst_y"][:] = rng.integers(0, ny, (ny, nx, L))
    prog["addr"][:] = rng.integers(0, 16, (ny, nx, L))
    prog["data"][:] = rng.integers(0, 1 << 20, (ny, nx, L))
    prog["cmp"][:] = rng.integers(0, 4, (ny, nx, L))
    return prog


FUZZ_MESHES = ((2, 2), (3, 2), (4, 3))
FUZZ_L = 6


@pytest.mark.parametrize("seed", range(10))
def test_parity_fuzz_corpus(seed):
    """Random programs (shape, ops incl. CAS, pacing, effective FIFO
    depth / credit allowance as state), run to drain on both impls:
    identical drain cycle and identical state.  Odd seeds run the kernel
    with a multi-cycle inner loop that does not divide the drain-fence
    cadence, so remainder launches are exercised across the corpus."""
    rng = np.random.default_rng(1000 + seed)
    nx, ny = FUZZ_MESHES[int(rng.integers(0, len(FUZZ_MESHES)))]
    fifo = int(rng.integers(2, 5))
    credits = int(rng.integers(1, 9))
    resp_latency = int(rng.integers(1, 3))
    rate = int(rng.integers(10, 101)) / 100.0
    ops = (OP_STORE, OP_LOAD, OP_CAS) if rng.integers(0, 2) \
        else (OP_STORE, OP_LOAD)
    prog = _random_prog(rng, ny, nx, FUZZ_L, ops)
    prog["not_before"][:] = np.floor(np.arange(FUZZ_L) / rate).astype(np.int64)

    # capacity config with the effective depth/credits as state, as the
    # differential fuzz does — amortizes compilations across the corpus
    cfg = MeshConfig(nx=nx, ny=ny, router_fifo=4, ep_fifo=4,
                     max_out_credits=8, mem_words=16,
                     resp_latency=resp_latency)
    a, b = _pair_impls(cfg, prog, cycles_per_call=3 if seed % 2 else 1,
                       check_every=4, fifo_depth=fifo, max_credits=credits)
    ca = a.run_until_drained(max_cycles=4000)
    cb = b.run_until_drained(max_cycles=4000)
    assert ca == cb, "drain cycle diverged"
    _assert_states_identical(a, b)


# ----------------------------------------------------------------------
# cycles_per_call bit-identity
# ----------------------------------------------------------------------
@pytest.mark.parametrize("cycles_per_call", [1, 4, 7])
def test_cycles_per_call_bit_identity(cycles_per_call):
    """A fixed 96-cycle horizon decomposes as 96x1, 24x4 and 13x7+5 (the
    last exercising the remainder launch); every decomposition must land
    on the same state as the fused reference."""
    cfg = MeshConfig(nx=4, ny=4, max_out_credits=6)
    entries = make_traffic("uniform", 4, 4, 12, rate=0.6, seed=5)
    a, b = _pair_impls(cfg, entries, cycles_per_call=cycles_per_call)
    a.run(96)
    b.run(96)
    _assert_states_identical(a, b)


@pytest.mark.parametrize("check_every,cycles_per_call",
                         [(1, 4),   # kernel loop longer than the fence block
                          (8, 3)])  # fence block not a multiple of the loop
def test_unroll_mismatched_drain_fences(check_every, cycles_per_call):
    """Drain fences that do not line up with the kernel's inner loop:
    the launch decomposition clamps/splits per fence block, and the drain
    cycle plus the full final state still match the fused path run with
    the same fence cadence."""
    cfg = MeshConfig(nx=4, ny=4, max_out_credits=4)
    entries = make_traffic("tornado", 4, 4, 6, seed=3)
    a, b = _pair_impls(cfg, entries, cycles_per_call=cycles_per_call,
                       check_every=check_every)
    ca = a.run_until_drained()
    cb = b.run_until_drained()
    assert ca == cb, "drain cycle diverged"
    _assert_states_identical(a, b)


def test_check_every_leaves_drain_cycle_unchanged():
    """The exact drain cycle is a property of the network, not of the
    fence-check cadence or the kernel's launch decomposition: every
    (check_every, cycles_per_call) combination reports the same cycle as
    the fused check_every=1 reference — and the same delivered memory."""
    cfg = MeshConfig(nx=4, ny=4, max_out_credits=4)
    entries = make_traffic("hotspot", 4, 4, 6, fraction=0.8, seed=9)
    ref = Simulator(cfg, backend="jax")
    ref.attach({k: v.copy() for k, v in entries.items()})
    c_ref = ref.run_until_drained()
    for check_every, cycles_per_call in [(1, 1), (5, 2), (8, 3)]:
        sim = Simulator(cfg, backend="jax", impl="pallas",
                        cycles_per_call=cycles_per_call,
                        check_every=check_every)
        sim.attach({k: v.copy() for k, v in entries.items()})
        assert sim.run_until_drained() == c_ref, \
            f"drain cycle moved at check_every={check_every}, " \
            f"cycles_per_call={cycles_per_call}"
        np.testing.assert_array_equal(np.asarray(ref.mem),
                                      np.asarray(sim.mem))


# ----------------------------------------------------------------------
# oracle anchor + functional entry points
# ----------------------------------------------------------------------
def test_pallas_matches_numpy_oracle():
    """Transitivity made explicit: the kernel path agrees with the numpy
    oracle directly (memory, stats, traces, telemetry, packet fields)."""
    cfg = MeshConfig(nx=4, ny=4, max_out_credits=4)
    entries = make_traffic("uniform", 4, 4, 6, op=OP_LOAD, seed=7)
    a = Simulator(cfg, backend="numpy")
    a.attach({k: v.copy() for k, v in entries.items()})
    b = Simulator(cfg, backend="jax", impl="pallas", cycles_per_call=2)
    b.attach(entries)
    assert a.run_until_drained() == b.run_until_drained()
    assert_state_equal(a, b)


def test_single_step_parity():
    """The raw functional ``step(..., impl="pallas")`` advances exactly
    one cycle, bit-identically, including the per-cycle completion
    count it returns."""
    cfg = MeshConfig(nx=3, ny=3, max_out_credits=4).to_sim()
    prog = load_program(make_traffic("neighbor", 3, 3, 4, seed=2))
    st_f = init_state(cfg)
    st_p = init_state(cfg)
    for cyc in range(12):
        st_f, done_f = step(cfg, prog, st_f)
        st_p, done_p = step(cfg, prog, st_p, impl="pallas")
        assert int(done_f) == int(done_p), f"completions diverged at {cyc}"
    la = jax.tree_util.tree_leaves(st_f)
    lb = jax.tree_util.tree_leaves(st_p)
    for i, (x, y) in enumerate(zip(la, lb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"SimState leaf {i}")


def test_load_latency_sweep_impl_invariant():
    """The measurement layer under ``impl="pallas"`` (vmapped over rates)
    reproduces the fused sweep bit-for-bit — histograms included."""
    kw = dict(warmup=20, measure=40, drain=40,
              cfg=MeshConfig(nx=2, ny=2, max_out_credits=8), seed=1)
    ref = load_latency_sweep("uniform", 2, 2, (0.1, 0.3), **kw)
    out = load_latency_sweep("uniform", 2, 2, (0.1, 0.3), impl="pallas",
                             cycles_per_call=5, **kw)
    for k in ("offered", "accepted", "delivered", "lat_mean", "lat_p99",
              "peak_link_util", "hist"):
        np.testing.assert_array_equal(np.asarray(ref[k]), np.asarray(out[k]),
                                      err_msg=f"sweep field {k!r}")


# ----------------------------------------------------------------------
# knob validation + facade-boundary snapshot semantics
# ----------------------------------------------------------------------
def test_invalid_knobs_rejected():
    cfg = MeshConfig(nx=2, ny=2)
    with pytest.raises(ValueError, match="impl"):
        Simulator(cfg, backend="jax", impl="bogus")
    with pytest.raises(ValueError, match="cycles_per_call"):
        Simulator(cfg, backend="jax", impl="pallas", cycles_per_call=0)
    with pytest.raises(ValueError, match="impl"):
        simulate(cfg.to_sim(), load_program(make_traffic("uniform", 2, 2, 2)),
                 init_state(cfg.to_sim()), 4, 1, "bogus")


@pytest.mark.parametrize("impl", ["fused", "pallas"])
def test_telemetry_snapshot_survives_donation(impl):
    """The aliasing regression: ``simulate`` donates its SimState and the
    Pallas kernel aliases inputs to outputs, so the backing buffers of a
    telemetry record taken mid-run get overwritten by the next ``run``.
    ``Telemetry.of`` must copy at the facade boundary — the snapshot is a
    point in time, whatever runs afterwards."""
    cfg = MeshConfig(nx=4, ny=4, max_out_credits=6)
    entries = make_traffic("uniform", 4, 4, 12, rate=0.8, seed=4)
    sim = Simulator(cfg, backend="jax", impl=impl,
                    cycles_per_call=2 if impl == "pallas" else 1)
    sim.attach(entries)
    sim.run(40)
    snap = sim.telemetry()
    frozen = {f: np.asarray(getattr(snap, f)).copy()
              for f in ("completed", "lat_sum", "completed_per_cycle",
                        "link_util_fwd", "lat_hist")}
    sim.run(80)
    after = sim.telemetry()
    assert int(after.completed.sum()) > int(snap.completed.sum()), \
        "the second run delivered nothing — the regression check is vacuous"
    for f, want in frozen.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(snap, f)), want,
            err_msg=f"telemetry snapshot field {f!r} mutated by a later run")


def test_vmem_limit_binds_the_compiled_kernel_only():
    """Interpret mode (the CPU tests' path) runs a mesh the compiled
    kernel would refuse for VMEM; only ``interpret=False`` raises."""
    from repro.kernels.router_step import (VMEM_LIMIT_BYTES, router_step_call,
                                           vmem_bytes)
    cfg = MeshConfig(nx=32, ny=32, max_out_credits=8).to_sim()
    prog = load_program(make_traffic("uniform", 32, 32, 4, seed=0))
    st = init_state(cfg)
    assert vmem_bytes(prog, st, 2) > VMEM_LIMIT_BYTES
    out = jax.eval_shape(lambda p, s: router_step_call(cfg, p, s, 2,
                                                       interpret=True),
                         prog, st)
    assert out[1].shape == (2,)
    with pytest.raises(ValueError, match=r"32x32"):
        jax.eval_shape(lambda p, s: router_step_call(cfg, p, s, 2,
                                                     interpret=False),
                       prog, st)
