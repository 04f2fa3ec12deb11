"""The plain reference against the program, and the control against the
reference, at sizes a test run holds."""
import numpy as np
import pytest

from bench import checks, patterns, reference
from repro.mesh import MeshConfig, Simulator
from repro.netsim_jax.measure import phased_stats
from repro.netsim_jax.sim import init_state, load_program

DRAINS = [("uniform", 4, 4, 16, 0.5, 4, 64), ("hotspot", 8, 4, 24, 0.3, 2, 8),
          ("tornado", 6, 6, 12, 0.2, 4, 32)]
PHASED = [("uniform", 0.3, 2, 8), ("bit_complement", 0.2, 4, 32),
          ("neighbor", 0.05, 4, 64), ("hotspot", 0.1, 2, 32)]


def _case(nx, ny, depth, credits, pattern, length, rate, seed, **kw):
    return dict(nx=nx, ny=ny, router_fifo=depth, ep_fifo=4,
                max_out_credits=credits, mem_words=64, pattern=pattern,
                length=length, rate=rate, seed=seed, **kw)


def _program_drain(case):
    sim = Simulator(MeshConfig(nx=case["nx"], ny=case["ny"],
                               router_fifo=case["router_fifo"],
                               max_out_credits=case["max_out_credits"]),
                    backend="jax")
    sim.attach(patterns.make_traffic(case["pattern"], case["nx"], case["ny"],
                                     case["length"], rate=case["rate"],
                                     seed=case["seed"]))
    cycle = sim.run_until_drained(case["max_cycles"])
    tel = sim.telemetry()
    out = {f: getattr(tel, f) for f in reference.TELEMETRY_FIELDS}
    out["cycles"] = np.asarray(tel.cycles)
    return cycle, out


@pytest.mark.parametrize("pattern,nx,ny,length,rate,depth,credits", DRAINS)
def test_drain_reference_matches_the_program_and_the_control_does_not(
        pattern, nx, ny, length, rate, depth, credits):
    case = _case(nx, ny, depth, credits, pattern, length, rate, 11,
                 max_cycles=20_000)
    got = _program_drain(case)
    assert checks.drain_numbers([(got, reference.replay(case))]) == \
        {"mismatched_jobs": 0, "drain_cycle_gap": 0}
    control = checks.drain_numbers([(reference.replay(case, False),
                                     reference.replay(case))])
    assert control["mismatched_jobs"] == 1
    assert control["drain_cycle_gap"] >= 1


def _program_phased(case, nx, ny):
    cfg = MeshConfig(nx=nx, ny=ny, router_fifo=4, max_out_credits=64).to_sim()
    prog = load_program(patterns.make_traffic(
        case["pattern"], nx, ny, case["length"], rate=case["rate"],
        seed=case["seed"]))
    st = phased_stats(cfg, prog, init_state(cfg, case["router_fifo"],
                                            case["max_out_credits"]),
                      case["warmup"], case["measure"], case["drain"])
    out = {k: float(v) for k, v in st._asdict().items() if k != "hist"}
    out["hist"] = np.asarray(st.hist)
    return out


@pytest.mark.parametrize("pattern,rate,depth,credits", PHASED)
def test_phased_reference_matches_the_program_and_the_control_does_not(
        pattern, rate, depth, credits):
    nx, ny, w, m, d = 8, 4, 30, 60, 60
    case = _case(nx, ny, depth, credits, pattern,
                 patterns.program_length(rate, w + m + d), rate, 5,
                 warmup=w, measure=m, drain=d)
    raw = reference.replay(case)
    got = _program_phased(case, nx, ny)
    sound = checks.phase_numbers([(got, raw)], nx * ny, m, "points")
    assert sound["mismatched_points"] == 0
    assert sound["lat_mean_gap"] < 1e-6
    control = checks.stats_from_raw(reference.replay(case, False), nx * ny, m)
    failed = checks.phase_numbers([(control, raw)], nx * ny, m, "points")
    assert failed["mismatched_points"] == 1
    assert failed["lat_mean_gap"] > 1e-3
