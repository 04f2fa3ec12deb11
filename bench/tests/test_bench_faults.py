"""Whole runs of each cell, at a test size on the CPU, with the timed path
sound and then broken underneath: ``correct`` has to come out false for
every fault the cell can have.

The harness's look for a chip is skipped (``require_tpu=False``); the
rest of a run is the benchmark's own: set-up, window, sample, replay on
the reference, comparison.  Each run checks every item its window made.
"""
import json
import shutil

import jax
import jax.numpy as jnp
import pytest

from bench import harness

DRAIN = "celerity-16x32.drain-uniform"
SERVICE = "celerity-16x32.service-open"


@pytest.fixture
def checkout(tmp_path):
    """A checkout of the benchmark's data in a temporary directory, so the
    runs' compile cache stays out of the repository."""
    shutil.copy(harness.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for d in ("configs", "traffic"):
        shutil.copytree(harness.ROOT / "bench" / d, tmp_path / "bench" / d)
    jax.clear_caches()
    yield tmp_path
    jax.clear_caches()


def shrink(cfg, mix):
    phases = dict(warmup=20, measure=40, drain=40)
    if mix["entry"] == "drain":
        cfg.update(nx=4, ny=4)
        mix.update(entries_per_tile=16, max_cycles=2000, check_jobs=10**6)
    else:
        cfg.update(nx=4, ny=8)
        mix.update(phases, check_every=10, rate_per_s=12.0,
                   check_requests=10**6, late_wait_s=30)
    return cfg, mix


def run(checkout, cell, seconds):
    r = harness.run(cell, 2**31 + 99, seconds, False, root=checkout,
                    require_tpu=False, shrink=shrink, workers=1)
    json.dumps(r)
    return r


# -- faults, each planted in the program under the timed path -----------

def frozen_step(monkeypatch):
    """A step that returns its state unchanged."""
    from repro.netsim_jax import sim
    real = sim._step_core

    def step(cfg, prog, st, *, kernel_safe=False):
        _, done = real(cfg, prog, st, kernel_safe=kernel_safe)
        return st, jnp.zeros_like(done)
    monkeypatch.setattr(sim, "_step_core", step)


def altered_drain_answer(monkeypatch):
    """One tile's latency sum off by one where the drain produces it."""
    from repro.netsim_jax import sim
    real = sim.run_until_drained_traced

    def drain(*a, **kw):
        st, steps, tr = real(*a, **kw)
        return st._replace(lat_sum=st.lat_sum.at[0, 0].add(1)), steps, tr
    monkeypatch.setattr(sim, "run_until_drained_traced", drain)


def _half(x):
    """Every other row of the batch left out: each odd row reads as the
    row before it."""
    n = x.shape[0] // 2
    return x.at[1:2 * n:2].set(x[0:2 * n:2])


def half_service_batch(monkeypatch):
    from repro.sim_service import streaming
    real = streaming._block_jit

    def block_jit(key, cycles):
        f = real(key, cycles)
        return lambda progs, states: jax.tree_util.tree_map(
            _half, f(progs, states))
    monkeypatch.setattr(streaming, "_block_jit", block_jit)


def altered_service_answer(monkeypatch):
    from repro.sim_service import streaming
    real = streaming._reduce_jit

    def reduce_jit(ntiles, measure):
        f = real(ntiles, measure)

        def red(*a):
            st = f(*a)
            return st._replace(lat_p95=st.lat_p95.at[0].add(1.0))
        return red
    monkeypatch.setattr(streaming, "_reduce_jit", reduce_jit)


CASES = [
    (DRAIN, 1.0, None), (DRAIN, 1.0, frozen_step),
    (DRAIN, 1.0, altered_drain_answer),
    (SERVICE, 1.0, None), (SERVICE, 1.0, half_service_batch),
    (SERVICE, 1.0, altered_service_answer),
]


@pytest.mark.parametrize(
    "cell,seconds,fault", CASES,
    ids=[f"{c.split('.')[1]}-{f.__name__ if f else 'sound'}"
         for c, _, f in CASES])
def test_correct_comes_out_false_for_each_fault(checkout, monkeypatch, cell,
                                                seconds, fault):
    if fault is not None:
        fault(monkeypatch)
    r = run(checkout, cell, seconds)
    assert r["attempted"] > 0
    assert r["correct"] is (fault is None), r["checks"]
    # set-up warmed every shape: nothing compiles inside the window
    assert r["checks"]["compiles_in_window"]["value"] == 0


def test_a_run_without_a_tpu_exits_2_and_prints_no_result(tmp_path):
    import subprocess
    import sys
    p = subprocess.run(
        [sys.executable, str(harness.ROOT / "bench" / "run.py"), "--workload",
         DRAIN, "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=300,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin",
             "HOME": str(tmp_path), "TMPDIR": str(tmp_path)})
    assert p.returncode == 2, p.stderr[-2000:]
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_a_traced_run_on_the_cpu_reports_no_device_metric(checkout):
    r = harness.run(DRAIN, 3, 1.0, True, root=checkout, require_tpu=False,
                    shrink=shrink, workers=1)
    bench = json.loads((checkout / "BENCHMARK.json").read_text())
    device = {m["name"] for m in bench["per_layer"]
              if m["source"] == "device_trace"}
    assert r["metrics"] and not device & set(r["metrics"])
    assert "busy_s" not in r["device"]
    assert r["checks"]["compiles_in_window"]["value"] == 0


def test_an_executable_built_in_the_window_makes_the_run_incorrect(
        checkout, monkeypatch):
    """A set-up that warms nothing leaves the drain program to compile
    inside the window."""
    from bench import entry
    real = entry.load

    def unwarmed(name):
        cls = real(name)
        return type("Unwarmed", (cls,), {"setup": lambda self: None})
    monkeypatch.setattr(entry, "load", unwarmed)
    r = harness.run(DRAIN, 5, 1.0, False, root=checkout, require_tpu=False,
                    shrink=shrink, workers=1)
    assert r["checks"]["compiles_in_window"]["value"] > 0
    assert r["correct"] is False
