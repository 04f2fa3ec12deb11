"""The four-chip sweep cell at a test size on the CPU, and its readers.

A traced run of ``epiphany-v-32x32.sweep-4chip``, shrunk to a 4x4 mesh
with a tile memory above the fused step's one-hot limit, runs on four
virtual CPU devices in a process of its own (this process's device
count is fixed when JAX starts): it is correct, builds nothing in the
window and reports both program-span metrics; with the step frozen, or
with its stores dropped or each written one word over, it is not
correct.  The readers of the device trace are checked on a
summary made by hand, and the span readers give ``None`` once the
program's ring let go of spans of the window.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness, patterns
from bench.common import RunView, Spans
from bench.trace import Summary
from repro.mesh.traffic import make_traffic
from test_bench_faults import checkout  # noqa: F401

SWEEP = "epiphany-v-32x32.sweep-4chip"
SPAN_READERS = ["build_ms_per_point.sweep", "host_ms_per_sweep.sweep"]


def shrink(cfg, mix):
    from repro.netsim_jax.sim import MEM_ONEHOT_MAX_WORDS
    cfg.update(nx=4, ny=4, mem_words=4 * MEM_ONEHOT_MAX_WORDS)
    mix.update(warmup=20, measure=40, drain=40)
    return cfg, mix


# run in a fresh process with four CPU devices: a sound traced run, then
# untraced ones with a step that returns its state unchanged, one that
# keeps the memory it was given, and one that writes each stored word
# into the next word of its row
RUNS = """
import json, sys
sys.path[0:0] = sys.argv[2:]
import jax.numpy as jnp
from pathlib import Path
from bench import harness
from test_bench_sweep import SWEEP, shrink

def run(trace):
    return harness.run(SWEEP, 2**31 + 41, 1.0, trace, root=Path(sys.argv[1]),
                       require_tpu=False, shrink=shrink, workers=1)

print(json.dumps(run(True)))
import jax
from repro.netsim_jax import sim
real = sim._step_core
def frozen(cfg, prog, st, *, kernel_safe=False):
    _, done = real(cfg, prog, st, kernel_safe=kernel_safe)
    return st, jnp.zeros_like(done)
def dropped(cfg, prog, st, *, kernel_safe=False):
    new, done = real(cfg, prog, st, kernel_safe=kernel_safe)
    return new._replace(mem=st.mem), done
def misplaced(cfg, prog, st, *, kernel_safe=False):
    new, done = real(cfg, prog, st, kernel_safe=kernel_safe)
    written = jnp.roll(new.mem != st.mem, 1, -1)
    return new._replace(
        mem=jnp.where(written, jnp.roll(new.mem, 1, -1), st.mem)), done
for fault in (frozen, dropped, misplaced):
    sim._step_core = fault
    jax.clear_caches()
    print(json.dumps(run(False)))
"""


def test_the_cell_on_four_devices_is_correct_and_a_frozen_step_is_not(
        checkout, tmp_path):
    """Each fault is its own run; the dropped and the misplaced stores
    leave every statistic as it was (a store's response carries no
    data), so only the tile memory tells them."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = dict(os.environ, JAX_PLATFORMS="cpu", HOME=str(tmp_path),
               TMPDIR=str(tmp_path),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "-c", RUNS, str(checkout), here,
         str(harness.ROOT), str(harness.ROOT / "src")],
        capture_output=True, text=True, timeout=600, env=env)
    assert p.returncode == 0, p.stderr[-3000:]
    sound, *faults = (json.loads(line)
                      for line in p.stdout.splitlines()[-4:])
    assert sound["device"]["count"] == 4
    assert sound["correct"] is True, sound["checks"]
    assert sound["attempted"] >= 64 and sound["failed"] == 0
    assert sound["checks"]["compiles_in_window"]["value"] == 0
    for name in SPAN_READERS:
        value = sound["metrics"][name]["value"]
        assert isinstance(value, float) and value > 0, (name, value)
    for fault in faults:
        assert fault["correct"] is False
        assert fault["checks"]["mismatched_points"]["value"] > 0
        assert fault["checks"]["compiles_in_window"]["value"] == 0


def _view(capacity, monkeypatch, summary=None, counters=None):
    """A window of one sweep holding the spans the span readers read,
    after a sweep in set-up outside it."""
    from repro import tracing
    monkeypatch.setattr(tracing, "RECORDER", tracing.Recorder(capacity))
    with tracing.span("dse.sweep"):                  # set-up: left out
        time.sleep(0.05)
    spans = Spans()
    with spans("sweep"):
        with tracing.span("dse.sweep", sweep="t"):
            with tracing.span("dse.bucket.build", points=64):
                time.sleep(0.002)
            with tracing.span("dse.bucket.wait"):
                time.sleep(0.004)
            with tracing.span("dse.bucket.pull"):
                pass
    cfg = harness.load_json(harness.ROOT / "bench" / "configs"
                            / "epiphany-v-32x32.json")
    peaks = harness.load_json(harness.BENCH / "peaks.json")["TPU v5 lite"]
    return RunView(cfg=cfg, peaks=peaks, spans=spans, summary=summary,
                   counters=counters or {})


@pytest.mark.parametrize("name", SPAN_READERS)
def test_a_span_reader_reads_the_window_and_none_once_spans_were_dropped(
        monkeypatch, name):
    read = harness.reader(name)
    value = read(_view(64, monkeypatch))
    assert isinstance(value, float) and 0 < value < 50
    # the ring holds 3 of the window's 4 spans and none of the set-up's
    assert read(_view(3, monkeypatch)) is None


def test_the_span_readers_count_what_they_name(monkeypatch):
    view = _view(64, monkeypatch)
    build = harness.reader("build_ms_per_point.sweep")(view)
    host = harness.reader("host_ms_per_sweep.sweep")(view)
    assert 2 / 64 <= build < 50 / 64
    # the sweep less its wait holds the build, not the 4 ms wait
    assert 2 <= host < 50


def test_the_device_readers_on_a_summary():
    """One chip busy 0.5 s of a 1 s window over 16,000 point-cycles:
    31.25 us per point-cycle against a least of 1,187,860 bytes read
    and written once at 819 GB/s (2.90 us)."""
    summary = Summary(window_s=1.0, busy_s=[0.5], device_ops=[],
                      idle_gaps=[])
    view = RunView(
        cfg=harness.load_json(harness.ROOT / "bench" / "configs"
                              / "epiphany-v-32x32.json"),
        peaks={"hbm_bytes_per_s": 8.19e11}, spans=Spans(), summary=summary,
        counters={"traced_point_cycles_per_chip": 16_000})
    read = {n: harness.reader(n)(view) for n in (
        "device_us_per_point_cycle.sweep", "step_roofline.sweep",
        "idle_share.sweep")}
    assert read["device_us_per_point_cycle.sweep"] == pytest.approx(31.25)
    assert read["step_roofline.sweep"] == pytest.approx(
        100 * 2 * 1_187_860 / 8.19e11 / 31.25e-6)
    assert read["idle_share.sweep"] == pytest.approx(50.0)
    view.summary = None
    assert all(harness.reader(n)(view) is None for n in read)


@pytest.mark.parametrize("rate", [0.03, 0.12])
@pytest.mark.parametrize("pattern",
                         ["uniform", "transpose", "tornado", "hotspot"])
def test_copy_gives_the_programs_generator_at_the_cells_sizes(pattern,
                                                               rate):
    length = patterns.program_length(0.12, 1000)
    got = patterns.make_traffic(pattern, 32, 32, length, rate=rate,
                                seed=2**31 + 3, mem_words=16384)
    want = make_traffic(pattern, 32, 32, length, rate=rate, seed=2**31 + 3,
                        mem_words=16384)
    assert sorted(got) == sorted(want)
    for k in want:
        assert (got[k] == want[k]).all(), k
