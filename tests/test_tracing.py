"""The program's host spans (``repro.tracing``) and device scopes.

Unit tests of the recorder (nesting, parents, self time, attributes, the
ring's bound); the spans one service request leaves, checked against the
server's own counters; the same spans in a CPU profile, nested as
recorded; and the ``step/...`` and ``drain/...`` scopes in the compiled
programs' op names.
"""
import re
import threading
from pathlib import Path

import jax
import pytest

from repro import tracing
from repro.mesh.config import MeshConfig
from repro.netsim_jax import sim
from repro.netsim_jax.traffic import make_traffic
from repro.sim_service import SimRequest, SimService

SERVICE = ("sim_service.submit", "sim_service.tick", "sim_service.batch.form",
           "sim_service.block", "sim_service.block.wait",
           "sim_service.batch.finalize")


# -- the recorder ---------------------------------------------------------

def test_spans_nest_with_parents_and_attrs():
    rec = tracing.Recorder()
    with rec.span("a", rid=3):
        with rec.span("b", batch=1):
            pass
        with rec.span("c"):
            with rec.span("d"):
                pass
    by = {s.name: s for s in rec.spans()}
    assert [s.name for s in rec.spans()] == ["b", "d", "c", "a"]
    assert by["a"].parent is None
    assert by["b"].parent == by["c"].parent == by["a"].index
    assert by["d"].parent == by["c"].index
    assert by["a"].attrs == {"rid": 3} and by["b"].attrs == {"batch": 1}
    assert by["c"].attrs == {}
    for child in "bcd":
        parent = next(s for s in rec.spans()
                      if s.index == by[child].parent)
        assert parent.start <= by[child].start <= by[child].end \
            <= parent.end


def test_self_seconds_is_a_span_less_its_children():
    mk = lambda i, s, e, p: tracing.Span(i, str(i), s, e, p, {})  # noqa
    recs = [mk(1, 1.0, 2.0, 0), mk(2, 2.5, 3.0, 0), mk(3, 1.2, 1.5, 1),
            mk(0, 0.0, 10.0, None)]
    own = tracing.self_seconds(recs)
    assert own == pytest.approx({0: 8.5, 1: 0.7, 2: 0.5, 3: 0.3})
    # a child outside the records given takes nothing off its parent
    assert tracing.self_seconds(recs[:3])[1] == pytest.approx(0.7)


def test_a_span_is_recorded_when_its_block_raises():
    rec = tracing.Recorder()
    with pytest.raises(KeyError):
        with rec.span("outer"):
            with rec.span("inner"):
                raise KeyError("x")
    assert [s.name for s in rec.spans()] == ["inner", "outer"]
    with rec.span("after"):
        pass
    assert rec.spans()[-1].parent is None


def test_the_ring_keeps_the_newest_and_counts_the_dropped():
    rec = tracing.Recorder(capacity=4)
    for i in range(10):
        with rec.span(f"s{i}"):
            pass
    assert [s.name for s in rec.spans()] == ["s6", "s7", "s8", "s9"]
    assert rec.dropped == 6
    assert tracing.RECORDER._ring.maxlen == tracing.CAPACITY == 65_536


def test_each_thread_has_its_own_stack():
    rec = tracing.Recorder()
    go = threading.Event()

    def worker():
        go.wait(10)
        with rec.span("other"):
            pass
    t = threading.Thread(target=worker)
    t.start()
    with rec.span("main"):
        go.set()
        t.join(10)
    assert not t.is_alive()
    by = {s.name: s for s in rec.spans()}
    assert by["other"].parent is None and by["main"].parent is None


# -- the service's spans ----------------------------------------------------

def _request():
    return SimRequest(cfg=MeshConfig(nx=4, ny=4), pattern="uniform",
                      load=0.2, seed=5, warmup=20, measure=40, drain=40,
                      check_every=20)


def _new_spans(before):
    seen = {s.index for s in before}
    return [s for s in tracing.spans() if s.index not in seen]


def test_one_request_leaves_its_spans_and_they_match_the_counters():
    svc = SimService(max_batch=4)
    before = tracing.spans()
    resp = svc.run_one(_request())
    recs = _new_spans(before)
    names = [s.name for s in recs]
    m = svc.metrics
    assert names.count("sim_service.submit") == m.submitted == 1
    assert names.count("sim_service.tick") == m.ticks
    assert names.count("sim_service.batch.form") == m.batches == 1
    assert names.count("sim_service.batch.finalize") == m.batches
    assert names.count("sim_service.block") == m.blocks == 5
    assert names.count("sim_service.block.wait") == m.blocks

    by_index = {s.index: s for s in recs}

    def parent(s):
        return by_index[s.parent]
    for w in (s for s in recs if s.name == "sim_service.block.wait"):
        block, tick = parent(w), parent(parent(w))
        assert (block.name, tick.name) == ("sim_service.block",
                                           "sim_service.tick")
        assert tick.start <= block.start <= w.start <= w.end <= block.end \
            <= tick.end
    for name in ("sim_service.batch.form", "sim_service.batch.finalize"):
        assert all(parent(s).name == "sim_service.tick"
                   for s in recs if s.name == name)
    batched = [s for s in recs if "batch" in s.attrs]
    assert {s.name for s in batched} == {
        "sim_service.batch.form", "sim_service.block",
        "sim_service.batch.finalize"}
    assert {s.attrs["batch"] for s in batched} == {resp.metrics["batch"]}
    form = next(s for s in recs if s.name == "sim_service.batch.form")
    assert form.attrs["width"] == resp.metrics["batch_width"] == 1
    submit = next(s for s in recs if s.name == "sim_service.submit")
    assert submit.attrs == {"rid": resp.rid}


def test_a_cpu_profile_holds_the_spans_nested_as_recorded(tmp_path):
    from jax.profiler import ProfileData
    svc = SimService(max_batch=4)
    svc.run_one(_request())                    # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        resp = svc.run_one(_request())
    finally:
        jax.profiler.stop_trace()
    path = sorted(Path(tmp_path).glob("**/*.xplane.pb"))[-1]
    events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
               dict(e.stats))
              for plane in ProfileData.from_file(str(path)).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name in SERVICE]
    names = [e[0] for e in events]
    assert set(names) == set(SERVICE)
    assert names.count("sim_service.block") == 5
    assert names.count("sim_service.block.wait") == 5

    def inside(inner, outer_name):
        return any(n == outer_name and s <= inner[1] and inner[2] <= e
                   for n, s, e, _ in events)
    for ev in events:
        if ev[0] == "sim_service.block.wait":
            assert inside(ev, "sim_service.block")
        if ev[0] in ("sim_service.block", "sim_service.batch.form",
                     "sim_service.batch.finalize"):
            assert inside(ev, "sim_service.tick")
            assert ev[3]["batch"] == resp.metrics["batch"]
        if ev[0] == "sim_service.submit":
            assert ev[3]["rid"] == resp.rid


def test_telemetry_of_is_one_span():
    from repro.mesh.telemetry import Telemetry
    jsim = sim.JaxMeshSim(MeshConfig(nx=4, ny=4).to_sim())
    jsim.load_program(make_traffic("uniform", 4, 4, 4, rate=0.5, seed=1))
    jsim.run_until_drained()
    before = tracing.spans()
    Telemetry.of(jsim)
    assert [s.name for s in _new_spans(before)] == ["mesh.telemetry.of"]


# -- device scopes ----------------------------------------------------------

STEP = ("step/stats", "step/arbitrate", "step/endpoint", "step/inject",
        "step/commit", "step/telemetry")
DRAIN = ("drain/fence", "drain/trace")


@pytest.mark.parametrize("program", ["run_until_drained_traced", "simulate"])
def test_compiled_op_names_carry_the_step_scopes(program):
    cfg = MeshConfig(nx=4, ny=4).to_sim()
    prog = sim.load_program(make_traffic("uniform", 4, 4, 8, rate=0.5,
                                         seed=1))
    st = sim.init_state(cfg)
    if program == "simulate":
        lowered = sim.simulate.lower(cfg, prog, st, 10)
        want = STEP
    else:
        lowered = sim.run_until_drained_traced.lower(cfg, prog, st, 500, 1)
        want = STEP + DRAIN
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))
    for scope in want:
        assert any(f"/{scope}/" in n for n in names), scope
