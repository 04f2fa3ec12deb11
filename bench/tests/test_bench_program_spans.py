"""The per-layer metrics that read the program's own spans.

A traced run of each cell, at the size ``test_bench_faults.shrink``
gives it, on the CPU, reports every such metric as a number and stays
correct.  Each reader counts only the spans inside the window's
benchmark spans, and gives ``None`` when the program's ring let go of
spans of the window.
"""
import time

import pytest

from bench import harness, program_spans
from bench.common import RunView, Spans
from test_bench_faults import DRAIN, SERVICE, checkout, shrink  # noqa: F401

READERS = {
    DRAIN: ["telemetry_ms_per_job.drain"],
    SERVICE: ["tick_host_share.service", "block_wait_ms.service",
              "block_readback_ms.service", "form_ms_per_batch.service",
              "submit_ms.service"],
}


@pytest.mark.parametrize("cell", [DRAIN, SERVICE],
                         ids=lambda c: c.split(".")[1])
def test_a_traced_run_reports_each_program_span_metric(checkout, cell):
    r = harness.run(cell, 2**31 + 77, 1.0, True, root=checkout,
                    require_tpu=False, shrink=shrink, workers=1)
    assert r["correct"] is True, r["checks"]
    for name in READERS[cell]:
        value = r["metrics"][name]["value"]
        assert isinstance(value, float) and value > 0, (name, value)
    if cell == SERVICE:
        assert r["metrics"]["tick_host_share.service"]["value"] < 100


def _view(recorder_capacity, monkeypatch):
    """A window with one of each benchmark span, each holding the
    program spans its reader reads, after a set-up span outside it."""
    from repro import tracing
    monkeypatch.setattr(tracing, "RECORDER",
                        tracing.Recorder(recorder_capacity))
    with tracing.span("sim_service.submit"):        # set-up: left out
        time.sleep(0.05)
    spans = Spans()
    with spans("submit"):
        with tracing.span("sim_service.submit", rid=0):
            pass
    with spans("tick"):
        with tracing.span("sim_service.tick"):
            with tracing.span("sim_service.batch.form", batch=0, width=1):
                pass
            with tracing.span("sim_service.block", batch=0):
                with tracing.span("sim_service.block.wait"):
                    time.sleep(0.002)
            with tracing.span("sim_service.batch.finalize", batch=0):
                pass
    with spans("telemetry"):
        with tracing.span("mesh.telemetry.of"):
            pass
    return RunView(cfg={}, peaks={}, spans=spans, summary=None,
                   counters={"jobs": 1})


@pytest.mark.parametrize("name", READERS[DRAIN] + READERS[SERVICE])
def test_a_reader_reads_the_window_and_none_once_spans_were_dropped(
        monkeypatch, name):
    read = harness.reader(name)
    value = read(_view(64, monkeypatch))
    assert isinstance(value, float) and value >= 0
    # the ring holds 3 of the window's 8 spans and of the set-up's one
    assert read(_view(3, monkeypatch)) is None


def test_spans_outside_the_window_are_left_out(monkeypatch):
    view = _view(64, monkeypatch)
    submits = [r for r in program_spans.window_spans(view)
               if r.name == "sim_service.submit"]
    assert [r.attrs for r in submits] == [{"rid": 0}]
    assert program_spans.mean_ms(view, "sim_service.submit") < 50
    assert program_spans.mean_ms(view, "sim_service.block", own=True) \
        < program_spans.mean_ms(view, "sim_service.block")
