"""The trace reduction: busy union, idle gaps and their labels."""
import pytest

from bench import trace

MS = 1_000_000


def _events(ops, host=()):
    return {"devices": {"/device:TPU:0": [list(o) for o in ops]},
            "host": [[trace.WINDOW, 0, 10 * MS]] + [list(h) for h in host]}


def test_busy_is_the_union_of_overlapping_ops_clipped_to_the_window():
    ops = [("a", 1 * MS, 2 * MS), ("b", 2 * MS, 2 * MS),   # 1..4 ms
           ("c", 3 * MS, 1 * MS),                          # inside a/b
           ("d", 9 * MS, 3 * MS)]                          # clipped at 10
    s = trace.summarize(_events(ops))
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == [pytest.approx(0.004)]
    assert s.idle_share == pytest.approx(0.6)
    assert dict(s.device_ops)["d"] == pytest.approx(0.001)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    ops = [("a", 2 * MS, 1 * MS), ("b", 8 * MS, 1 * MS)]
    host = [("bench:sweep", 0, 10 * MS), ("bench:build", 4 * MS, 3 * MS)]
    s = trace.summarize(_events(ops, host))
    # gaps: 0-2 ms (sweep), 3-8 ms (build covers its midpoint), 9-10 ms
    assert s.idle_gaps[0] == ("build", pytest.approx(0.005))
    assert sorted(g for _, g in s.idle_gaps) == [
        pytest.approx(0.001), pytest.approx(0.002), pytest.approx(0.005)]


def test_busy_is_averaged_over_devices():
    ev = _events([("a", 0, 10 * MS)])
    ev["devices"]["/device:TPU:1"] = [["a", 0, 5 * MS]]
    s = trace.summarize(ev)
    assert s.busy_s == [pytest.approx(0.010), pytest.approx(0.005)]
    assert s.idle_share == pytest.approx(0.25)


def test_a_trace_without_device_planes_gives_no_summary():
    assert trace.summarize({"devices": {}, "host": []}) is None


def _naive_busy_ns(ops, w0, w1):
    """Busy nanoseconds by a sweep over sorted interval edges."""
    edges = sorted([(max(s, w0), 1) for _, s, d in ops if s + d > w0 and s < w1]
                   + [(min(s + d, w1), -1) for _, s, d in ops
                      if s + d > w0 and s < w1])
    busy, depth, last = 0, 0, None
    for t, step in edges:
        if depth > 0:
            busy += t - last
        depth += step
        last = t
    return busy


def test_busy_matches_a_plain_sweep_on_random_ops():
    """Nested and overlapping ops, as a device's op line holds them
    (loop bodies inside their loop), some past the window's edges."""
    import numpy as np
    rng = np.random.default_rng(3)
    starts = rng.integers(-MS, 11 * MS, 500)
    ops = [(f"%fusion.{i % 7}", int(st), int(d))
           for i, (st, d) in enumerate(zip(starts, rng.integers(1, 40_000, 500)))]
    ops.append(("%while.1", 2 * MS, 3 * MS))
    s = trace.summarize(_events(ops))
    assert s.busy_s[0] == pytest.approx(_naive_busy_ns(ops, 0, 10 * MS) * 1e-9,
                                        rel=1e-12)
    assert "%while.1" not in dict(s.device_ops)
