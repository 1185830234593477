"""The trace reduction, on made-up intervals and on a small trace
recorded on a TPU v5e."""
from pathlib import Path

import pytest

import trace_reduce as tr

RECORDED = Path(__file__).parent / "data" / "cnn8_tpu_v5e.xplane.pb"


def test_union_gaps_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]
    assert tr.clip([(0, 4), (6, 12)], 2, 10) == [(2, 4), (6, 10)]


def test_reduce_busy_ops_gaps_and_spans():
    ms = 10**6
    host = [(0, 100 * ms, "bench.window"),
            (10 * ms, 20 * ms, "bench.prepare"),
            (20 * ms, 22 * ms, "bench.dispatch"),
            (22 * ms, 60 * ms, "bench.wait"),
            (60 * ms, 70 * ms, "bench.fetch"),
            (150 * ms, 160 * ms, "bench.fetch")]       # after the window
    dev = {"/device:TPU:0": [(25 * ms, 40 * ms, "fusion.1"),
                             (35 * ms, 55 * ms, "convolution.2"),
                             (90 * ms, 120 * ms, "fusion.1")]}
    s = tr.reduce(host, dev)
    assert s.window_s == pytest.approx(0.1)
    assert s.busy_s == pytest.approx(0.030 + 0.010)
    assert s.idle_share == pytest.approx(0.6)
    assert s.device_ops == [("fusion.1", pytest.approx(0.025)),
                            ("convolution.2", pytest.approx(0.020))]
    # gaps: [0, 25) ms has its middle in bench.prepare; [55, 90) ms has
    # its middle in no span
    assert s.idle_gaps == [("sleep", pytest.approx(0.035)),
                           ("bench.prepare", pytest.approx(0.025))]
    assert s.spans["bench.fetch"] == (1, pytest.approx(0.010))
    assert s.span_count("bench.dispatch") == 1
    assert s.breakdown()["idle_gaps"][0][0] == "sleep"


def test_reduce_without_a_device_reads_no_idle_share():
    s = tr.reduce([(0, 10, "bench.window")], {})
    assert s.chips == 0 and s.idle_share is None


def test_reduce_needs_the_window_span():
    with pytest.raises(ValueError):
        tr.reduce([(0, 10, "bench.wait")], {})


def test_recorded_trace():
    """Five cnn8 batches (tiers 1-8) served through the window on one
    TPU v5e, traced from the window's start."""
    s = tr.reduce_file(str(RECORDED))
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.022543388)
    assert s.busy_s == pytest.approx(0.002668311)
    assert s.idle_share == pytest.approx(1 - 0.002668311 / 0.022543388)
    assert s.span_count("bench.dispatch") == 5
    assert s.span_count("bench.wait") == 5
    kinds = dict(s.device_ops)
    assert set(kinds) >= {"copy", "fusion:kCustom", "reshape"}
    assert sum(kinds.values()) >= s.busy_s * 0.99
    assert s.idle_gaps[0] == ("sleep", pytest.approx(0.004972339))
    assert {n for n, _ in s.idle_gaps} <= {"sleep", "bench.pop",
                                           "bench.prepare", "bench.dispatch",
                                           "bench.wait", "bench.fetch"}


def test_recorded_device_ops_run_inside_their_batches_spans():
    """Host spans and device ops share one clock: every device op starts
    after some batch's dispatch began and ends before its wait ended."""
    host, dev = tr.read_xplane(str(RECORDED))
    batches = sorted((s, e) for s, e, n in host if n == "bench.dispatch")
    waits = sorted(e for s, e, n in host if n == "bench.wait")
    windows = list(zip([s for s, _ in batches], waits))
    for s, e, _ in dev["/device:TPU:0"]:
        assert any(lo <= s and e <= hi for lo, hi in windows)
