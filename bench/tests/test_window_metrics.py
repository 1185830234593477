"""What the window records for the metrics and the sweep: batches launched
inside the trace, queue growth, the step watch, and ``mfu`` read from
the traced run."""
from types import SimpleNamespace

import numpy as np
import pytest

import cells
import window


def _win(**kw):
    base = dict(seconds=10.0, sizes=np.ones(4, np.int64),
                latency_s=np.full(4, np.inf), wait_s=np.full(4, np.inf),
                lateness_s=np.zeros(4), tier_of=np.zeros(4, np.int64),
                batches=[], kept={}, due=4, queued_at_close=0)
    base.update(kw)
    return window.WindowResult(**base)


def test_traced_images_count_batches_launched_after_the_trace_began():
    batches = [(64, 60, 6.9), (64, 64, 7.0), (32, 20, 8.5)]
    assert _win(batches=batches).traced_images == 0
    assert _win(batches=batches, traced_from=7.0).traced_images == 84
    assert _win(batches=batches).batch_fill == pytest.approx(144 / 160)
    assert _win(batches=batches).tiers_served == (32, 64)


@pytest.mark.parametrize("depth,close,grew", [
    ([(1.0, 5), (4.0, 9), (6.0, 30)], 9, False),
    ([(1.0, 5), (4.0, 9), (6.0, 30)], 10, True),
    ([], 0, False)])
def test_queue_grew_compares_the_close_with_the_first_half(depth, close,
                                                           grew):
    assert _win(depth=depth, queued_at_close=close).queue_grew is grew


def test_step_watch_keeps_the_longest_step():
    st = window.StepWatch()
    st.begin()
    st.end(1.0, (1.0, 1.001, 1.002, 1.010, 1.011, 1.012))
    st.begin()
    st.gc_event("start", {})
    st.gc_event("stop", {})
    st.end(2.0, (2.0, 2.001, 2.002, 2.003, 2.5, 2.501))
    assert st.slow == 1 and st.slow_s == pytest.approx(0.501)
    assert st.longest["at_s"] == 2.0
    assert st.longest["fetch_s"] == pytest.approx(0.497)
    assert st.gc_runs == 1 and st.longest["gc_s"] >= 0.0
    assert set(st.longest) >= {"thread_cpu_s", "minor_faults",
                               "involuntary_switches"}


def test_mfu_reads_useful_flops_over_device_busy_time():
    read = cells.metric_reader("mfu")
    win = _win(batches=[(64, 64, 1.0), (64, 50, 8.0)], traced_from=7.0)
    trace = SimpleNamespace(busy_s=0.5)
    run = SimpleNamespace(window=win, trace=trace, flops_per_image=2e8,
                          peaks={"bf16_flops_per_s": 2e14})
    assert read(run) == pytest.approx(50 * 2e8 / 0.5 / 2e14)
    assert read(SimpleNamespace(**{**vars(run), "trace": None})) is None
    idle = SimpleNamespace(busy_s=0.0)
    assert read(SimpleNamespace(**{**vars(run), "trace": idle})) is None
