"""The trace reduction (bench/tracing.py) on a synthesized trace."""

from types import SimpleNamespace

import pytest

from bench import tracing
from bench.tracing import Event

MS = 1e6   # ns


def trace():
    # window 0..100 ms; device ops as one TPU line: nested (a while op and
    # the kernel inside it), one crossing the window's end, one outside it
    ops = [Event("fusion.1", 8 * MS, 20 * MS),
           Event("fusion.2", 20 * MS, 30 * MS),
           Event("while.4", 40 * MS, 70 * MS),
           Event("lloyd_update_kernel.6", 50 * MS, 60 * MS),
           Event("fusion.1", 90 * MS, 110 * MS),
           Event("late", 120 * MS, 130 * MS)]
    host = [Event("window", 0, 100 * MS),
            Event("round", 30 * MS, 70 * MS),
            Event("fetch", 32 * MS, 40 * MS),
            Event("block", 70 * MS, 95 * MS)]
    return tracing.Trace({"/device:TPU:0": ops}, host)


def test_union_and_busy():
    tr = trace()
    w = tr.window()
    assert w == (0, 100 * MS)
    assert tracing.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    # 8..30 (22 ms) + 40..70 (30) + 90..100 clipped (10)
    busy = tracing.busy_ns(tr.devices["/device:TPU:0"], w)
    assert busy == pytest.approx(62 * MS)
    assert tracing.idle_share(tr.devices["/device:TPU:0"], w) == \
        pytest.approx(0.38)


def test_kernel_time_is_summed_by_name():
    ops = trace().devices["/device:TPU:0"]
    w = (0, 100 * MS)
    hits = tracing.matching(ops, ("lloyd_update_kernel.",))
    assert [e.name for e in hits] == ["lloyd_update_kernel.6"]
    assert tracing.summed_ns(hits, w) == pytest.approx(10 * MS)
    fusions = tracing.matching(ops, ("fusion.",))
    assert tracing.summed_ns(fusions, w) == pytest.approx(32 * MS)


def test_device_event_is_named_by_its_instruction():
    ev = SimpleNamespace(
        name="%pq_quantize_kernel.1 = (f32[10,8,24576]) custom-call(...)",
        start_ns=1.0, end_ns=3.0, stats=[("device_offset_ps", "5"),
                                         ("n", 3)])
    e = tracing._device_event(ev)
    assert e.name == "pq_quantize_kernel.1" and (e.start, e.end) == (1.0, 3.0)
    assert "custom-call" in e.detail and "device_offset_ps=5" in e.detail


def test_gaps_are_labelled_by_innermost_host_span():
    tr = trace()
    w = tr.window()
    gaps = tracing.gaps(tr.devices["/device:TPU:0"], w)
    assert gaps == [(0, 8 * MS), (30 * MS, 40 * MS), (70 * MS, 90 * MS)]
    labels = tracing.label_gaps(gaps, tr.host)
    # 0..8: no span; 30..40 mid 35 -> fetch (inside round); 70..90 -> block
    assert labels == pytest.approx({"none": 0.008, "fetch": 0.010,
                                    "block": 0.020})


def test_top_ops_by_self_time_in_window():
    tr = trace()
    times = tracing.self_times(tr.devices["/device:TPU:0"], tr.window())
    # the while op's 30 ms hold the kernel's 10: 20 of its own
    assert times == pytest.approx({"fusion.1": 0.022, "fusion.2": 0.010,
                                   "while.4": 0.020,
                                   "lloyd_update_kernel.6": 0.010})
    top = tracing.top_ops(tr.devices["/device:TPU:0"], tr.window(), n=2)
    assert [k for k, _ in top] == ["fusion.1", "while.4"]


def test_window_must_be_one_span():
    tr = tracing.Trace({}, [Event("round", 0, 1)])
    with pytest.raises(ValueError):
        tr.window()
