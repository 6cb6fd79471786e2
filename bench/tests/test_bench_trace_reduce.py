"""The trace reduction: busy/idle union, per-operation time and the
attribution of idle gaps, on hand-made planes and on a small trace
recorded on a TPU v5e: two rounds of q1, q6, q14_promo and q18 at SF 0.05
under a ``bench.window`` span, with the Python tracer's events removed
from the file to keep it small (the device and host-runtime events are as
recorded)."""
from __future__ import annotations

import importlib.util
import os

import pytest

from bench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small_trace.xplane.pb")
METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _planes(device_ops, host_lines):
    return [("/host:CPU", host_lines),
            ("/device:TPU:0", [("XLA Modules", [("jit_run", 0, 10**9)]),
                               ("XLA Ops", device_ops)])]


def test_union_and_gaps():
    assert tr.union([(5, 9), (0, 2), (1, 3), (8, 12)]) == [[0, 3], [5, 12]]
    assert tr.gaps([[0, 3], [5, 12]], 1, 20) == [(3, 5), (12, 20)]
    assert tr.gaps([[0, 30]], 1, 20) == []


def test_busy_ops_and_idle_attribution():
    ms = 1_000_000
    ops = [("%a = f32[] fusion(x)", 10 * ms, 20 * ms),   # 10..30
           ("%a = f32[] fusion(x)", 25 * ms, 10 * ms),   # 25..35 overlaps
           ("%b = u32[] custom-call(y)", 70 * ms, 10 * ms),  # 70..80
           ("%c = f32[] copy(z)", 95 * ms, 10 * ms)]     # 95..105, clipped
    host = [("main", [(tr.WINDOW_SPAN, 0, 100 * ms),
                      ("bench.execute", 5 * ms, 80 * ms),   # 5..85
                      ("device_get", 36 * ms, 40 * ms)]),   # 36..76
            ("futex-default", [("wait", 0, 100 * ms)])]
    red = tr.reduce_planes(_planes(ops, host))
    assert red["window_s"] == pytest.approx(0.1)
    # busy: 10..35, 70..80, 95..100 = 40 ms
    assert red["busy_s"] == pytest.approx(0.040)
    assert tr.idle_pct(red) == pytest.approx(60.0)
    assert red["ops"]["%a = f32[] fusion(x)"] == (2, pytest.approx(0.030))
    assert red["ops"]["%c = f32[] copy(z)"] == (1, pytest.approx(0.005))
    # gaps: 35..70 lies inside bench.execute (device_get starts too late
    # to cover all of it); 0..10 and 80..95 lie inside no span but the
    # window's
    idle = red["idle_gaps"]
    assert idle["bench.execute"] == pytest.approx(0.035)
    assert idle[tr.NO_SPAN] == pytest.approx(0.010 + 0.015)
    assert "wait" not in idle  # threads that only wait explain nothing
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "%a = f32[] fusion(x)"
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_small_gaps_are_summed_apart():
    us = 1_000
    ops = [("%a", 0, 100 * us), ("%a", 110 * us, 100 * us)]
    host = [("main", [(tr.WINDOW_SPAN, 0, 210 * us),
                      ("bench.execute", 0, 210 * us)])]
    red = tr.reduce_planes(_planes(ops, host))
    assert red["idle_gaps"] == {tr.SMALL_GAPS: pytest.approx(10e-6)}


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_planes(_planes([], [("main", [("x", 0, 5)])]))


def test_recorded_chip_trace():
    red = tr.reduce(DATA)
    assert red["devices"] == ["/device:TPU:0"]
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(red["idle_gaps"].values()) == pytest.approx(
        red["window_s"] - red["busy_s"])
    spec = importlib.util.spec_from_file_location(
        "reader_scan", os.path.join(METRICS, "scan_filter_roofline_pct.py"))
    scan = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(scan)
    kernel = {k: v for k, v in red["ops"].items() if scan.is_kernel(k)}
    # two rounds of q1 (ship date), q6 (ship date, discount, quantity),
    # q14_promo (ship date) and q18 (none)
    assert sum(n for n, _ in kernel.values()) == 2 * (1 + 3 + 1)
    assert all(t > 0 for _, t in kernel.values())
    assert sum(t for _, t in kernel.values()) < red["busy_s"]
    bd = tr.breakdown(red)
    assert bd["device_ops"] and bd["idle_gaps"]
    assert bd["device_ops"][0][1] == max(t for _, t in bd["device_ops"])
