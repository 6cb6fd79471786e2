"""The yardstick's arithmetic: scan bytes for the roofline, the peaks
table, the exact percentile, and the traffic's fixed work per seed."""
from __future__ import annotations

import importlib.util
import itertools
import os

import pytest

from bench import peaks
from bench.traffic import closed_loop, open_loop
from bench.traffic.common import percentile

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        f"reader_{name}", os.path.join(METRICS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("rows,width,want", [
    # 60M rows = 1,875,000 groups of 32: 12-bit words read + bitset written
    (60_000_000, 12, 1_875_000 * 12 * 4 + 1_875_000 * 4),
    (60_000_000, 4, 60_000_000 * 4 // 8 + 60_000_000 // 8),
    # 100 rows pad to 4 groups (128 rows): 4 * 6 words + 4 bitset words
    (100, 6, (4 * 6 + 4) * 4),
])
def test_scan_bytes_hand_worked(rows, width, want):
    assert _reader("scan_filter_roofline_pct").scan_bytes(rows, width) == want


def test_peaks_known_and_unknown():
    assert peaks.for_kind("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(peaks.UnknownDeviceError):
        peaks.for_kind("TPU v99")


def test_percentile_is_an_order_statistic():
    xs = list(range(1, 201))
    assert percentile(xs, 0.5) == 101
    assert percentile(xs, 0.95) == 191
    assert percentile(list(reversed(xs)), 0.95) == 191


def test_open_loop_sends_the_same_work_for_every_seed():
    from bench import run

    mix = run.resolve({"config": "tpch_sf10_cubes_1chip",
                       "traffic": "dashboard_overload", "chips": 1})[0]["mix"]
    seen = []
    for seed in (1, 2**31 + 5):
        reqs = open_loop.requests(mix, seed, 40)
        assert [t for t, _, _ in reqs] == sorted(t for t, _, _ in reqs)
        assert 0 <= reqs[0][0] and reqs[-1][0] < 40
        seen.append(sorted(n for _, n, _ in reqs))
        gaps = sorted(round(b - a, 9) for (a, _, _), (b, _, _)
                      in zip(reqs, reqs[1:]))
        seen.append(len(gaps))
    assert seen[0] == seen[2] and seen[1] == seen[3]
    assert len(seen[0]) == round(mix["rate_qps"] * 40)


def test_closed_loop_rounds_are_permutations():
    from bench import run

    mix = run.load_cell("power_sf10")[0]["mix"]
    stream = closed_loop.requests(mix, 2**31 + 9)
    n = len(mix["queries"])
    for _ in range(5):
        names = [q for q, _ in itertools.islice(stream, n)]
        assert sorted(names) == sorted(mix["queries"])
