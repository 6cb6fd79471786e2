"""A whole run on the CPU at SF 0.01, past the harness's look for a chip,
of the power cell and of the serving deployment under its open-loop mix
(no cell of ``BENCHMARK.json`` yet): sound, it reads correct; with the
timed path broken underneath, it reads not correct.  The faults a
one-chip OLAP run can have: an answer altered where it is produced, and
(when serving) half of a coalesced batch left out, its lanes given the
answers of the other half."""
from __future__ import annotations

import time

import numpy as np
import pytest

from bench import run

SF, SEED = 0.01, 2**31 + 78
SERVING = {"config": "tpch_sf10_cubes_1chip", "traffic": "dashboard_overload",
           "chips": 1}


def _run(cell_name: str) -> dict:
    import jax

    cell, config = (run.resolve(SERVING) if cell_name == "serving"
                    else run.load_cell(cell_name))
    config = dict(config, scale_factor=SF)
    if "engine" in config:  # a window long enough to coalesce on the CPU
        config["engine"] = dict(config["engine"], max_batch=4,
                                max_wait_us=50_000)
        cell["mix"] = dict(cell["mix"], rate_qps=40, lane_buckets=[2, 4])
    return run.run_cell(cell_name, cell, config, seed=SEED, seconds=1.5,
                        trace=False, devices=jax.devices()[:1],
                        t_start=time.perf_counter())


def _alter_answers(monkeypatch):
    """Every scalar tier-2 answer has its largest value moved by 0.1%."""
    from repro.tpch import driver as drv

    execute = drv.PreparedQuery.execute

    def altered(self, params=None):
        ans = execute(self, params)
        if ans.tier == 2 and isinstance(ans.value, np.ndarray):
            v = np.array(ans.value)
            v.flat[np.argmax(np.abs(v))] *= 1 + 1e-3
            ans.value = v
        return ans

    monkeypatch.setattr(drv.PreparedQuery, "execute", altered)


def _half_batch(monkeypatch):
    """A batch computes only its first half of lanes; the other lanes get
    the answers of the first half."""
    from repro.tpch import driver as drv

    execute_batch = drv.PreparedQuery.execute_batch

    def halved(self, param_table, pad_to=None):
        rows = list(param_table)
        half = max(len(rows) // 2, 1)
        kept = rows[:half] + [rows[i % half] for i in range(half, len(rows))]
        return execute_batch(self, kept, pad_to=pad_to)

    monkeypatch.setattr(drv.PreparedQuery, "execute_batch", halved)


@pytest.mark.parametrize("cell_name", ["power_sf10", "serving"])
def test_sound_run_is_correct(cell_name):
    res = _run(cell_name)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert res["metrics"]["setup_s"]["value"] > 0


@pytest.mark.parametrize("cell_name,fault", [
    ("power_sf10", _alter_answers),
    ("serving", _alter_answers),
    ("serving", _half_batch),
], ids=["power-altered", "serve-altered", "serve-half-batch"])
def test_broken_timed_path_is_not_correct(monkeypatch, cell_name, fault):
    fault(monkeypatch)
    res = _run(cell_name)
    assert not res["correct"] and res["failed"] > 0
    assert res["checks"]["agg_gap"]["value"] > res["checks"]["agg_gap"]["limit"]
