"""The benchmark's output check at SF 0.01 on the CPU: the plain reference
agrees with the program's answers for the query sets of the power cell
and of the serving deployment, and the comparison fails a perturbed
aggregate, a dropped top-k row, a set overflow flag and the
lower-precision control."""
from __future__ import annotations

import numpy as np
import pytest

from bench import control, run
from bench.reference import compare, tpch_data
from bench.reference.tpch import Reference
from bench.traffic.common import draw

SF, SEED = 0.01, 2**31 + 78  # a seed whose Q18 has two rows at SF 0.01
LIMITS = run.load_cell("power_sf10")[1]["limits"]


@pytest.fixture(scope="module")
def driver():
    import jax

    from repro.core import Cluster
    from repro.tpch.driver import TPCHDriver

    d = TPCHDriver(SF, cluster=Cluster(devices=jax.devices()[:1]), seed=SEED,
                   storage="packed")
    d.build_cubes()
    return d


@pytest.fixture(scope="module")
def tables():
    return tpch_data.generate(SF, 1, SEED)


@pytest.fixture(scope="module")
def ref(tables):
    return Reference(tables)


def _prepared(driver, name):
    from bench.traffic.open_loop import _query
    from repro.tpch import queries as tq

    if name in ("q4", "q18"):
        return driver.prepare(name)
    return driver.prepare(_query(tq, name))


def _answers(driver, names):
    """(name, binding, QueryAnswer) for each query of a cell's set: three
    §2.4 draws of each parameterized query, the defaults of the rest."""
    rng = np.random.default_rng(5)
    out = []
    for name in names:
        prep = _prepared(driver, name)
        params = name in ("q1", "q6", "q14_promo")
        for b in ([draw(name, rng) for _ in range(3)] if params else [None]):
            ans = prep.answer_tier1(prep.binding(b))
            out.append((name, b, ans if ans is not None else prep.execute(b)))
    return out


POWER = ["q1", "q6", "q14_promo", "q4", "q18"]
SERVE = ["q6", "q14_promo", "q1_cube", "revenue_by_shipmonth",
         "orders_by_priority", "q1_offedge"]


@pytest.mark.parametrize("names", [POWER, SERVE], ids=["power", "serve"])
def test_true_answers_pass(driver, ref, names):
    for name, b, ans in _answers(driver, names):
        nums = compare.check_answer(ans.value, ans.overflow,
                                    ref.answer(name, b))
        assert compare.passes(nums, LIMITS), (name, b, nums)
    tiers = {name: ans.tier for name, _, ans in _answers(driver, names)}
    if names is SERVE:
        assert tiers["q1_cube"] == 1 and tiers["q1_offedge"] == 2


def test_coalesced_lanes_pass(driver, ref):
    rng = np.random.default_rng(9)
    for name in ("q6", "q14_promo"):
        lanes = [draw(name, rng) for _ in range(4)]
        ans = _prepared(driver, name).execute_batch(lanes)
        for i, b in enumerate(lanes):
            nums = compare.check_answer(np.asarray(ans.value)[i],
                                        ans.overflow[i], ref.answer(name, b))
            assert compare.passes(nums, LIMITS), (name, b, nums)


def test_reference_matches_program_oracles(tables, ref, driver):
    """The grouped evaluation of the parameterized queries selects the
    same rows as the row-wise float64 oracles of the program."""
    from repro.tpch import queries as tq
    from repro.tpch.reference import ALL

    rng = np.random.default_rng(3)
    for name in ("q1", "q6", "q14_promo"):
        for _ in range(4):
            b = draw(name, rng)
            p = tq.oracle_params(name, b)
            want = ALL["q14" if name == "q14_promo" else name](
                driver.tables, p=p)
            want = want[1] if name == "q14_promo" else want
            np.testing.assert_allclose(
                np.asarray(ref.answer(name, b)).reshape(np.shape(want)),
                want, rtol=1e-7)  # its Q14 rounds each row to float32
    np.testing.assert_array_equal(ref.answer("q4").ravel(),
                                  ALL["q4"](driver.tables))
    v, k = ref.answer("q18")
    ov, ok = ALL["q18"](driver.tables)
    np.testing.assert_array_equal(k, ok)
    np.testing.assert_array_equal(v, ov)


@pytest.mark.parametrize("nodes", [1, 2])
def test_generator_copy_matches_program(nodes):
    from repro.tpch import dbgen

    mine = tpch_data.generate(SF, nodes, SEED,
                              tables=tpch_data.PARTITIONED_TABLES)
    theirs = dbgen.generate(SF, nodes, SEED)
    for t, cols in mine.items():
        for c, v in cols.items():
            w = np.asarray(theirs[t].columns[c])
            assert v.dtype == w.dtype and np.array_equal(v, w), (t, c)


def test_perturbed_aggregate_fails(driver, ref):
    name, b, ans = _answers(driver, ["q1"])[0]
    value = np.array(ans.value, np.float64)
    value.flat[np.argmax(np.abs(value))] *= 1 + 1e-3
    nums = compare.check_answer(value, False, ref.answer(name, b))
    assert nums["agg_gap"] > LIMITS["agg_gap"]
    assert not compare.passes(nums, LIMITS)


def test_dropped_topk_row_fails(driver, ref):
    _, _, ans = _answers(driver, ["q18"])[0]
    value = {k: np.array(v) for k, v in ans.value.items()}
    n = int(value["valid"].sum())
    assert n > 1
    for k in ("values", "keys"):  # drop rank 0; the rest move up one
        value[k][:n - 1] = value[k][1:n]
    value["valid"][n - 1] = False
    nums = compare.check_answer(value, False, ref.answer("q18"))
    assert nums["topk_miss"] > 0 and not compare.passes(nums, LIMITS)


def test_overflow_fails(driver, ref):
    name, b, ans = _answers(driver, ["q14_promo"])[0]
    nums = compare.check_answer(ans.value, True, ref.answer(name, b))
    assert nums["overflow"] == 1 and not compare.passes(nums, LIMITS)


@pytest.mark.parametrize("cell", ["power_sf10", "serving"])
def test_control_is_not_correct(tables, cell):
    """The reference computed on bfloat16-rounded inputs fails the check
    on the requests of a window of the power cell, and of the serving
    deployment's open-loop mix."""
    import ml_dtypes

    c, config = (run.load_cell(cell) if cell != "serving" else run.resolve(
        {"config": "tpch_sf10_cubes_1chip", "traffic": "dashboard_overload",
         "chips": 1}))
    reqs = control.window_requests(c, SEED, 40)
    r = control.readings(tables, reqs, config["limits"], ml_dtypes.bfloat16)
    assert not r["passed"]
    assert r["agg_gap"] > 3 * config["limits"]["agg_gap"]
