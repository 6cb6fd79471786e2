"""The compacted semi-join probe: probe only the rows a filter keeps.

The compaction kernel (``kernels/compact.py``, interpret mode here)
against its ``ref.py`` oracle and a numpy reading of its slot order; the
probe over the compacted rows (``semijoin.probe_survivors``) against the
probe over every row, on each side of each capacity; then q14_promo's
plans, literal and parameterized, scalar and batched, on one node and on
the eight-node CPU mesh, against ``tpch/reference.py``, with the
``semijoin.probe.*`` counters and EXPLAIN's ``probe=``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Cluster, compression, engine, semijoin
from repro.kernels import compact, ref
from repro.query import C, Q
from repro.query.lower import lower
from repro.tpch import queries as tq
from repro.tpch import reference
from repro.tpch import schema as S
from repro.tpch.schema import day


def _words(mask):
    mask = np.asarray(mask)
    padded = np.zeros(-(-mask.shape[0] // 32) * 32, bool)
    padded[:mask.shape[0]] = mask
    return compression.pack_bitset(jnp.asarray(padded))


def _slots(mask, cap):
    """The survivors of ``mask`` in the kernel's slot order, read from its
    layout in numpy: the defined part of its output."""
    order = np.asarray(compact.plane_order(-(-mask.shape[0] // 32)))
    order = order[order < mask.shape[0]]
    return order[mask[order]][:cap]


MASKS = {
    "empty": lambda rng, n: np.zeros(n, bool),
    "month": lambda rng, n: rng.random(n) < 0.013,   # q14_promo's share
    "run": lambda rng, n: np.arange(n) // 1000 % 7 == 3,
    "every": lambda rng, n: np.ones(n, bool),
}


@pytest.mark.parametrize("kind", sorted(MASKS))
@pytest.mark.parametrize("cap", [1024, 6000])
def test_kernel_matches_oracle(kind, cap):
    """70,000 rows (not whole words): the survivors in slot order, the
    first ``cap`` of them."""
    mask = MASKS[kind](np.random.default_rng(cap), 70_000)
    words = _words(mask)
    offsets = compact.plane_offsets(words)
    assert int(offsets[-1]) == mask.sum()
    want = _slots(mask, cap)
    n = want.shape[0]
    got = np.asarray(compact.compact_rows(words, offsets, cap=cap,
                                          interpret=True))
    oracle = np.asarray(ref.compact_rows(words, cap))
    np.testing.assert_array_equal(got[:n], want)
    np.testing.assert_array_equal(oracle[:n], want)
    assert (oracle[n:] == -1).all()


def test_kernel_across_calls(monkeypatch):
    """Plane offsets beyond one call's SMEM share go to further calls,
    whose slots merge."""
    monkeypatch.setattr(compact, "CALL_PLANES", 32 * compact.STEP_TILES)
    mask = np.random.default_rng(5).random(600_000) < 0.2
    words = _words(mask)
    got = compact.compact_rows(words, compact.plane_offsets(words),
                               cap=1 << 17, interpret=True)
    want = _slots(mask, 1 << 17)
    np.testing.assert_array_equal(np.asarray(got)[:want.shape[0]], want)


# ---------------------------------------------------------------------------
# the probe over the compacted rows
# ---------------------------------------------------------------------------

ROWS = 200_000
CAPS = semijoin.probe_capacities(ROWS)


def test_capacities_scale_with_rows():
    assert CAPS == (4096, 16384)
    assert semijoin.probe_capacities(59_986_052) == (1 << 20, 1 << 22)
    assert semijoin.probe_capacities(15_000_000) == (1 << 18, 1 << 20)


@pytest.mark.parametrize("survivors,packed,tier", [
    (0, True, 0),
    (CAPS[0], True, 0),             # exactly at the first capacity
    (CAPS[0] + 1, True, 1),         # one above: the next capacity
    (CAPS[0] + 1, False, 1),        # the mask alone: packed here
    (CAPS[1], True, 1),
    (CAPS[1] + 1, True, semijoin.FULL_WIDTH),   # every row probed
    (ROWS, False, semijoin.FULL_WIDTH),
])
def test_probe_survivors_equals_full_width(survivors, packed, tier):
    rng = np.random.default_rng(survivors)
    mask = np.zeros(ROWS, bool)
    mask[rng.choice(ROWS, survivors, replace=False)] = True
    keys = jnp.asarray(rng.integers(0, 5000, ROWS), jnp.int32)
    target = jnp.asarray(rng.random(5000) < 0.3)

    def run(m, w):
        return semijoin.probe_survivors(
            m, w, lambda idx: keys if idx is None else keys[idx],
            lambda k: target[k])

    bits, got_tier = jax.jit(run)(jnp.asarray(mask),
                                  _words(mask) if packed else None)
    assert int(got_tier) == tier
    np.testing.assert_array_equal(np.asarray(bits),
                                  mask & np.asarray(target)[keys])


# ---------------------------------------------------------------------------
# q14_promo's plans
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def one_node_driver():
    from repro.tpch.driver import TPCHDriver

    return TPCHDriver(sf=0.01, cluster=Cluster(devices=jax.devices()[:1]),
                      seed=0)


def _plan_out(driver, q):
    plan = lower(q, driver.catalog)
    fn = driver.cluster.compile(plan, driver.ctx, driver.placed)
    return jax.tree.map(np.asarray, fn({n: t.columns
                                        for n, t in driver.placed.items()}))


@pytest.mark.parametrize("nodes", [1, 8])
def test_q14_promo_compacted_equals_full_width(nodes, one_node_driver,
                                               tpch_driver, monkeypatch):
    """The literal q14_promo compacts its month window, answers as the
    reference does, and to the last bit as the probe over every row."""
    driver = one_node_driver if nodes == 1 else tpch_driver
    q = tq.q14_promo_ir()
    assert lower(q, driver.catalog).probes == ("compact",)
    out = _plan_out(driver, q)
    assert out["probe_tier"].tolist() == [0]
    np.testing.assert_allclose(out["value"].reshape(()),
                               driver.oracle("q14")[1], rtol=2e-4)
    # capacities of one slot: every row probed, which is no overflow
    monkeypatch.setattr(semijoin, "PROBE_FRACTIONS", (1 << 40,) * 2)
    full = _plan_out(driver, q)
    assert full["probe_tier"].tolist() == [semijoin.FULL_WIDTH]
    assert "overflow" not in full
    np.testing.assert_array_equal(full["value"], out["value"])


def _window(months):
    """A ship-date window of ``months`` months from 1995-03-01."""
    y, m = divmod(2 + months, 12)
    return {"q14_date_min": day(1995, 3, 1),
            "q14_date_max": day(1995 + y, m + 1, 1)}


def _expected_tier(driver, b):
    ship = np.asarray(driver.tables["lineitem"].columns["l_shipdate"])
    kept = ((ship >= b["q14_date_min"]) & (ship < b["q14_date_max"])).sum()
    caps = semijoin.probe_capacities(ship.shape[0])
    return sum(int(kept > c) for c in caps)


def _promo_revenue(driver, b):
    return reference.q14(driver.tables,
                         p=tq.oracle_params("q14_promo", b))[1]


def _probe_counts(driver):
    m = driver.obs.metrics
    return (m.value("semijoin.probe.compact") or 0,
            m.value("semijoin.probe.full") or 0)


def test_parameterized_bindings_on_both_sides_of_a_tier(one_node_driver):
    """One prepared plan: a month takes the first capacity, a quarter the
    second, two years every row; each execution counts once."""
    driver = one_node_driver
    prep = driver.prepare(tq.q14_promo_param_ir())
    assert [_expected_tier(driver, _window(n)) for n in (1, 3, 24)] == [
        0, 1, semijoin.FULL_WIDTH]
    for months in (1, 3, 24):
        b = _window(months)
        before = _probe_counts(driver)
        ans = prep.execute(b)
        compacted = _expected_tier(driver, b) < semijoin.FULL_WIDTH
        assert _probe_counts(driver) == (before[0] + compacted,
                                         before[1] + (not compacted))
        assert not ans.overflow
        np.testing.assert_allclose(np.asarray(ans.value).reshape(()),
                                   _promo_revenue(driver, b), rtol=2e-4)


BINDINGS = [_window(1), _window(3), _window(24)]


def test_vmapped_batch_probes_every_row(one_node_driver):
    """Lanes vmapped under one dispatch keep the probe over every row
    (a switch under vmap runs every branch)."""
    driver = one_node_driver
    prep = driver.prepare(tq.q14_promo_param_ir())
    assert lower(prep.entry.shape, driver.catalog,
                 batched=True).probes == ("full",)
    before = _probe_counts(driver)
    ans = prep.execute_batch(BINDINGS)
    assert _probe_counts(driver) == (before[0], before[1] + len(BINDINGS))
    for lane, b in enumerate(BINDINGS):
        np.testing.assert_allclose(np.asarray(ans.value)[lane].reshape(()),
                                   _promo_revenue(driver, b), rtol=2e-4)


def test_looped_batch_compacts_each_lane(monkeypatch):
    """Partitions too large to vmap loop their lanes, and each lane takes
    the tier its own window needs."""
    from repro.tpch.driver import TPCHDriver

    monkeypatch.setattr(engine, "BATCH_VMAP_MAX_ROWS", 1000)
    driver = TPCHDriver(sf=0.01, cluster=Cluster(devices=jax.devices()[:1]),
                        seed=0)
    prep = driver.prepare(tq.q14_promo_param_ir())
    assert lower(prep.entry.shape, driver.catalog,
                 batched=True).probes == ("compact",)
    ans = prep.execute_batch(BINDINGS)
    assert _probe_counts(driver) == (2, 1)
    assert not np.asarray(ans.overflow).any()
    for lane, b in enumerate(BINDINGS):
        np.testing.assert_allclose(np.asarray(ans.value)[lane].reshape(()),
                                   _promo_revenue(driver, b), rtol=2e-4)


def test_explain_and_lower_event_name_the_probe(tpch_driver):
    """A probe after the filter compacts; one over the whole table cannot."""
    unfiltered = (
        Q.scan("lineitem")
        .semijoin("part", key=C("l_partkey"),
                  pred=C("p_type") < S.PROMO_TYPES, alt="bitset")
        .group_agg(aggs=[("promo_revenue", "sum", tq.REVENUE)])
        .named("promo_revenue_all"))
    assert "probe=compact]" in tpch_driver.explain(tq.q14_promo_ir()).text()
    assert "probe=full]" in tpch_driver.explain(unfiltered).text()
    tpch_driver.prepare(unfiltered).execute()
    events = [e for e in tpch_driver.obs.find("lower")
              if e.attrs.get("query") == "promo_revenue_all"]
    assert events and events[-1].attrs["probe"] == "full"
