"""Runtime query parameters: prepared plans compiled ONCE and executed for
any literal binding (the paper's §2/§3.1 compile-once model).

- hypothesis sweep: for q1/q6/q14 random TPC-H §2.4 substitution draws
  across seeds x cluster sizes must match the float64 numpy oracle via the
  SAME prepared plan object, with exactly one XLA compile per shape
  (``TPCHDriver.compile_events`` counts traces),
- the prepared plan is BIT-FOR-BIT identical to a freshly compiled
  literal-bound plan (parameterization changes no arithmetic),
- plan-cache regression: IR trees differing only in literals share one
  executable; trees differing in structure still miss,
- parameterized Tier-1 routing: bin-edge exactness decided per binding at
  execute time (in-range edge -> cube, off-edge/out-of-range -> the
  prepared Tier-2 plan),
- batched execution: ``execute_batch`` lanes are bitwise equal to scalar
  executes and one overflowing lane never poisons its siblings.
"""
from __future__ import annotations

import dataclasses

import jax
import numpy as np
import pytest

from repro.query import (
    C,
    IRValidationError,
    Param,
    Q,
    UnboundParamError,
    bind_params,
    lower,
    parameterize,
    query_params,
    same_query,
)
from repro.tpch import queries as tq
from repro.tpch.driver import TPCHDriver
from repro.tpch.reference import ALL as ORACLES
from repro.tpch.schema import DEFAULT_PARAMS as DP, day

pytestmark = pytest.mark.tier1

PARAM_LABELS = {"q1": "q1_param", "q6": "q6_param",
                "q14_promo": "q14_promo_param"}


def _oracle(name: str, driver, binding: dict):
    p = tq.oracle_params(name, binding)
    if name == "q14_promo":
        return ORACLES["q14"](driver.tables, p=p)[1]  # promo revenue term
    return ORACLES[name](driver.tables, p=p)


def _check(name: str, value, ref):
    got = np.asarray(value)
    if name == "q1":
        np.testing.assert_allclose(got.reshape(6, 6), ref, rtol=2e-4)
    else:
        np.testing.assert_allclose(got.reshape(()), ref, rtol=2e-4, atol=1e-2)


# ---------------------------------------------------------------------------
# acceptance: one prepare, many executes, ONE compile, oracle on every binding
# ---------------------------------------------------------------------------


def test_one_compile_serves_eight_distinct_bindings(cluster):
    driver = TPCHDriver(sf=0.005, cluster=cluster, seed=0)
    prep = driver.prepare(tq.q6_param_ir())
    rng = np.random.default_rng(11)
    bindings = [tq.random_binding("q6", rng) for _ in range(8)]
    assert len({tuple(sorted(b.items())) for b in bindings}) == 8
    for b in bindings:
        ans = prep.execute(b)
        assert ans.tier == 2 and not ans.overflow
        _check("q6", ans.value, _oracle("q6", driver, b))
    assert driver.compile_events == ["q6_param"], (
        "8 distinct executes of one prepared q6 must trigger exactly 1 "
        f"XLA compile, saw {driver.compile_events}"
    )


# ---------------------------------------------------------------------------
# property sweep across seeds x node counts (same prepared plan object):
# hypothesis drives the draws when available; a fixed grid of pre-seeded
# draws keeps the property exercised when it is not (requirements-dev.txt)
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # the suite degrades gracefully without hypothesis
    HAVE_HYPOTHESIS = False

_DRIVERS = {}   # (seed, nodes) -> TPCHDriver, cached across examples
_PREPARED = {}  # (seed, nodes, qname) -> PreparedQuery


def _driver(seed: int, nodes: int) -> TPCHDriver:
    key = (seed, nodes)
    if key not in _DRIVERS:
        from repro.core import Cluster

        cluster = Cluster(devices=jax.devices()[:nodes])
        _DRIVERS[key] = TPCHDriver(sf=0.002, cluster=cluster, seed=seed)
    return _DRIVERS[key]


def _prepared(seed: int, nodes: int, qname: str):
    key = (seed, nodes, qname)
    if key not in _PREPARED:
        _PREPARED[key] = _driver(seed, nodes).prepare(
            tq.PARAM_QUERIES[qname]())
    return _PREPARED[key]


def _sweep_example(seed, nodes, qname, draw):
    d = _driver(seed, nodes)
    prep = _prepared(seed, nodes, qname)
    binding = tq.random_binding(qname, np.random.default_rng(draw))
    ans = prep.execute(binding)
    assert not np.any(ans.overflow), (qname, binding)
    _check(qname, ans.value, _oracle(qname, d, binding))
    # the compile-once contract: however many examples ran on this driver,
    # the prepared shape traced exactly once
    assert d.compile_events.count(PARAM_LABELS[qname]) == 1


if HAVE_HYPOTHESIS:
    @settings(max_examples=8, deadline=None)
    @given(
        seed=st.sampled_from([0, 1]),
        nodes=st.sampled_from([1, 2, 8]),
        qname=st.sampled_from(["q1", "q6", "q14_promo"]),
        draw=st.integers(0, 2**31 - 1),
    )
    def test_prepared_plan_matches_oracle_for_any_binding(seed, nodes, qname,
                                                          draw):
        _sweep_example(seed, nodes, qname, draw)


_FIXED_GRID = [
    (0, 8, "q1", 101), (0, 8, "q6", 202), (0, 8, "q14_promo", 303),
    (1, 2, "q1", 404), (1, 2, "q6", 505), (1, 2, "q14_promo", 606),
    (0, 1, "q6", 707), (1, 8, "q6", 808),
]


@pytest.mark.parametrize("seed,nodes,qname,draw", _FIXED_GRID)
def test_prepared_plan_matches_oracle_fixed_grid(seed, nodes, qname, draw):
    _sweep_example(seed, nodes, qname, draw)


# ---------------------------------------------------------------------------
# bit-for-bit: the prepared plan IS the literal plan
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("qname", ["q1", "q6", "q14_promo"])
def test_prepared_bitwise_equals_fresh_literal_compile(tpch_driver, qname):
    """Executing a prepared plan with a binding must produce byte-identical
    results to compiling the literal-bound query from scratch —
    parameterization moves literals out of the executable without touching
    a single arithmetic op."""
    d = tpch_driver
    prep = d.prepare(tq.PARAM_QUERIES[qname]())
    binding = tq.random_binding(qname, np.random.default_rng(23))
    cols = {n: t.columns for n, t in d.placed.items()}
    fn = d._ensure_compiled(prep.entry)
    merged = prep.binding(binding)  # incl. auto-extracted defaults
    out_p = jax.device_get(fn(cols, prep._cast(merged)))
    literal = bind_params(prep.query, merged)
    assert not query_params(literal.root)
    fn_l = d.cluster.compile(
        lower(literal, d.catalog, wire=d.wire, binding=merged),
        d.ctx, d.placed)
    out_l = jax.device_get(fn_l(cols))
    assert set(out_p) == set(out_l)
    for k in out_p:
        assert np.asarray(out_p[k]).tobytes() == np.asarray(out_l[k]).tobytes(), (
            f"{qname}[{k}] differs between prepared and literal plan"
        )


def test_batched_q1_lanes_match_oracle(tpch_driver):
    """The batched lowering swaps q1's grouped aggregation for the
    ``mask @ (onehot (x) measures)`` GEMM — every lane must still agree
    with the float64 oracle for its own binding."""
    prep = tpch_driver.prepare(tq.q1_param_ir())
    rng = np.random.default_rng(41)
    bindings = [tq.random_binding("q1", rng) for _ in range(8)]
    ansb = prep.execute_batch(bindings)
    for i, b in enumerate(bindings):
        _check("q1", np.asarray(ansb.value)[i],
               _oracle("q1", tpch_driver, b))


def test_batch_lanes_bitwise_equal_scalar_executes(tpch_driver):
    d = tpch_driver
    prep = d.prepare(tq.q6_param_ir())
    rng = np.random.default_rng(31)
    bindings = [tq.random_binding("q6", rng) for _ in range(8)]
    ansb = prep.execute_batch(bindings)
    batched = np.asarray(ansb.value)
    assert batched.shape[0] == 8
    assert np.asarray(ansb.overflow).shape == (8,)
    cols = {n: t.columns for n, t in d.placed.items()}
    fn = d._ensure_compiled(prep.entry)
    for i, b in enumerate(bindings):
        scalar = jax.device_get(fn(cols, prep._cast(prep.binding(b))))
        assert batched[i].tobytes() == np.asarray(scalar["value"]).tobytes()


@pytest.mark.parametrize("name", ["q1", "q6", "q14_promo"])
def test_batch_lanes_loop_on_large_partitions(cluster, monkeypatch, name):
    """Past ``BATCH_VMAP_MAX_ROWS`` rows per node a batched plan runs its
    lanes one after another in the same dispatch; every lane must still
    match the oracle for its own binding."""
    from repro.core import engine

    monkeypatch.setattr(engine, "BATCH_VMAP_MAX_ROWS", 0)
    driver = TPCHDriver(sf=0.005, cluster=cluster, seed=0)
    prep = driver.prepare(tq.PARAM_QUERIES[name]())
    rng = np.random.default_rng(7)
    bindings = [tq.random_binding(name, rng) for _ in range(3)]
    ans = prep.execute_batch(bindings)
    assert not np.asarray(ans.overflow).any()
    for i, b in enumerate(bindings):
        _check(name, np.asarray(ans.value)[i], _oracle(name, driver, b))


# ---------------------------------------------------------------------------
# plan-cache regression: key modulo parameter values, not modulo structure
# ---------------------------------------------------------------------------


def test_plan_cache_hits_for_literal_differing_trees(tpch_driver):
    """Two IR trees differing ONLY in predicate literals canonicalize to
    one shape and share one compiled executable (they used to be two
    separate XLA compiles)."""
    shifted = dataclasses.replace(DP, q6_quantity=30.0,
                                  q6_date_min=day(1995, 1, 1))
    p1 = tpch_driver.prepare(tq.q6_ir())
    p2 = tpch_driver.prepare(tq.q6_ir(shifted))
    assert p1.entry is p2.entry, "literal-differing trees must share a plan"
    assert p1.defaults != p2.defaults  # ... but keep their own bindings
    # identical literals memoize down to the same bound closure
    assert (tpch_driver.compile_query(tq.q6_ir())
            is tpch_driver.compile_query(tq.q6_ir()))


def test_plan_cache_misses_for_structure_differing_trees(tpch_driver):
    """Guards against over-normalizing the cache key: a structural change
    (extra conjunct / different aggregate expression) must MISS."""
    base = tpch_driver.prepare(tq.q6_ir())
    extra_filter = (
        Q.scan("lineitem")
        .filter((C("l_shipdate") >= DP.q6_date_min)
                & (C("l_shipdate") < DP.q6_date_max)
                & (C("l_discount") >= DP.q6_disc_min)
                & (C("l_discount") <= DP.q6_disc_max)
                & (C("l_quantity") < DP.q6_quantity)
                & (C("l_tax") >= 0.0))
        .group_agg(aggs=[("revenue", "sum",
                          C("l_extendedprice") * C("l_discount"))])
    )
    other_measure = (
        Q.scan("lineitem")
        .filter((C("l_shipdate") >= DP.q6_date_min)
                & (C("l_shipdate") < DP.q6_date_max)
                & (C("l_discount") >= DP.q6_disc_min)
                & (C("l_discount") <= DP.q6_disc_max)
                & (C("l_quantity") < DP.q6_quantity))
        .group_agg(aggs=[("revenue", "sum", C("l_extendedprice"))])
    )
    assert tpch_driver.prepare(extra_filter).entry is not base.entry
    assert tpch_driver.prepare(other_measure).entry is not base.entry


def test_parameterize_reaches_literals_under_nested_not():
    """A comparison literal inside ~(...) nested in a conjunction must be
    parameterized too — otherwise literal variants silently miss the
    cache."""

    def q(qty):
        return (Q.scan("lineitem")
                .filter(~(C("l_quantity") < qty) & (C("l_discount") >= 0.05))
                .group_agg(aggs=[("n", "count")]))

    s1, b1 = parameterize(q(24.0))
    s2, b2 = parameterize(q(30.0))
    assert same_query(s1, s2)
    assert sorted(b1.values()) != sorted(b2.values())


def test_bound_closure_cache_is_lru_bounded(cluster):
    """compile_query memoizes one closure per literal binding; a stream of
    ever-changing literals must not grow that memo without bound."""
    driver = TPCHDriver(sf=0.002, cluster=cluster, seed=0)
    fns = [driver.compile_query(
        tq.q6_ir(dataclasses.replace(DP, q6_quantity=float(q))))
        for q in range(20, 34)]
    prep = driver.prepare(tq.q6_ir())
    assert len(prep.entry.bound) <= driver.BOUND_CACHE_MAX
    assert len(set(map(id, fns))) == len(fns)  # distinct bindings, own closures
    cols = {n: t.columns for n, t in driver.placed.items()}
    fns[0](cols)
    fns[-1](cols)
    assert driver.compile_events == ["q6"]     # ... but ONE executable


def _q6_variant(extra_cols):
    """A q6-shaped tree with one extra conjunct per column in
    ``extra_cols`` — each distinct column SET is a distinct structure
    (literal values alone would canonicalize to the same shape)."""
    cond = ((C("l_shipdate") >= DP.q6_date_min)
            & (C("l_shipdate") < DP.q6_date_max)
            & (C("l_discount") >= DP.q6_disc_min)
            & (C("l_discount") <= DP.q6_disc_max)
            & (C("l_quantity") < DP.q6_quantity))
    for col in extra_cols:
        cond = cond & (C(col) >= 0.0)
    return (Q.scan("lineitem").filter(cond)
            .group_agg(aggs=[("revenue", "sum",
                              C("l_extendedprice") * C("l_discount"))]))


def test_prepared_plan_cache_evicts_oldest_shape(cluster):
    """Overfill the structural plan-cache LRU: the OLDEST (least recently
    used) shape is the one evicted, a hit refreshes recency, and an
    evicted shape re-prepares as a fresh miss."""
    cols = ["l_tax", "l_quantity", "l_discount", "l_extendedprice",
            "l_shipdate", "l_orderkey"]
    shapes = [_q6_variant(cols[:k]) for k in range(6)]
    driver = TPCHDriver(sf=0.002, cluster=cluster, seed=0)
    driver.IR_CACHE_MAX = 4
    mreg = driver.obs.metrics

    preps = [driver.prepare(s) for s in shapes[:5]]   # 5th insert evicts #0
    assert len(driver._prepared) == 4
    miss0 = mreg.value("plan_cache.miss")
    again0 = driver.prepare(shapes[0])                # oldest: gone -> miss
    assert mreg.value("plan_cache.miss") == miss0 + 1
    assert again0.entry is not preps[0].entry
    hit0 = mreg.value("plan_cache.hit")
    assert driver.prepare(shapes[4]).entry is preps[4].entry  # newest: hit
    assert mreg.value("plan_cache.hit") == hit0 + 1
    # recency, not insertion order: after again0's insert evicted #1 and
    # the hit refreshed #4, the oldest entry is #2 — the next overfill
    # must drop IT while the refreshed #3/#4 survive
    driver.prepare(shapes[5])
    assert driver.prepare(shapes[3]).entry is preps[3].entry
    m = mreg.value("plan_cache.miss")
    assert driver.prepare(shapes[2]).entry is not preps[2].entry
    assert mreg.value("plan_cache.miss") == m + 1


def test_bound_closure_cache_evicts_oldest_binding(cluster):
    """Overfill the per-shape bound-closure LRU: the oldest binding's
    closure is dropped (rebuilt on re-request), the newest survives."""
    driver = TPCHDriver(sf=0.002, cluster=cluster, seed=0)
    driver.BOUND_CACHE_MAX = 3

    def fn_for(q):
        return driver.compile_query(
            tq.q6_ir(dataclasses.replace(DP, q6_quantity=float(q))))

    fns = [fn_for(q) for q in (20, 21, 22, 23)]       # 4th insert evicts 20
    prep = driver.prepare(tq.q6_ir())
    assert len(prep.entry.bound) == 3
    assert fn_for(23) is fns[3], "newest binding must still be memoized"
    assert fn_for(20) is not fns[0], "evicted binding must rebuild"
    assert fn_for(21) is not fns[1], "20's rebuild evicted 21, next-oldest"
    assert driver.compile_events == [], (
        "closure churn must not touch the compiled executable")


def test_batched_division_measure_stays_finite_and_correct(cluster):
    """A measure that divides can be non-finite on filtered-out rows; the
    batched lowering must not take the mask-GEMM shortcut there (0 * inf
    poisons group sums) — lanes must match a numpy oracle computed over
    unmasked rows only."""
    driver = TPCHDriver(sf=0.005, cluster=cluster, seed=0)
    q = (Q.scan("lineitem")
         .filter(C("l_shipdate") > Param("cut", "int32"))
         .group_agg(keys=[("returnflag", C("l_returnflag"), 3)],
                    aggs=[("ratio_sum", "sum",
                           C("l_quantity") / (C("l_shipdate") - 100.0))]))
    prep = driver.prepare(q)
    cuts = [150, 400, 800, 1200, 1600, 2000, 2200, 2400]
    ans = prep.execute_batch([{"cut": c} for c in cuts])
    got = np.asarray(ans.value)
    assert np.isfinite(got).all(), "masked non-finite rows leaked into sums"
    li = driver.tables["lineitem"].columns
    ship = li["l_shipdate"].astype(np.float64)
    assert (ship == 100).any(), "test needs a zero-denominator masked row"
    for i, c in enumerate(cuts):
        sel = ship > c
        ref = np.zeros(3)
        np.add.at(ref, li["l_returnflag"][sel],
                  li["l_quantity"][sel].astype(np.float64)
                  / (ship[sel] - 100.0))
        np.testing.assert_allclose(got[i].reshape(3), ref, rtol=2e-4)


def test_maskgemm_eligibility_guards():
    from repro.query.ir import GroupAgg
    from repro.query.lower import ONEHOT_MAX_GROUPS, _maskgemm_eligible

    def root_of(q):
        assert isinstance(q.root, GroupAgg)
        return q.root

    assert _maskgemm_eligible(root_of(tq.q1_param_ir()), 6)
    big = Q.scan("lineitem").group_agg(
        keys=[("k", C("l_orderkey"), ONEHOT_MAX_GROUPS + 1)],
        aggs=[("n", "count")])
    assert not _maskgemm_eligible(root_of(big), ONEHOT_MAX_GROUPS + 1)
    div = Q.scan("lineitem").group_agg(
        keys=[("returnflag", C("l_returnflag"), 3)],
        aggs=[("r", "sum", C("l_quantity") / C("l_extendedprice"))])
    assert not _maskgemm_eligible(root_of(div), 3)
    param_measure = Q.scan("lineitem").group_agg(
        keys=[("returnflag", C("l_returnflag"), 3)],
        aggs=[("s", "sum", C("l_quantity") * Param("w", "float32"))])
    assert not _maskgemm_eligible(root_of(param_measure), 3)


def test_parameterize_is_deterministic_and_invertible():
    shape1, b1 = parameterize(tq.q6_ir())
    shape2, b2 = parameterize(
        tq.q6_ir(dataclasses.replace(DP, q6_quantity=30.0)))
    assert same_query(shape1, shape2)
    assert b1 != b2 and set(b1) == set(b2)
    round_trip = bind_params(shape1, b1)
    assert same_query(round_trip, tq.q6_ir())
    # structural literals survive: the Bin edges of a grouped key are not
    # parameterized
    shape3, b3 = parameterize(tq.revenue_by_shipmonth_query())
    assert b3 == {} and same_query(shape3, tq.revenue_by_shipmonth_query())


# ---------------------------------------------------------------------------
# typed negative paths
# ---------------------------------------------------------------------------


def test_missing_and_unknown_bindings_are_typed(tpch_driver):
    prep = tpch_driver.prepare(tq.q6_param_ir())
    with pytest.raises(UnboundParamError, match="q6_date_min"):
        prep.execute({"q6_date_max": DP.q6_date_max})
    with pytest.raises(UnboundParamError, match="q6_typo"):
        prep.execute({**tq.default_binding("q6"), "q6_typo": 1})


def test_conflicting_param_declarations_rejected():
    q = (Q.scan("lineitem")
         .filter((C("l_shipdate") >= Param("p", "int32"))
                 & (C("l_quantity") < Param("p", "float32")))
         .group_agg(aggs=[("n", "count")]))
    with pytest.raises(IRValidationError, match="declared twice"):
        query_params(q.root)


# ---------------------------------------------------------------------------
# parameterized Tier-1 routing (execute-time bin-edge exactness)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def cubed_driver(tpch_driver):
    if not tpch_driver.cubes:
        tpch_driver.build_cubes()
    return tpch_driver


def test_param_binding_on_bin_edge_serves_tier1(cubed_driver):
    prep = cubed_driver.prepare(tq.q1_param_ir())
    ans = prep.execute(tq.default_binding("q1"))  # validation cutoff = edge
    assert ans.tier == 1 and ans.source == "lineitem_pricing"
    _check("q1", np.asarray(ans.value).reshape(6, 6),
           ORACLES["q1"](cubed_driver.tables))


def test_param_binding_off_edge_falls_back_to_prepared_tier2(cubed_driver):
    prep = cubed_driver.prepare(tq.q1_param_ir())
    binding = {"q1_shipdate_max": DP.q1_shipdate_max - 1}  # inside a bin
    ans = prep.execute(binding)
    assert ans.tier == 2
    _check("q1", ans.value, _oracle("q1", cubed_driver, binding))


def test_param_binding_out_of_range_falls_back_to_prepared_tier2(cubed_driver):
    prep = cubed_driver.prepare(tq.q1_param_ir())
    beyond = day(1999, 6, 1)  # past the last bin edge (open last bin)
    ans = prep.execute({"q1_shipdate_max": beyond})
    assert ans.tier == 2
    _check("q1", ans.value, _oracle("q1", cubed_driver,
                                    {"q1_shipdate_max": beyond}))


def test_tier1_and_tier2_share_one_prepared_object(cubed_driver):
    """The SAME PreparedQuery serves edge bindings from the cube and
    off-edge bindings from the compiled plan — one compile covers every
    fallback."""
    d = cubed_driver
    prep = d.prepare(tq.q1_param_ir())
    before = d.compile_events.count("q1_param")
    tiers = {prep.execute(tq.default_binding("q1")).tier,
             prep.execute({"q1_shipdate_max": DP.q1_shipdate_max - 3}).tier,
             prep.execute({"q1_shipdate_max": DP.q1_shipdate_max - 9}).tier}
    assert tiers == {1, 2}
    assert d.compile_events.count("q1_param") <= max(before, 1)


# ---------------------------------------------------------------------------
# batched execution: overflow lanes stay isolated
# ---------------------------------------------------------------------------


def test_batch_overflow_lane_does_not_poison_siblings(cluster):
    """Force the q14 request exchange down to a tiny capacity: a narrow
    month window fits, the five-year window overflows — the overflow flag
    must come back PER LANE and the narrow lane's revenue must still match
    the oracle."""
    driver = TPCHDriver(sf=0.01, cluster=cluster, seed=0,
                        capacities={"q14_promo_param_request_sj0": 64})
    prep = driver.prepare(tq.q14_promo_param_ir(alt="request"))
    narrow = tq.default_binding("q14_promo")
    wide = {"q14_date_min": day(1993, 1, 1), "q14_date_max": day(1998, 1, 1)}
    ans = prep.execute_batch([narrow, wide])
    overflow = np.asarray(ans.overflow)
    assert overflow.tolist() == [False, True], overflow
    _check("q14_promo", np.asarray(ans.value)[0],
           _oracle("q14_promo", driver, narrow))
    # scalar executions agree with the per-lane flags
    assert prep.execute(narrow).overflow is False
    assert prep.execute(wide).overflow is True
