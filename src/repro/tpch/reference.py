"""Pure-numpy oracle for the 12 implemented TPC-H queries (paper §4.3).

Operates on the GLOBAL (unpartitioned) tables in float64 — the correctness
baseline every distributed plan must match ("we check the query results for
correctness", §4.1).  Rankings use (value desc, key asc) exactly like the
plans so top-k sets compare deterministically.
"""
from __future__ import annotations

import numpy as np

from repro.tpch import schema as S
from repro.tpch.schema import DEFAULT_PARAMS as DP


def _topk(values, keys, k):
    """(value desc, key asc) ranking; returns (values, keys) padded with
    (-inf, -1) when fewer than k rows qualify."""
    values = np.asarray(values, np.float64)
    keys = np.asarray(keys, np.int64)
    order = np.lexsort((keys, -values))[:k]
    out_v = np.full(k, -np.inf)
    out_k = np.full(k, -1, np.int64)
    out_v[: len(order)] = values[order]
    out_k[: len(order)] = keys[order]
    return out_v, out_k


def q1(t, p=DP):
    li = t["lineitem"].columns
    sel = li["l_shipdate"] <= p.q1_shipdate_max
    rf = li["l_returnflag"][sel]
    ls = li["l_linestatus"][sel]
    g = rf * 2 + ls
    qty = li["l_quantity"][sel].astype(np.float64)
    price = li["l_extendedprice"][sel].astype(np.float64)
    disc = li["l_discount"][sel].astype(np.float64)
    tax = li["l_tax"][sel].astype(np.float64)
    disc_price = price * (1 - disc)
    charge = disc_price * (1 + tax)
    out = np.zeros((6, 6))
    for col, v in enumerate([qty, price, disc_price, charge, disc, np.ones_like(qty)]):
        np.add.at(out[:, col], g, v)
    return out  # [sum_qty, sum_base, sum_disc_price, sum_charge, sum_disc, count]


def q2(t, p=DP, k=100):
    part = t["part"].columns
    ps = t["partsupp"].columns
    sup = t["supplier"].columns
    psel = (part["p_size"] == p.q2_size) & (part["p_type"] % S.NUM_BRASS == p.q2_type_finish)
    s_in_region = S.nation_region(sup["s_nationkey"]) == p.q2_region
    ps_part_ok = psel[ps["ps_partkey"]]
    ps_sup_ok = s_in_region[ps["ps_suppkey"]]
    cand = ps_part_ok & ps_sup_ok
    cost = ps["ps_supplycost"].astype(np.float64)
    nparts = part["p_partkey"].shape[0]
    mincost = np.full(nparts, np.inf)
    np.minimum.at(mincost, ps["ps_partkey"][cand], cost[cand])
    is_min = cand & (cost <= mincost[ps["ps_partkey"]] + 1e-6) & (
        cost >= mincost[ps["ps_partkey"]] - 1e-6)
    # result rows: (acctbal of supplier, composite key part*NS+supp)
    num_sup = sup["s_suppkey"].shape[0]
    comp = ps["ps_partkey"][is_min].astype(np.int64) * num_sup + ps["ps_suppkey"][is_min]
    bal = sup["s_acctbal"].astype(np.float64)[ps["ps_suppkey"][is_min]]
    return _topk(bal, comp, k)


def q3(t, p=DP, k=10):
    cust = t["customer"].columns
    orders = t["orders"].columns
    li = t["lineitem"].columns
    c_ok = cust["c_mktsegment"] == p.q3_segment
    o_ok = (orders["o_orderdate"] < p.q3_date) & c_ok[orders["o_custkey"]]
    l_ok = li["l_shipdate"] > p.q3_date
    rev = np.zeros(orders["o_orderkey"].shape[0])
    lsel = l_ok & o_ok[li["l_orderkey"]]
    np.add.at(
        rev,
        li["l_orderkey"][lsel],
        (li["l_extendedprice"][lsel] * (1 - li["l_discount"][lsel])).astype(np.float64),
    )
    keys = orders["o_orderkey"][rev > 0]
    return _topk(rev[rev > 0], keys, k)


def q4(t, p=DP):
    orders = t["orders"].columns
    li = t["lineitem"].columns
    o_ok = (orders["o_orderdate"] >= p.q4_date_min) & (orders["o_orderdate"] < p.q4_date_max)
    late = li["l_commitdate"] < li["l_receiptdate"]
    has_late = np.zeros(orders["o_orderkey"].shape[0], bool)
    has_late[li["l_orderkey"][late]] = True
    sel = o_ok & has_late
    return np.bincount(orders["o_orderpriority"][sel], minlength=5).astype(np.float64)


def q5(t, p=DP):
    cust = t["customer"].columns
    orders = t["orders"].columns
    li = t["lineitem"].columns
    sup = t["supplier"].columns
    o_ok = (orders["o_orderdate"] >= p.q5_date_min) & (orders["o_orderdate"] < p.q5_date_max)
    s_nat = sup["s_nationkey"]
    s_ok = S.nation_region(s_nat) == p.q5_region
    c_nat = cust["c_nationkey"]
    l_sup_nat = s_nat[li["l_suppkey"]]
    l_cust = orders["o_custkey"][li["l_orderkey"]]
    sel = (
        o_ok[li["l_orderkey"]]
        & s_ok[li["l_suppkey"]]
        & (c_nat[l_cust] == l_sup_nat)
    )
    rev = np.zeros(25)
    np.add.at(
        rev,
        l_sup_nat[sel],
        (li["l_extendedprice"][sel] * (1 - li["l_discount"][sel])).astype(np.float64),
    )
    return rev  # revenue per nation (only the region's nations are nonzero)


def q6(t, p=DP):
    li = t["lineitem"].columns
    sel = (
        (li["l_shipdate"] >= p.q6_date_min)
        & (li["l_shipdate"] < p.q6_date_max)
        & (li["l_discount"] >= p.q6_disc_min)
        & (li["l_discount"] <= p.q6_disc_max)
        & (li["l_quantity"] < p.q6_quantity)
    )
    rev = li["l_extendedprice"].astype(np.float64) * li["l_discount"].astype(np.float64)
    return rev[sel].sum()


def q11(t, p=DP, sf: float = 1.0, cap: int = 128):
    ps = t["partsupp"].columns
    sup = t["supplier"].columns
    s_ok = sup["s_nationkey"] == p.q11_nation
    sel = s_ok[ps["ps_suppkey"]]
    value = (ps["ps_supplycost"].astype(np.float64) * ps["ps_availqty"]).astype(np.float64)
    nparts = t["part"].columns["p_partkey"].shape[0]
    per_part = np.zeros(nparts)
    np.add.at(per_part, ps["ps_partkey"][sel], value[sel])
    total = per_part.sum()
    thresh = total * p.q11_fraction / sf
    qualified = per_part > thresh
    return _topk(per_part[qualified], np.nonzero(qualified)[0], cap)


def q13(t, p=DP, hist_cap: int = 64):
    orders = t["orders"].columns
    cust = t["customer"].columns
    sel = ~orders["o_comment_special"]
    counts = np.bincount(
        orders["o_custkey"][sel], minlength=cust["c_custkey"].shape[0]
    )
    counts = np.minimum(counts, hist_cap - 1)
    return np.bincount(counts, minlength=hist_cap).astype(np.float64)


def q14(t, p=DP):
    li = t["lineitem"].columns
    part = t["part"].columns
    sel = (li["l_shipdate"] >= p.q14_date_min) & (li["l_shipdate"] < p.q14_date_max)
    promo = (part["p_type"] < S.PROMO_TYPES)[li["l_partkey"]]
    rev = (li["l_extendedprice"] * (1 - li["l_discount"])).astype(np.float64)
    total = rev[sel].sum()
    promo_rev = rev[sel & promo].sum()
    return np.array([100.0 * promo_rev / total, promo_rev, total])


def q15(t, p=DP, k=1):
    li = t["lineitem"].columns
    sup = t["supplier"].columns
    sel = (li["l_shipdate"] >= p.q15_date_min) & (li["l_shipdate"] < p.q15_date_max)
    rev = np.zeros(sup["s_suppkey"].shape[0])
    np.add.at(
        rev,
        li["l_suppkey"][sel],
        (li["l_extendedprice"][sel] * (1 - li["l_discount"][sel])).astype(np.float64),
    )
    return _topk(rev, np.arange(rev.shape[0]), k)


def q18(t, p=DP, k=100):
    li = t["lineitem"].columns
    orders = t["orders"].columns
    qty = np.zeros(orders["o_orderkey"].shape[0])
    np.add.at(qty, li["l_orderkey"], li["l_quantity"].astype(np.float64))
    sel = qty > p.q18_quantity
    return _topk(
        orders["o_totalprice"].astype(np.float64)[sel], orders["o_orderkey"][sel], k
    )


def q18_sj(t, qty=250.0, segment=DP.q3_segment):
    """``queries.q18_sj_ir``: total quantity and count of the orders above
    ``qty`` whose customer is in market ``segment``."""
    li = t["lineitem"].columns
    orders = t["orders"].columns
    cust = t["customer"].columns
    sq = np.zeros(orders["o_orderkey"].shape[0])
    np.add.at(sq, li["l_orderkey"], li["l_quantity"].astype(np.float64))
    sel = (sq > qty) & (cust["c_mktsegment"][orders["o_custkey"]] == segment)
    return np.array([sq[sel].sum(), sel.sum()])


def q21(t, p=DP, k=100):
    li = t["lineitem"].columns
    orders = t["orders"].columns
    sup = t["supplier"].columns
    num_sup = sup["s_suppkey"].shape[0]
    delayed = li["l_receiptdate"] > li["l_commitdate"]
    lo = li["l_orderkey"].astype(np.int64)
    norders = orders["o_orderkey"].shape[0]
    cnt_lines = np.bincount(lo, minlength=norders)
    cnt_delayed = np.bincount(lo[delayed], minlength=norders)
    comp = lo * num_sup + li["l_suppkey"]
    uniq, inv, counts = np.unique(comp, return_inverse=True, return_counts=True)
    same_lines = counts[inv]
    uniq_d, counts_d = np.unique(comp[delayed], return_counts=True)
    same_delayed_u = np.zeros(len(uniq), np.int64)
    same_delayed_u[np.searchsorted(uniq, uniq_d)] = counts_d
    same_delayed = same_delayed_u[inv]
    status_f = orders["o_orderstatus"][lo] == 0
    nation_ok = (sup["s_nationkey"] == p.q21_nation)[li["l_suppkey"]]
    qualify = (
        delayed
        & status_f
        & nation_ok
        & (cnt_lines[lo] - same_lines > 0)
        & (cnt_delayed[lo] - same_delayed == 0)
    )
    numwait = np.bincount(li["l_suppkey"][qualify], minlength=num_sup)
    sel = numwait > 0
    return _topk(numwait[sel].astype(np.float64), np.nonzero(sel)[0], k)


def assert_topk_matches(values, keys, valid, oracle_values, oracle_keys,
                        rtol=2e-3, atol=1e-2):
    """Compare a plan TopK (values desc, key asc ties) against the float64
    numpy oracle.  Positionwise value check + key-set check with tolerance
    for rank flips between near-equal float32/float64 aggregates."""
    values = np.asarray(values, np.float64)
    keys = np.asarray(keys, np.int64)
    valid = np.asarray(valid, bool)
    n_valid = int(valid.sum())
    ov = np.asarray(oracle_values, np.float64)
    ok = np.asarray(oracle_keys, np.int64)
    o_valid = np.isfinite(ov)
    n_oracle = int(o_valid.sum())
    # the plan may be capped below the oracle's k on tiny data; compare the
    # overlapping prefix
    n = min(n_valid, n_oracle) if len(values) != len(ov) else max(n_valid, n_oracle)
    assert n_valid >= min(n, n_oracle), (
        f"plan found {n_valid} rows, oracle {n_oracle}"
    )
    pv, pk = values[:n], keys[:n]
    qv, qk = ov[:n], ok[:n]
    np.testing.assert_allclose(pv, qv, rtol=rtol, atol=atol)
    mismatched = pk != qk
    if mismatched.any():
        # allow key mismatches only where the values tie within tolerance
        tied = np.isclose(pv, qv, rtol=rtol, atol=atol)
        assert (mismatched <= tied).all(), (
            f"key mismatch outside value ties:\nplan {list(zip(pk, pv))}\n"
            f"oracle {list(zip(qk, qv))}"
        )
        # and the key multisets must still agree on the tied region
        assert sorted(pk.tolist()) == sorted(qk.tolist()) or np.allclose(
            np.sort(pv), np.sort(qv), rtol=rtol, atol=atol
        )


ALL = {
    "q1": q1, "q2": q2, "q3": q3, "q4": q4, "q5": q5, "q6": q6, "q11": q11,
    "q13": q13, "q14": q14, "q15": q15, "q18": q18, "q21": q21,
}
