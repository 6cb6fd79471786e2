"""Plain float64 reference answers for the benchmark's TPC-H requests.

The same semantics as the float64 oracles of ``repro.tpch.reference`` and
the query definitions of ``repro.tpch.queries`` (Q1, Q4, Q6, the Q14
promotion revenue, Q18 and the three cube-serving queries), written again
here in numpy over :mod:`bench.reference.tpch_data` tables.  Nothing of the
program is imported.

A run asks for one answer per request, and the parameterized queries get a
fresh TPC-H §2.4 draw each time, so those three are evaluated over a
pre-aggregation: the rows are summed once per distinct value of the
columns their predicate reads (ship day, discount, quantity, promo flag),
and each binding then applies its predicate to those distinct values.
That is the same selection as the row-wise filter, in a few milliseconds.

``round_inputs`` rounds every float input column through a lower
precision first (the control: the reference computed as a bfloat16
contraction would see its inputs).
"""
from __future__ import annotations

import dataclasses

import numpy as np

from bench.reference.tpch_data import PROMO_TYPES, day

Q1_CUT = day(1998, 12, 1)


@dataclasses.dataclass(frozen=True)
class Defaults:
    """TPC-H validation-run substitution values (§2.4) on the schema's day
    numbers, as the engine's registry queries state them."""

    q1_shipdate_max: int = Q1_CUT - 90
    q4_date_min: int = day(1993, 7, 1)
    q4_date_max: int = day(1993, 10, 1)
    q18_quantity: float = 300.0
    q18_k: int = 100


DP = Defaults()


def month_edges(extra=()) -> np.ndarray:
    """Last day of every month 1992-01..1998-12, plus ``extra`` cut points;
    bin ``j`` holds ``(edges[j-1], edges[j]]``."""
    edges = set(extra)
    for y in range(1992, 1999):
        for m in range(1, 13):
            nxt = (y + 1, 1) if m == 12 else (y, m + 1)
            edges.add(day(nxt[0], nxt[1], 1) - 1)
    return np.asarray(sorted(edges), np.int64)


def topk(values, keys, k):
    """(value desc, key asc) ranking, padded with (-inf, -1) to k rows."""
    values = np.asarray(values, np.float64)
    keys = np.asarray(keys, np.int64)
    order = np.lexsort((keys, -values))[:k]
    out_v = np.full(k, -np.inf)
    out_k = np.full(k, -1, np.int64)
    out_v[:len(order)] = values[order]
    out_k[:len(order)] = keys[order]
    return out_v, out_k


def _codes(col):
    """Distinct values of a column and each row's index into them.  The
    values are found on a sample and checked on every row, which is much
    faster than sorting the column; a column the sample misses is sorted."""
    vals = np.unique(col[::max(1, len(col) // 100_000)])
    code = np.minimum(np.searchsorted(vals, col), len(vals) - 1)
    if not np.array_equal(vals[code], col):
        vals, code = np.unique(col, return_inverse=True)
    return vals, code.reshape(-1)


class Reference:
    """Expected answers over one seed's tables (``tpch_data.generate``)."""

    def __init__(self, tables: dict, round_inputs=None):
        li = dict(tables["lineitem"])
        orders = dict(tables["orders"])
        if round_inputs is not None:
            for cols in (li, orders):
                for c, v in cols.items():
                    if v.dtype == np.float32:
                        cols[c] = v.astype(round_inputs).astype(np.float32)
        self.li, self.orders = li, orders
        self.p_type = tables["part"]["p_type"]
        self.memo = {}
        self._f64 = {}
        self._q1 = self._q6 = self._q14 = None

    def f64(self, c):
        if c not in self._f64:
            self._f64[c] = self.li[c].astype(np.float64)
        return self._f64[c]

    # -- pre-aggregations of the parameterized queries ---------------------
    def _q1_table(self):
        """(6 groups, ship days, 6 measures) sums; group = flag*2+status."""
        if self._q1 is None:
            li = self.li
            ndays = int(li["l_shipdate"].max()) + 1
            idx = ((li["l_returnflag"] * 2 + li["l_linestatus"]).astype(
                np.int64) * ndays + li["l_shipdate"])
            price, disc = self.f64("l_extendedprice"), self.f64("l_discount")
            disc_price = price * (1 - disc)
            charge = disc_price * (1 + self.f64("l_tax"))
            measures = (self.f64("l_quantity"), price, disc_price, charge,
                        disc, None)
            t = np.stack([np.bincount(idx, weights=w, minlength=6 * ndays)
                          for w in measures], axis=-1)
            self._q1 = t.reshape(6, ndays, 6)
        return self._q1

    def _q6_table(self):
        if self._q6 is None:
            li = self.li
            dvals, dcode = _codes(li["l_discount"])
            qvals, qcode = _codes(li["l_quantity"])
            ndays = int(li["l_shipdate"].max()) + 1
            idx = ((li["l_shipdate"].astype(np.int64) * len(dvals) + dcode)
                   * len(qvals) + qcode)
            rev = self.f64("l_extendedprice") * self.f64("l_discount")
            sums = np.bincount(idx, weights=rev,
                               minlength=ndays * len(dvals) * len(qvals))
            self._q6 = (sums.reshape(ndays, len(dvals), len(qvals)), dvals,
                        qvals)
        return self._q6

    def _q14_table(self):
        if self._q14 is None:
            li = self.li
            ndays = int(li["l_shipdate"].max()) + 1
            promo = (self.p_type < PROMO_TYPES)[li["l_partkey"]]
            rev = self.f64("l_extendedprice") * (1 - self.f64("l_discount"))
            idx = li["l_shipdate"].astype(np.int64) * 2 + promo
            self._q14 = np.bincount(idx, weights=rev,
                                    minlength=2 * ndays).reshape(ndays, 2)
        return self._q14

    # -- answers -------------------------------------------------------------
    def q1(self, b):
        return self._q1_table()[:, :b["q1_shipdate_max"] + 1].sum(axis=1)

    def q6(self, b):
        sums, dvals, qvals = self._q6_table()
        days = sums[max(b["q6_date_min"], 0):max(b["q6_date_max"], 0)]
        # the predicate on each distinct (float32) value, as on each row
        d_ok = (dvals >= np.float32(b["q6_disc_min"])) & (
            dvals <= np.float32(b["q6_disc_max"]))
        q_ok = qvals < np.float32(b["q6_quantity"])
        return np.asarray([[days[:, d_ok][:, :, q_ok].sum()]])

    def q14_promo(self, b):
        t = self._q14_table()
        return np.asarray([[t[max(b["q14_date_min"], 0):
                              max(b["q14_date_max"], 0), 1].sum()]])

    def q1_cube(self, b=None):
        return self.q1({"q1_shipdate_max": DP.q1_shipdate_max})

    def q1_offedge(self, b=None):
        full = self.q1({"q1_shipdate_max": DP.q1_shipdate_max - 1})
        return full[:, [0, 5]]  # sum_qty, count_order

    def q4(self, b=None):
        o, li = self.orders, self.li
        o_ok = ((o["o_orderdate"] >= DP.q4_date_min)
                & (o["o_orderdate"] < DP.q4_date_max))
        has_late = np.zeros(o["o_orderkey"].shape[0], bool)
        has_late[li["l_orderkey"][li["l_commitdate"] < li["l_receiptdate"]]] = True
        counts = np.bincount(o["o_orderpriority"][o_ok & has_late],
                             minlength=5).astype(np.float64)
        return counts.reshape(5, 1)

    def q18(self, b=None):
        o, li = self.orders, self.li
        qty = np.bincount(li["l_orderkey"], weights=self.f64("l_quantity"),
                          minlength=o["o_orderkey"].shape[0])
        sel = qty > DP.q18_quantity
        return topk(o["o_totalprice"].astype(np.float64)[sel],
                    o["o_orderkey"][sel], DP.q18_k)

    def revenue_by_shipmonth(self, b=None):
        li = self.li
        edges = month_edges(extra=(DP.q1_shipdate_max,))
        code = np.searchsorted(edges, li["l_shipdate"], side="left")
        n = len(edges) + 1
        rev = self.f64("l_extendedprice") * (1 - self.f64("l_discount"))
        return np.stack([np.bincount(code, weights=rev, minlength=n),
                         np.bincount(code, minlength=n).astype(np.float64)],
                        axis=-1)

    def orders_by_priority(self, b=None):
        o = self.orders
        sel = ((o["o_orderdate"] >= DP.q4_date_min)
               & (o["o_orderdate"] < DP.q4_date_max))
        pri = o["o_orderpriority"][sel]
        price = o["o_totalprice"][sel].astype(np.float64)
        return np.stack([np.bincount(pri, minlength=5).astype(np.float64),
                         np.bincount(pri, weights=price, minlength=5)],
                        axis=-1)

    def answer(self, name: str, binding=None):
        """Expected value of query ``name`` under ``binding`` (memoized)."""
        key = (name, tuple(sorted((binding or {}).items())))
        if key not in self.memo:
            self.memo[key] = getattr(self, name)(binding)
        return self.memo[key]
