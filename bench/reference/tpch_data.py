"""The benchmark's own TPC-H data: the tables a run's answers are checked on.

A copy of the deterministic generator the engine loads from
(``repro.tpch.dbgen``: per-node chunks, each from a seed sequence keyed by
``(seed, table, node)``), kept here so the yardstick imports nothing of the
program and takes none of its tables.  The engine is expected to hold
exactly these tables for a seed; a program whose data drifts from them
reads as incorrect.  Only the raw host tables are built (no packing), as
plain ``{table: {column: ndarray}}`` dicts.
"""
from __future__ import annotations

import datetime

import numpy as np

EPOCH = datetime.date(1992, 1, 1)


def day(y: int, m: int, d: int) -> int:
    """Days since 1992-01-01 (the TPC-H date domain of the schema)."""
    return (datetime.date(y, m, d) - EPOCH).days


BASE_ROWS = {"orders": 1_500_000, "customer": 150_000, "part": 200_000,
             "supplier": 10_000}
LINEITEM_FANOUT_AVG = 4
SUPPLIERS_PER_PART = 4
NUM_TYPES = 150
PROMO_TYPES = 25          # p_type < 25 <=> 'PROMO%'
NUM_SEGMENTS = 5
NUM_PRIORITIES = 5
STATUS_CUTOFF = day(1995, 6, 17)
PARTITIONED_TABLES = ("supplier", "customer", "part", "partsupp", "orders",
                      "lineitem")


def table_sizes(sf: float, num_nodes: int) -> dict:
    sizes = {}
    for name, base in BASE_ROWS.items():
        per_node = max(32, int(round(base * sf / num_nodes)))
        sizes[name] = per_node * num_nodes
    sizes["partsupp"] = sizes["part"] * SUPPLIERS_PER_PART
    sizes["lineitem"] = sizes["orders"] * LINEITEM_FANOUT_AVG
    return sizes


def _rng(seed: int, table: str, node: int) -> np.random.Generator:
    ss = np.random.SeedSequence([seed, PARTITIONED_TABLES.index(table), node])
    return np.random.default_rng(ss)


def _supplier(rng, n, base):
    key = base + np.arange(n, dtype=np.int32)
    return {
        "s_suppkey": key,
        "s_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "s_acctbal": rng.uniform(-999.99, 9999.99, n).astype(np.float32),
        "s_name_code": key,
        "s_address_code": rng.integers(0, 1 << 30, n).astype(np.int32),
        "s_phone_code": rng.integers(0, 1 << 30, n).astype(np.int32),
    }


def _customer(rng, n, base):
    key = base + np.arange(n, dtype=np.int32)
    return {
        "c_custkey": key,
        "c_nationkey": rng.integers(0, 25, n).astype(np.int32),
        "c_mktsegment": rng.integers(0, NUM_SEGMENTS, n).astype(np.int32),
        "c_name_code": key,
        "c_acctbal": rng.uniform(-999.99, 9999.99, n).astype(np.float32),
    }


def _part(rng, n, base):
    key = base + np.arange(n, dtype=np.int32)
    return {
        "p_partkey": key,
        "p_size": rng.integers(1, 51, n).astype(np.int32),
        "p_type": rng.integers(0, NUM_TYPES, n).astype(np.int32),
        "p_mfgr": rng.integers(0, 5, n).astype(np.int32),
        "p_retailprice": (900.0 + (key % 1000)
                          + 100.0 * rng.random(n)).astype(np.float32),
        "p_name_code": key,
    }


def _partsupp(rng, n_parts, part_base, num_suppliers):
    pk = np.repeat(part_base + np.arange(n_parts, dtype=np.int32),
                   SUPPLIERS_PER_PART)
    n = pk.shape[0]
    return {
        "ps_partkey": pk,
        "ps_suppkey": rng.integers(0, num_suppliers, n).astype(np.int32),
        "ps_supplycost": rng.uniform(1.0, 1000.0, n).astype(np.float32),
        "ps_availqty": rng.integers(1, 10_000, n).astype(np.float32),
    }


def _orders_and_lineitem(rng, n_orders, order_base, num_customers,
                         num_parts, num_suppliers):
    okey = order_base + np.arange(n_orders, dtype=np.int32)
    odate = rng.integers(0, day(1998, 8, 2), n_orders).astype(np.int32)
    # 1..7 lineitems per order, corrected so the total is exactly
    # LINEITEM_FANOUT_AVG per order
    target = LINEITEM_FANOUT_AVG * n_orders
    nl = rng.integers(1, 8, n_orders).astype(np.int64)
    diff = int(target - nl.sum())
    order_ids = np.arange(n_orders)
    rng.shuffle(order_ids)
    step = 1 if diff > 0 else -1
    idx = 0
    while diff != 0:
        o = order_ids[idx % n_orders]
        nv = nl[o] + step
        if 1 <= nv <= 7:
            nl[o] = nv
            diff -= step
        idx += 1

    local = np.repeat(np.arange(n_orders, dtype=np.int32), nl)
    n_li = local.shape[0]
    l_odate = odate[local]
    qty = rng.integers(1, 51, n_li).astype(np.float32)
    price_base = rng.uniform(900.0, 2000.0, n_li).astype(np.float32)
    extprice = (qty * price_base).astype(np.float32)
    disc = (rng.integers(0, 11, n_li) / 100.0).astype(np.float32)
    tax = (rng.integers(0, 9, n_li) / 100.0).astype(np.float32)
    shipdate = (l_odate + rng.integers(1, 122, n_li)).astype(np.int32)
    commitdate = (l_odate + rng.integers(30, 91, n_li)).astype(np.int32)
    receiptdate = (shipdate + rng.integers(1, 31, n_li)).astype(np.int32)
    linestatus = (shipdate > STATUS_CUTOFF).astype(np.int32)
    returnflag = np.where(receiptdate <= STATUS_CUTOFF,
                          rng.integers(0, 2, n_li),
                          2 * np.ones(n_li, dtype=np.int64)).astype(np.int32)
    returnflag = np.where(rng.random(n_li) < 0.33, 1,
                          returnflag).astype(np.int32)
    lineitem = {
        "l_orderkey": okey[local],
        "l_partkey": rng.integers(0, num_parts, n_li).astype(np.int32),
        "l_suppkey": rng.integers(0, num_suppliers, n_li).astype(np.int32),
        "l_quantity": qty,
        "l_extendedprice": extprice,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": linestatus,
        "l_shipdate": shipdate,
        "l_commitdate": commitdate,
        "l_receiptdate": receiptdate,
    }
    charge = extprice * (1.0 - disc) * (1.0 + tax)
    # in index order, as an unbuffered scatter-add would sum
    totalprice = np.bincount(local, weights=charge.astype(np.float64),
                             minlength=n_orders)
    orders = {
        "o_orderkey": okey,
        "o_custkey": rng.integers(0, num_customers, n_orders).astype(np.int32),
        "o_orderdate": odate,
        "o_orderpriority": rng.integers(0, NUM_PRIORITIES,
                                        n_orders).astype(np.int32),
        "o_orderstatus": rng.integers(0, 3, n_orders).astype(np.int32),
        "o_totalprice": totalprice.astype(np.float32),
        "o_comment_special": rng.random(n_orders) < 0.02,
    }
    return orders, lineitem


def _node(sf: float, node: int, num_nodes: int, seed: int,
          tables) -> dict:
    """One node's chunk of ``tables``.  Every table draws from a stream of
    its own, so leaving one out changes none of the others."""
    sizes = table_sizes(sf, num_nodes)
    per = {t: sizes[t] // num_nodes for t in BASE_ROWS}
    out = {}
    if "supplier" in tables:
        out["supplier"] = _supplier(_rng(seed, "supplier", node),
                                    per["supplier"], node * per["supplier"])
    if "customer" in tables:
        out["customer"] = _customer(_rng(seed, "customer", node),
                                    per["customer"], node * per["customer"])
    if "part" in tables:
        out["part"] = _part(_rng(seed, "part", node), per["part"],
                            node * per["part"])
    if "partsupp" in tables:
        out["partsupp"] = _partsupp(_rng(seed, "partsupp", node),
                                    per["part"], node * per["part"],
                                    sizes["supplier"])
    if "orders" in tables or "lineitem" in tables:
        out["orders"], out["lineitem"] = _orders_and_lineitem(
            _rng(seed, "orders", node), per["orders"], node * per["orders"],
            sizes["customer"], sizes["part"], sizes["supplier"])
    return out


def generate(sf: float, num_nodes: int, seed: int,
             tables=("orders", "lineitem", "part")) -> dict:
    """The global columns (node chunks concatenated) of ``tables``."""
    chunks = [_node(sf, n, num_nodes, seed, tables) for n in range(num_nodes)]
    return {t: {c: np.concatenate([ch[t][c] for ch in chunks])
                for c in chunks[0][t]} for t in tables}
