"""The §3.2.2 selectivity model: predicate selectivities, semi-join
alternative choice, and exchange buffer capacities derived from them.

The paper sizes its communication buffers from the expected number of
surviving keys after local filtering (n requests over a remote table of m
rows; §3.2.2 gives the bits-communicated model, ``repro.core.compression``
implements it).  Plans here are static-shape SPMD programs, so the same
estimate must become a COMPILE-TIME buffer capacity: we take the expected
per-destination message count under uniform key routing (a binomial with
mean ``e = n_local / P``), add a 6-sigma tail margin plus a constant floor,
and round up to a power of two.  Overflow flags in the exchange layer
surface any under-estimate at run time instead of corrupting results.
"""
from __future__ import annotations

import bisect
import dataclasses
import math
from typing import Callable, Mapping, Optional

from repro.core.exchange import WireFormat
from repro.query.ir import (
    Bin,
    BinOp,
    Col,
    ColumnStats,
    Expr,
    Lit,
    PackedInfo,
    Param,
    UnaryOp,
    expr_columns,
    normalize_comparison,
)

# Selinger-style default for predicates the model cannot see through
# (column-vs-column comparisons, opaque expressions).
DEFAULT_SELECTIVITY = 1.0 / 3.0


def next_pow2(n: int) -> int:
    return 1 << max(0, int(n - 1).bit_length())


def capacity_for(expected: float, *, floor: int = 64) -> int:
    """Static per-destination buffer capacity for an expected message count:
    mean + 6*sqrt(mean) binomial tail margin + constant slack, rounded up to
    a power of two (fixed shapes; see DESIGN.md on static shapes)."""
    e = max(float(expected), 0.0)
    need = e + 6.0 * math.sqrt(e) + 16.0
    return next_pow2(max(floor, math.ceil(need)))


def _range_fraction(st: ColumnStats, op: str, v: float) -> float:
    """Fraction of a uniform [lo, hi] domain satisfying ``col op v``."""
    lo, hi = st.lo, st.hi
    if hi <= lo:
        return 1.0
    integral = st.n_distinct > 0
    span = (hi - lo + 1.0) if integral else (hi - lo)
    if op == "<":
        frac = (v - lo) / span
    elif op == "<=":
        frac = (v - lo + (1.0 if integral else 0.0)) / span
    elif op == ">":
        frac = (hi - v) / span
    elif op == ">=":
        frac = (hi - v + (1.0 if integral else 0.0)) / span
    else:
        return DEFAULT_SELECTIVITY
    return min(1.0, max(0.0, frac))


def estimate_selectivity(pred: Expr, stats: Mapping[str, ColumnStats],
                         binding=None) -> float:
    """Estimated fraction of rows satisfying ``pred`` under independence +
    uniformity (the paper's model; good enough to size buffers, and the
    run-time overflow flag catches the rest).

    Parameterized comparisons (``col op Param``) are resolved in order of
    preference: the value from ``binding`` when one is supplied (the
    prepare-time defaults of an auto-parameterized literal query), else
    the WORST binding in the parameter's declared ``lo``/``hi`` range
    (range selectivity is monotone in the bound, so the worst case sits at
    an endpoint), else a fully conservative 1.0 — a prepared plan's
    exchange capacities must stay sound for every future binding."""
    if isinstance(pred, BinOp):
        if pred.op == "and":
            return (estimate_selectivity(pred.lhs, stats, binding)
                    * estimate_selectivity(pred.rhs, stats, binding))
        if pred.op == "or":
            a = estimate_selectivity(pred.lhs, stats, binding)
            b = estimate_selectivity(pred.rhs, stats, binding)
            return min(1.0, a + b - a * b)
        norm = normalize_comparison(pred)
        if norm is not None:
            col, op, v = norm
            st = stats.get(col)
            if st is None:
                return 1.0 if isinstance(v, Param) else DEFAULT_SELECTIVITY
            if op == "==":
                # value-independent under the distinct-count model, so a
                # parameterized equality needs no binding
                return 1.0 / st.n_distinct if st.n_distinct else DEFAULT_SELECTIVITY
            if op == "!=":
                return 1.0 - (1.0 / st.n_distinct) if st.n_distinct else DEFAULT_SELECTIVITY
            if isinstance(v, Param):
                if binding is not None and v.name in binding:
                    v = binding[v.name]
                elif v.lo is not None and v.hi is not None:
                    return max(_range_fraction(st, op, float(v.lo)),
                               _range_fraction(st, op, float(v.hi)))
                else:
                    return 1.0
            try:
                return _range_fraction(st, op, float(v))
            except (TypeError, ValueError):
                return DEFAULT_SELECTIVITY
        return DEFAULT_SELECTIVITY
    if isinstance(pred, UnaryOp) and pred.op == "not":
        return 1.0 - estimate_selectivity(pred.operand, stats, binding)
    if isinstance(pred, Col):
        # bare boolean column: no histogram, assume an even split
        return 0.5
    if isinstance(pred, (Lit, Bin, Param)):
        return DEFAULT_SELECTIVITY
    return DEFAULT_SELECTIVITY


def request_capacity(table_rows: int, selectivity: float, num_nodes: int) -> int:
    """Capacity for an Alt-1 request / owner-routed exchange: each node
    ships ``rows/P * sel`` keys, spread uniformly over P destinations."""
    n_local = (table_rows / max(num_nodes, 1)) * min(max(selectivity, 0.0), 1.0)
    return capacity_for(n_local / max(num_nodes, 1))


# ---------------------------------------------------------------------------
# compressed residency: code-space predicate rewrite + per-column scan
# strategy.  A comparison against a constant/parameter rewrites into an
# inclusive code-range test ``lo <= code <= hi`` (optionally negated) over
# the packed words — frame-of-reference columns by integer arithmetic on
# the offset, dictionary columns by binary search over the sorted values.
# Anything else (column-vs-column, arithmetic on the column) forces an
# eager full-column decode; the SCAN001 verifier rule reports those.
# ---------------------------------------------------------------------------

_I32_MIN, _I32_MAX = -(2 ** 31), 2 ** 31 - 1


def _clamp_i32(v: float) -> int:
    return int(min(max(v, _I32_MIN), _I32_MAX))


@dataclasses.dataclass(frozen=True)
class ScanRewrite:
    """A predicate rewritten into code space: ``bounds(params)`` yields
    the inclusive (lo, hi) code range (python ints for literal
    predicates, traced int32 scalars for parameterized ones)."""

    column: str
    negate: bool
    describe: str
    bounds: Callable

    def static_bounds(self) -> Optional[tuple]:
        """(lo, hi) when the predicate is literal (binding-free);
        None for parameterized rewrites."""
        try:
            lo, hi = self.bounds(None)
        except Exception:
            return None
        if isinstance(lo, int) and isinstance(hi, int):
            return lo, hi
        return None


def _for_bounds(op: str, v, offset: int, maxc: int):
    """Inclusive code bounds of ``x op v`` over FOR codes ``x - offset``.
    ``v`` may be a python scalar (static) or a traced jnp scalar."""
    if isinstance(v, (int, float)):
        fl, ce = math.floor(v), math.ceil(v)
        if op == "<=":
            return 0, _clamp_i32(fl - offset)
        if op == "<":
            return 0, _clamp_i32(ce - 1 - offset)
        if op == ">=":
            return _clamp_i32(ce - offset), maxc
        if op == ">":
            return _clamp_i32(fl + 1 - offset), maxc
        # == / != : a non-integral value matches nothing (negation of an
        # empty range is everything, which the negate flag handles)
        if fl == v:
            c = _clamp_i32(fl - offset)
            return c, c
        return 0, -1
    import jax.numpy as jnp

    fl = jnp.floor(v).astype(jnp.int32)
    ce = jnp.ceil(v).astype(jnp.int32)
    off = jnp.int32(offset)
    if op == "<=":
        return jnp.int32(0), fl - off
    if op == "<":
        return jnp.int32(0), ce - jnp.int32(1) - off
    if op == ">=":
        return ce - off, jnp.int32(maxc)
    if op == ">":
        return fl + jnp.int32(1) - off, jnp.int32(maxc)
    exact = fl.astype(v.dtype if hasattr(v, "dtype") else jnp.float32) == v
    c = fl - off
    return (jnp.where(exact, c, 0).astype(jnp.int32),
            jnp.where(exact, c, -1).astype(jnp.int32))


def _dict_bounds(op: str, v, values: tuple):
    """Inclusive code bounds of ``x op v`` over dictionary positions in
    the sorted ``values``."""
    k = len(values)
    if isinstance(v, (int, float)):
        left = bisect.bisect_left(values, v)
        right = bisect.bisect_right(values, v)
        if op == "<=":
            return 0, right - 1
        if op == "<":
            return 0, left - 1
        if op == ">=":
            return left, k - 1
        if op == ">":
            return right, k - 1
        if right > left:  # == / != : present in the dictionary?
            return left, left
        return 0, -1
    import jax.numpy as jnp
    import numpy as np

    va = jnp.asarray(np.asarray(values))
    vv = jnp.asarray(v).astype(va.dtype)
    left = jnp.searchsorted(va, vv, side="left").astype(jnp.int32)
    right = jnp.searchsorted(va, vv, side="right").astype(jnp.int32)
    if op == "<=":
        return jnp.int32(0), right - jnp.int32(1)
    if op == "<":
        return jnp.int32(0), left - jnp.int32(1)
    if op == ">=":
        return left, jnp.int32(k - 1)
    if op == ">":
        return right, jnp.int32(k - 1)
    found = right > left
    return (jnp.where(found, left, 0).astype(jnp.int32),
            jnp.where(found, left, -1).astype(jnp.int32))


def scan_rewrite(conjunct: Expr,
                 packed: Mapping[str, PackedInfo]) -> Optional[ScanRewrite]:
    """Rewrite one filter conjunct into a code-space range test over a
    packed column, or None when the shape does not admit it (not a
    ``col op scalar`` comparison, or the column is not packed-resident)."""
    norm = normalize_comparison(conjunct)
    if norm is None:
        return None
    col, op, v = norm
    info = packed.get(col)
    if info is None:
        return None
    negate = op == "!="
    cmp_op = "==" if negate else op
    maxc = (1 << info.width) - 1

    if isinstance(v, Param):
        param = v

        def bounds(params):
            if params is None or param.name not in params:
                raise KeyError(param.name)
            pv = params[param.name]
            if info.values is not None:
                return _dict_bounds(cmp_op, pv, info.values)
            return _for_bounds(cmp_op, pv, info.offset, maxc)

        vs = f"${param.name}"
    else:
        if not isinstance(v, (int, float, bool)):
            return None
        if info.values is not None:
            lo, hi = _dict_bounds(cmp_op, v, info.values)
        else:
            lo, hi = _for_bounds(cmp_op, v, info.offset, maxc)

        def bounds(params, _lo=lo, _hi=hi):
            return _lo, _hi

        vs = repr(v)
    kind = "dict" if info.values is not None else "for"
    return ScanRewrite(
        column=col, negate=negate,
        describe=f"{col}{op}{vs} -> {kind} code range", bounds=bounds)


@dataclasses.dataclass(frozen=True)
class ScanDecision:
    """Per-(filter conjunct, packed column) scan strategy, decided at
    lower time by the :mod:`repro.core.scancal` roofline and rendered by
    EXPLAIN."""

    table: str
    column: str
    mode: str                      # 'packed' | 'decode'
    width: int
    rows_per_node: int
    scan_bytes: int                # predicted bytes scanned per node
    raw_bytes: int                 # raw-residency bytes for the same scan
    rewrite: Optional[ScanRewrite] = None
    reason: str = ""

    @property
    def rewritable(self) -> bool:
        return self.rewrite is not None


def decide_scan_conjunct(conjunct: Expr, table_name: str,
                         packed: Mapping[str, PackedInfo],
                         rows_per_node: int, *, cal) -> list:
    """Scan strategy for one filter conjunct over a packed-resident base
    table: one :class:`ScanDecision` per packed column the conjunct
    touches.  Rewritable predicates go packed iff the roofline says the
    saved bandwidth beats the in-place ALU cost; non-rewritable shapes
    are 'decode' (SCAN001 territory)."""
    from repro.core import scancal

    touched = [c for c in sorted(expr_columns(conjunct)) if c in packed]
    if not touched:
        return []
    rewrite = scan_rewrite(conjunct, packed)
    out = []
    for cname in touched:
        info = packed[cname]
        itemsize = 1 if info.dtype == "bool" else 4
        pb = scancal.packed_scan_bytes(rows_per_node, info.width)
        db = scancal.decode_scan_bytes(rows_per_node, info.width, itemsize)
        raw = rows_per_node * itemsize
        if rewrite is not None and rewrite.column == cname:
            mode = scancal.choose_scan_mode(rows_per_node, info.width,
                                            itemsize, cal=cal)
            out.append(ScanDecision(
                table=table_name, column=cname, mode=mode, width=info.width,
                rows_per_node=rows_per_node,
                scan_bytes=pb if mode == "packed" else db, raw_bytes=raw,
                rewrite=rewrite,
                reason=(rewrite.describe if mode == "packed"
                        else "roofline prefers decode")))
        else:
            out.append(ScanDecision(
                table=table_name, column=cname, mode="decode",
                width=info.width, rows_per_node=rows_per_node,
                scan_bytes=db, raw_bytes=raw, rewrite=None,
                reason="predicate not rewritable into code space"))
    return out


def merge_rewrites(a: ScanRewrite, b: ScanRewrite) -> ScanRewrite:
    """Intersect two non-negated code-space range tests over the SAME
    column into one: ``a AND b`` holds iff the code lies in
    ``[max(lo_a, lo_b), min(hi_a, hi_b)]`` — one kernel scan instead of
    two passes over the packed words."""
    assert a.column == b.column and not a.negate and not b.negate

    def bounds(params, _a=a, _b=b):
        lo1, hi1 = _a.bounds(params)
        lo2, hi2 = _b.bounds(params)
        if all(isinstance(v, (int, float)) for v in (lo1, hi1, lo2, hi2)):
            return max(lo1, lo2), min(hi1, hi2)
        import jax.numpy as jnp

        return (jnp.maximum(jnp.asarray(lo1, jnp.int32),
                            jnp.asarray(lo2, jnp.int32)),
                jnp.minimum(jnp.asarray(hi1, jnp.int32),
                            jnp.asarray(hi2, jnp.int32)))

    return ScanRewrite(column=a.column, negate=False,
                       describe=f"{a.describe} & {b.describe}",
                       bounds=bounds)


def merge_scan_conjuncts(per: list) -> list:
    """Fuse a filter's same-column range tests into single scans.

    Input: ``[(conjunct, [ScanDecision, ...]), ...]`` as produced per
    filter by :func:`decide_scan_conjunct`.  Output has the shape
    ``[(conjuncts_tuple, [ScanDecision, ...]), ...]``: entries whose
    decision is a non-negated packed-mode rewrite over the same column
    collapse into one entry carrying all their conjuncts and a merged
    rewrite (bounds intersected), so e.g. ``lo <= c AND c < hi`` costs
    ONE pass over the packed words.  Everything else — negated tests,
    decode-mode or non-rewritable decisions — passes through unchanged
    with a 1-tuple of its conjunct."""
    out = []
    by_col = {}
    for conj, ds in per:
        d = ds[0] if len(ds) == 1 else None
        mergeable = (d is not None and d.mode == "packed"
                     and d.rewrite is not None and not d.rewrite.negate)
        if not mergeable:
            out.append(((conj,), ds))
            continue
        i = by_col.get(d.column)
        if i is None:
            by_col[d.column] = len(out)
            out.append(((conj,), ds))
        else:
            conjs0, ds0 = out[i]
            d0 = ds0[0]
            merged = merge_rewrites(d0.rewrite, d.rewrite)
            out[i] = (conjs0 + (conj,), [dataclasses.replace(
                d0, rewrite=merged, reason=merged.describe)])
    return out


def wire_format_for(table_rows: int, num_nodes: int,
                    kind: str = "packed", *, capacity: int = 0,
                    cal=None) -> WireFormat:
    """Wire format of an exchange addressing the owners of a table
    range-partitioned over ``num_nodes``: the per-destination key domain is
    ``rows_per_node`` and its catalog-derived ``required_width`` fixes the
    packed key width (``repro.core.compression``).

    ``kind="auto"`` asks the LATENCY model: packed only when the roofline
    (``repro.core.wirecal``) predicts the codec time is bought back by the
    byte reduction — i.e. the exchange is network-bound, not codec-bound.
    Requires the exchange ``capacity``; ``cal`` defaults to the persisted
    (or builtin) machine calibration."""
    if kind == "auto":
        from repro.core import wirecal

        wf = WireFormat.packed_for(table_rows, num_nodes)
        kind = wirecal.choose_wire_kind(
            int(capacity), num_nodes, wf.domain,
            cal=cal if cal is not None else wirecal.load())
        return wf if kind == "packed" else WireFormat.raw()
    if kind != "packed":
        return WireFormat.raw()
    return WireFormat.packed_for(table_rows, num_nodes)
