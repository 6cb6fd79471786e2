"""Lowering pass: logical IR -> physical SPMD plan.

``lower(query, catalog)`` compiles an IR tree into a plan function with the
engine's standard signature ``plan(ctx, tables)`` — it runs inside
``shard_map`` over the ``nodes`` axis and synchronizes only through the
exchange layer, so ``Cluster.compile`` turns it into ONE SPMD executable
exactly like the hand-written plans (the paper's precompiled query
function).

Physical mapping:

- ``Filter``/``Project``   -> vectorized column ops on the local partition
- ``SemiJoin``             -> local probe for co-partitioned edges, else
  Alt-1 (index-lookup request exchange) or Alt-2 (replicated bitset),
  chosen by the §3.2.2 cost model; exchange buffer capacities come from the
  selectivity model (``repro.query.stats``), not hand knobs.  A local or
  bitset probe after a filter probes only the rows the filter keeps
  (``semijoin.probe_survivors``), unless the plan vmaps its lanes; the
  path of each is ``plan.probes``
- ``Exists``, ``GroupAggByKey`` -> keyed reduction of the co-partitioned
  child into the parent partition: a segmented sum over contiguous runs
  where the catalog finds the child clustered by its foreign key
  (``Catalog.clustered``), else a scatter (``.at[].max`` / ``.at[].add``);
  the path of each is ``plan.keyed``
- ``GroupAgg``             -> one-hot MXU contraction / dense scatter-add /
  the fused Pallas ``grouped_agg`` kernel, merged with one ``psum``
- ``TopK``                 -> per-node top-k + §3.2.3 merging reduction,
  late-materializing fetch attributes (§3.2.7)

Each operator's own work runs in the :func:`repro.obs.layer` scope of
its engine layer, opened after its child is evaluated, so the compiled
operations carry at most one layer in their ``op_name``: ``scan``
(``Filter``, with the packed scan kernel and bitset unpack, and
``Project``), ``semijoin`` (``SemiJoin``, all alternatives, and
``Exists``), ``aggregate`` (``GroupAggByKey`` and the ``GroupAgg`` root
with its ``psum``) and ``topk`` (the ``TopK`` root with its merging
reduction and late materialization).

Lowered plans return a dict: ``{"value"}`` for ``GroupAgg`` roots,
``{"values", "keys", "valid", <fetched attrs>}`` for ``TopK`` roots.  When
(and only when) the plan contains a request exchange, an ``"overflow"``
flag is included: True iff a derived buffer capacity was exceeded at run
time.  The result is then incomplete; recover by re-compiling with an
explicit capacity override in ``PlanContext.capacities`` under the key
``"<query-name>_sj<i>"`` (the i-th request semijoin of the chain) — for
``TPCHDriver``, pass it via the ``capacities=`` constructor argument.
A plan with compacted probes also returns ``"probe_tier"``: for each, the
index of the capacity its survivors took, ``semijoin.FULL_WIDTH`` where
they filled every capacity and every row was probed (not an overflow).

Min/max aggregates are Tier-1-only (rollup cubes serve them); lowering
them raises :class:`LoweringError`.
"""
from __future__ import annotations

import dataclasses
import math

import jax.numpy as jnp
from jax import lax

from repro.core import aggregation, late_materialization, semijoin, topk
from repro.core import compression, scancal, wirecal
from repro.core.columnar import PackedColumn
from repro.core.compression import choose_semijoin_wire
from repro.core.engine import vmaps_lanes
from repro.core.exchange import WireFormat
from repro.obs.trace import layer
from repro.query import stats as qstats
from repro.query.ir import (
    Bin,
    BinOp,
    Catalog,
    Col,
    Exists,
    Filter,
    GroupAgg,
    GroupAggByKey,
    Lit,
    LoweringError,
    Project,
    Query,
    Scan,
    SemiJoin,
    TopK,
    UnaryOp,
    conjuncts,
    eval_expr,
    expr_columns,
    expr_params,
    query_params,
    validate,
)

ONEHOT_MAX_GROUPS = 8192
KERNEL_MAX_GROUPS = 512

# the engine layer (repro.obs.layer scope) of each non-root operator
_LAYERS = {Filter: "scan", Project: "scan", SemiJoin: "semijoin",
           Exists: "semijoin", GroupAggByKey: "aggregate"}


# ---------------------------------------------------------------------------
# static planning: walk the chain once on the host, fix every runtime knob
# ---------------------------------------------------------------------------


def _chain(root) -> list:
    """Operator chain scan-first (every operator here is single-child)."""
    out = []
    node = root
    while not isinstance(node, Scan):
        out.append(node)
        node = node.child
    out.append(node)
    return out[::-1]


@dataclasses.dataclass(frozen=True)
class _SemiJoinPlan:
    alt: str        # local | request | bitset
    capacity: int   # derived request-exchange bucket capacity (0 if unused)
    key: str = ""   # PlanContext.capacities override key ("<name>_sj<i>")
    wire: WireFormat = WireFormat.raw()  # packed format of the exchange
    table: str = ""    # semi-join target table (observability/EXPLAIN)
    gamma: float = 0.0  # predicted target-predicate selectivity
    # model capacity regardless of the chosen alternative — what the
    # request exchange WOULD need under this binding (the static verifier
    # compares it against the compiled capacity for other bindings)
    derived_capacity: int = 0
    # roofline predictions (repro.core.wirecal) for the chosen alternative
    # at its static shapes: codec time vs link volume + collective latency
    codec_ms: float = 0.0
    wire_ms: float = 0.0


def _decide_semijoins(root, catalog: Catalog, query_name=None,
                      wire: str = "packed", binding=None, cal=None,
                      predict_cal=None) -> dict:
    """Choose each SemiJoin's physical alternative and buffer capacity from
    the §3.2.2 model, using selectivities accumulated along the chain.  The
    alternative choice is BYTE-ACCURATE by default: it compares the static
    wire bytes of the compiled Alt-1 exchange — at its derived capacity and
    actual packed widths under ``wire`` — against the Alt-2 bitset
    allgather.  With a ``cal`` (:class:`repro.core.wirecal.WireCalibration`)
    the comparison is LATENCY-accurate (codec + link + per-collective
    roofline), and ``wire="auto"`` lets the same model pick packed vs raw
    per semi-join.  Every decision carries its predicted ``codec_ms`` /
    ``wire_ms`` for EXPLAIN, computed with ``predict_cal`` (else ``cal``,
    else builtin) — a prediction-only calibration NEVER changes the
    decisions, so EXPLAIN can render machine-calibrated estimates for the
    exact plan the byte model compiled.  ``binding`` resolves parameterized
    predicates for the estimates; an unbound param is sized for the worst
    binding in its declared range (see ``repro.query.stats``)."""
    pcal = (predict_cal if predict_cal is not None
            else cal if cal is not None else wirecal.BUILTIN)
    decisions = {}
    base = None
    sel = 1.0
    for node in _chain(root):
        if isinstance(node, Scan):
            base = node.table
            sel = 1.0
            continue
        tinfo = catalog.table(base)
        if isinstance(node, Filter):
            sel *= qstats.estimate_selectivity(node.pred, tinfo.stats, binding)
        elif isinstance(node, Exists):
            sel *= qstats.DEFAULT_SELECTIVITY
        elif isinstance(node, GroupAggByKey):
            base = node.into
            sel = 1.0
        elif isinstance(node, SemiJoin):
            target = catalog.table(node.table)
            gamma = qstats.estimate_selectivity(node.pred, target.stats,
                                                binding)
            edge = catalog.copartitioned.get(base)
            local_ok = (
                edge is not None and edge[0] == node.table
                and isinstance(node.key, Col) and node.key.name == edge[1]
            )
            alt = node.alt
            if alt == "local" and not local_ok:
                raise LoweringError(
                    f"semijoin alt='local' requires {node.table!r} "
                    f"co-partitioned with {base!r} on the key column"
                )
            if local_ok:
                # co-partitioned keys all route to their LOCAL owner when
                # forced through the request exchange — no uniform spread
                # over P destinations, the self-bucket takes everything
                cap = qstats.capacity_for(
                    tinfo.num_rows / max(catalog.num_nodes, 1) * sel
                )
            else:
                cap = qstats.request_capacity(
                    tinfo.num_rows, sel, catalog.num_nodes
                )
            wf = qstats.wire_format_for(
                target.num_rows, catalog.num_nodes, kind=wire,
                capacity=cap, cal=cal,
            )
            if alt == "auto":
                if local_ok:
                    alt = "local"
                else:
                    choice = choose_semijoin_wire(
                        cap, target.num_rows, max(catalog.num_nodes, 1),
                        domain=wf.domain, packed=wf.packed, cal=cal,
                    )
                    alt = "request" if choice == 1 else "bitset"
            P = max(catalog.num_nodes, 1)
            if alt == "request":
                codec_ms, wire_ms = wirecal.predict_alt1_ms(
                    cap, P, wf.domain, packed=wf.packed, cal=pcal)
            elif alt == "bitset":
                codec_ms, wire_ms = wirecal.predict_alt2_ms(
                    target.num_rows, P, cal=pcal)
            else:
                codec_ms, wire_ms = 0.0, 0.0
            decisions[id(node)] = _SemiJoinPlan(
                alt=alt, capacity=cap if alt == "request" else 0,
                key=f"{query_name or 'query'}_sj{len(decisions)}",
                wire=wf, table=node.table, gamma=gamma,
                derived_capacity=cap,
                codec_ms=codec_ms, wire_ms=wire_ms,
            )
            sel *= gamma
    return decisions


def _decide_scans(root, catalog: Catalog) -> dict:
    """Per-Filter predicate-on-packed decisions over compressed-resident
    base tables: each filter conjunct that is a ``col op scalar``
    comparison against a packed column rewrites into a code-space range
    test the scan kernel evaluates on the packed words directly
    (``repro.query.stats.scan_rewrite``); the :mod:`repro.core.scancal`
    roofline, at the rates of the catalog's device kind, arbitrates packed
    vs decode per column.  Same-column range
    tests fuse into one scan (``qstats.merge_scan_conjuncts``).  Returns
    ``{id(filter): [(conjuncts_tuple, [ScanDecision, ...]), ...]}`` for
    filters touching at least one packed column."""
    cal = scancal.for_device(catalog.device_kind)
    decisions = {}
    base = None
    for node in _chain(root):
        if isinstance(node, Scan):
            base = node.table
            continue
        if isinstance(node, GroupAggByKey):
            base = node.into
            continue
        if not isinstance(node, Filter):
            continue
        tinfo = catalog.table(base)
        if not tinfo.packed:
            continue
        rows = tinfo.num_rows // max(catalog.num_nodes, 1)
        per = [(conj, qstats.decide_scan_conjunct(conj, base, tinfo.packed,
                                                  rows, cal=cal))
               for conj in conjuncts(node.pred)]
        if any(ds for _, ds in per):
            decisions[id(node)] = qstats.merge_scan_conjuncts(per)
    return decisions


def _decide_probes(root, sj_plans: dict, lanes_vmapped: bool) -> dict:
    """The path of each local or bitset probe: ``"compact"`` where a
    filter, semi-join or EXISTS has masked the stream before it (only the
    rows it keeps are probed, ``semijoin.probe_survivors``), else
    ``"full"``.  Plans that vmap their lanes probe every row: under
    ``vmap`` the capacity switch would run every branch.  Returns
    ``{id(node): path}`` in chain order."""
    paths = {}
    masked = False
    for node in _chain(root):
        if isinstance(node, (Scan, GroupAggByKey)):
            masked = False
        elif isinstance(node, SemiJoin) and sj_plans[id(node)].alt != "request":
            paths[id(node)] = ("compact" if masked and not lanes_vmapped
                               else "full")
        if isinstance(node, (Filter, SemiJoin, Exists)):
            masked = True
    return paths


def _decide_keyed(root, catalog: Catalog) -> dict:
    """The path of each keyed reduction into a parent (``Exists``,
    ``GroupAggByKey``): ``"clustered"`` where the catalog finds the child
    clustered by its foreign key and the key is the stored column (no
    projection below redefines it), else ``"scatter"``.  Returns
    ``{id(node): path}`` in chain order."""
    paths = {}
    base, projected = None, set()
    for node in _chain(root):
        if isinstance(node, Scan):
            base, projected = node.table, set()
        elif isinstance(node, Project):
            projected.update(name for name, _ in node.cols)
        elif isinstance(node, Exists):
            paths[id(node)] = ("clustered" if catalog.clustered.get(node.table)
                               else "scatter")
        elif isinstance(node, GroupAggByKey):
            paths[id(node)] = (
                "clustered" if catalog.clustered.get(base)
                and node.key.name not in projected else "scatter")
            base, projected = node.into, set()
    return paths


# stable public entry points for the static verifier (repro.query.verify):
# the same decision passes the lowering runs, usable without lowering
decide_semijoins = _decide_semijoins
SemiJoinPlan = _SemiJoinPlan
decide_scans = _decide_scans


def explain_chain(query: Query, catalog: Catalog, *, wire: str = "packed",
                  binding=None, cal=None, predict_cal=None) -> list:
    """Scan-first per-operator annotations for EXPLAIN: each operator as a
    dict carrying the cost model's view of it — predicted selectivity for
    filters/probes, the chosen alternative / derived capacity / wire
    format for semi-joins (exactly what :func:`lower` would decide, via
    the same ``_decide_semijoins`` call), group/agg shape for roots.
    Purely static: nothing is compiled or executed."""
    root = query.root
    validate(root, catalog)
    decisions = _decide_semijoins(root, catalog, query_name=query.name,
                                  wire=wire, binding=binding, cal=cal,
                                  predict_cal=predict_cal)
    scan_plans = _decide_scans(root, catalog)
    keyed = _decide_keyed(root, catalog)
    probes = _decide_probes(root, decisions, lanes_vmapped=False)
    rows = []
    base, sel = None, 1.0
    for node in _chain(root):
        if isinstance(node, Scan):
            base, sel = node.table, 1.0
            tinfo = catalog.table(node.table)
            rows.append({"op": "Scan", "table": node.table,
                         "rows": tinfo.num_rows,
                         "packed_cols": sorted(tinfo.packed)})
            continue
        tinfo = catalog.table(base)
        if isinstance(node, Filter):
            s = qstats.estimate_selectivity(node.pred, tinfo.stats, binding)
            sel *= s
            rows.append({"op": "Filter", "pred": node.pred, "sel": s,
                         "cum_sel": sel,
                         "scans": [d for _, ds in scan_plans.get(id(node), [])
                                   for d in ds]})
        elif isinstance(node, Project):
            rows.append({"op": "Project",
                         "cols": [n for n, _ in node.cols]})
        elif isinstance(node, SemiJoin):
            d = decisions[id(node)]
            sel *= d.gamma
            rows.append({
                "op": "SemiJoin", "table": node.table, "key": node.key,
                "pred": node.pred, "alt": d.alt, "capacity": d.capacity,
                "capacity_key": d.key, "wire": d.wire, "gamma": d.gamma,
                "codec_ms": d.codec_ms, "wire_ms": d.wire_ms,
                "cum_sel": sel, "probe": probes.get(id(node)),
            })
        elif isinstance(node, Exists):
            sel *= qstats.DEFAULT_SELECTIVITY
            rows.append({"op": "Exists", "table": node.table,
                         "sel": qstats.DEFAULT_SELECTIVITY, "cum_sel": sel,
                         "path": keyed[id(node)]})
        elif isinstance(node, GroupAggByKey):
            base, sel = node.into, 1.0
            rows.append({"op": "GroupAggByKey", "into": node.into,
                         "aggs": [a.name for a in node.aggs],
                         "path": keyed[id(node)]})
        elif isinstance(node, GroupAgg):
            groups = math.prod(k.cardinality for k in node.keys) \
                if node.keys else 1
            method = node.method
            if method == "auto":
                method = "onehot" if groups <= ONEHOT_MAX_GROUPS else "dense"
            rows.append({"op": "GroupAgg", "groups": groups,
                         "method": method,
                         "keys": [k.name for k in node.keys],
                         "aggs": [a.name for a in node.aggs]})
        elif isinstance(node, TopK):
            rows.append({"op": "TopK", "k": node.k})
    return rows


def _has_division(e) -> bool:
    """Whether an expression can turn finite inputs non-finite (division).
    Used to gate the batched mask-GEMM: it folds the lane mask in AFTER
    aggregation inputs are built, and 0 * inf = NaN would poison a group
    sum that the pre-masked scalar path computes correctly."""
    if isinstance(e, BinOp):
        return e.op == "/" or _has_division(e.lhs) or _has_division(e.rhs)
    if isinstance(e, UnaryOp):
        return _has_division(e.operand)
    if isinstance(e, Bin):
        return _has_division(e.child)
    return False


def _maskgemm_eligible(root: GroupAgg, num_groups: int) -> bool:
    """The batched ``mask @ (onehot (x) measures)`` GEMM requires the
    expanded tensor to be parameter-independent (else vmap batches it B
    times), bounded (onehot-sized group spaces only), and NaN-safe (no
    division anywhere feeding group codes or measures — the lane mask is
    folded in multiplicatively, after evaluation)."""
    if not 1 < num_groups <= ONEHOT_MAX_GROUPS:
        return False
    exprs = [k.expr for k in root.keys]
    exprs += [a.expr for a in root.aggs if a.expr is not None]
    # projections below the root may feed group keys / measures
    for node in _chain(root)[:-1]:
        if isinstance(node, Project):
            exprs += [e for _, e in node.cols]
    return not any(expr_params(e) or _has_division(e) for e in exprs)


def _kernel_filter(root: GroupAgg) -> tuple:
    """The fused Pallas kernel consumes its filter directly: the chain must
    be Scan -> Filter(Col <= Lit int) -> GroupAgg.  Returns (col, cutoff)."""
    ops_below = _chain(root)[:-1]  # strip GroupAgg
    if len(ops_below) == 2 and isinstance(ops_below[1], Filter):
        p = ops_below[1].pred
        if (isinstance(p, BinOp) and p.op == "<="
                and isinstance(p.lhs, Col) and isinstance(p.rhs, Lit)
                and isinstance(p.rhs.value, int)):
            return p.lhs.name, int(p.rhs.value)
    raise LoweringError(
        "method='kernel' lowers to the fused filter+aggregate Pallas kernel "
        "and requires exactly Scan -> Filter(col <= int) -> GroupAgg"
    )


# ---------------------------------------------------------------------------
# trace-time stream evaluation
# ---------------------------------------------------------------------------


class _LazyCols(dict):
    """Column view over a (possibly packed-resident) local partition.
    Packed columns decode on first touch and the decoded view is cached,
    so a column whose only consumer is the predicate-on-packed kernel is
    NEVER expanded to raw — late materialization at filter granularity.
    ``raw()`` exposes the undecoded resident form for gather/kernel
    consumers."""

    def __getitem__(self, name):
        v = super().__getitem__(name)
        if isinstance(v, PackedColumn):
            v = v.decode()
            super().__setitem__(name, v)
        return v

    def raw(self, name):
        return super().__getitem__(name)


def _col_at(col, idx):
    """Rows ``idx`` of a local column — code-space gather + decode for
    packed residents (touches O(len(idx)) words, not the column); every
    row for ``idx=None``."""
    if idx is None:
        return col.decode() if isinstance(col, PackedColumn) else col
    return col.gather(idx) if isinstance(col, PackedColumn) else col[idx]


@dataclasses.dataclass
class _Stream:
    base: str          # table whose partitioning the stream follows
    cols: dict         # visible columns (local partition views)
    mask: object       # bool array or None
    overflow: object   # python False until an exchange contributes a flag
    probe: tuple = ()  # the tier each compacted probe took
    words: object = None  # the mask's bitset words, where a scan made them

    def and_mask(self, bits, words=None):
        """AND ``bits`` into the mask; ``words`` is their bitset, where the
        packed scan made it (kept while every part of the mask has one)."""
        if self.mask is None:
            self.mask, self.words = bits, words
            return
        self.mask = self.mask & bits
        self.words = (None if words is None or self.words is None
                      else self.words & words)


def _local_index(ctx, table, keys):
    return keys - ctx.part(table).my_base(ctx.axis)


def _measure_stack(aggs, cols, mask, pv=None):
    n = next(iter(cols.values())).shape[0]
    outs = []
    for a in aggs:
        if a.agg == "count":
            v = jnp.ones(n, jnp.float32)
        else:
            v = eval_expr(a.expr, cols, pv).astype(jnp.float32)
        outs.append(v)
    stacked = jnp.stack(outs, axis=1)
    if mask is not None:
        stacked = jnp.where(mask[:, None], stacked, 0.0)
    return stacked


def lower(query: Query, catalog: Catalog, *, wire: str = "packed",
          binding=None, batched: bool = False, obs=None):
    """Compile ``query`` into ``plan(ctx, tables)`` (see module docstring
    for the output contract).  ``wire`` selects the exchange encoding the
    §3.2.2 byte-accurate cost model assumes ("packed" bit-packs request
    keys to catalog-derived widths with the mask folded in; "raw" ships
    int32 buckets + a separate mask collective); the compiled plan applies
    the packed format only when the execution context agrees
    (``PlanContext.wire == "packed"``).

    A query containing :class:`~repro.query.ir.Param` placeholders lowers
    to ``plan(ctx, tables, params)`` — the params become TRACED jit
    arguments (dict name -> scalar), so one compiled executable serves
    every binding; the ordered parameter signature is exposed as
    ``plan.params`` and ``Cluster.compile`` threads the extra argument
    through ``shard_map``.  ``binding`` only feeds the STATIC capacity /
    alternative decisions (never the traced values): pass the prepare-time
    defaults of an auto-parameterized literal query to size its buffers
    exactly as the literal plan would; without it, parameterized
    predicates are sized for the worst binding in their declared range.

    ``batched=True`` tunes the physical choices for a plan that will be
    ``vmap``-ed over a stacked parameter axis (``Cluster.compile(...,
    batch=True)``): a ``method="auto"`` GroupAgg factors its masked
    contraction as ``mask @ (onehot (x) measures)`` — group codes and
    measures are parameter-independent, so vmap keeps the ``n x (G*M)``
    expanded tensor UNBATCHED and B lanes cost ONE ``(B,n) x (n,G*M)``
    GEMM over the lane masks instead of B independently masked pipelines
    (or B scatter passes — XLA has no fast batched segment-sum).
    Explicit methods are honored either way, and shapes the GEMM cannot
    serve soundly (params or division feeding the keys/measures,
    beyond-onehot group spaces) fall back to the plain per-lane
    lowering.

    Raises :class:`IRValidationError` for malformed IR and
    :class:`LoweringError` for valid-but-uncompilable queries (min/max
    aggregates, kernel-ineligible shapes)."""
    root = query.root
    validate(root, catalog)
    params = query_params(root)
    if not isinstance(root, (GroupAgg, TopK)):
        raise LoweringError(
            f"query root must be group_agg or top_k to produce a result set "
            f"(got {type(root).__name__}) — add an aggregation or selection"
        )
    if isinstance(root, GroupAgg):
        bad = [a.name for a in root.aggs if a.agg in ("min", "max")]
        if bad:
            raise LoweringError(
                f"min/max aggregates {bad} are served by Tier-1 rollup cubes "
                f"only; the SPMD lowering supports sum/count — route this "
                f"query through a covering cube or drop the measure"
            )
        num_groups = math.prod(k.cardinality for k in root.keys) if root.keys else 1
        if root.method == "kernel":
            if num_groups > KERNEL_MAX_GROUPS:
                raise LoweringError(
                    f"{num_groups} groups exceeds the grouped_agg kernel "
                    f"limit {KERNEL_MAX_GROUPS}"
                )
            kernel_col, kernel_cutoff = _kernel_filter(root)

    sj_plans = _decide_semijoins(root, catalog, query_name=query.name,
                                 wire=wire, binding=binding)
    scan_plans = _decide_scans(root, catalog)
    keyed = _decide_keyed(root, catalog)
    # the mask-GEMM only pays where the cluster vmaps batch lanes; larger
    # partitions run them one by one (Cluster.compile), where the plain
    # lowering is the same work
    lanes_vmapped = vmaps_lanes(
        max((t.num_rows for t in catalog.tables.values()
             if not t.replicated), default=0) // max(catalog.num_nodes, 1))
    probes = _decide_probes(root, sj_plans, batched and lanes_vmapped)
    if obs is not None:
        obs.event(
            "lower", cat="plan",
            query=query.name or "<lowered-ir>", batched=batched, wire=wire,
            n_params=len(params),
            semijoins=" ".join(f"{d.key}:{d.alt}" for d in sj_plans.values())
            or "none",
            keyed=" ".join(keyed.values()) or "none",
            probe=" ".join(probes.values()) or "none",
        )

    def _eval(node, ctx, t, pv) -> _Stream:
        if isinstance(node, Scan):
            return _Stream(base=node.table, cols=_LazyCols(t[node.table]),
                           mask=None, overflow=False)

        s = _eval(node.child, ctx, t, pv)
        if type(node) not in _LAYERS:
            raise LoweringError(f"cannot lower operator {type(node).__name__}")
        with layer(_LAYERS[type(node)]):
            return _apply(node, s, ctx, t, pv)

    def _apply(node, s, ctx, t, pv) -> _Stream:
        """One operator's own work on its child's stream ``s``."""
        if isinstance(node, Filter):
            per = scan_plans.get(id(node))
            if per is None:
                s.and_mask(eval_expr(node.pred, s.cols, pv))
                return s
            from repro.kernels import ops

            acc = None          # AND of per-column bitsets, in word space
            acc_shape = None    # (rows, padded_rows) — same table, so same
            for conjs, ds in per:
                dec = next((d for d in ds if d.mode == "packed"
                            and d.rewrite is not None), None)
                col = (s.cols.raw(dec.rewrite.column)
                       if dec is not None else None)
                if isinstance(col, PackedColumn):
                    # predicate-on-packed: code-space range test over the
                    # resident words, no decode of the column at all
                    lo, hi = dec.rewrite.bounds(pv)
                    words = ops.scan_filter(
                        col.words, lo, hi, rows=col.rows,
                        padded_rows=col.padded_rows, width=col.width,
                        negate=dec.rewrite.negate)
                    acc = words if acc is None else acc & words
                    acc_shape = (col.rows, col.padded_rows)
                else:
                    for conj in conjs:
                        s.and_mask(eval_expr(conj, s.cols, pv))
            if acc is not None:
                rows, padded = acc_shape
                s.and_mask(compression.unpack_bitset(acc, padded)[:rows], acc)
            return s

        if isinstance(node, Project):
            for name, e in node.cols:
                s.cols[name] = eval_expr(e, s.cols, pv)
            return s

        if isinstance(node, SemiJoin):
            plan = sj_plans[id(node)]
            target_cols = _LazyCols(t[node.table])
            part = ctx.part(node.table)
            if plan.alt == "request":  # Alt-1 index-lookup exchange
                key = eval_expr(node.key, s.cols, pv)
                needed = expr_columns(node.pred)

                def pred_fn(local_idx, m, _cols=target_cols, _p=node.pred,
                            _need=needed, _pv=pv):
                    # requested rows only: packed targets gather+decode
                    # capacity-many codes instead of expanding the column
                    view = {c: _col_at(_cols.raw(c), local_idx)
                            for c in _need}
                    return eval_expr(_p, view, _pv) & m

                mask = (s.mask if s.mask is not None
                        else jnp.ones(key.shape[0], bool))
                bits, ovf = semijoin.alt1_request(
                    key, mask, part, pred_fn,
                    # the derived capacity, unless the execution context
                    # carries an explicit override under this plan's key
                    capacity=ctx.cap(plan.key, plan.capacity),
                    axis=ctx.axis, backend=ctx.backend,
                    # the plan's per-semijoin wire decision ("auto" may mix
                    # packed and raw) unless the context forces raw
                    wire=(plan.wire if ctx.wire != "raw"
                          else WireFormat.raw()),
                    observer=getattr(ctx, "obs", None), label=plan.key,
                )
                s.and_mask(bits)
                s.overflow = s.overflow | ovf
                return s
            if plan.alt == "local":
                owner = eval_expr(node.pred, target_cols, pv)

                def test(k, _t=node.table):
                    return owner[_local_index(ctx, _t, k)]
            else:  # bitset (Alt-2)
                words = semijoin.alt2_bitset(
                    eval_expr(node.pred, target_cols, pv), axis=ctx.axis)

                def test(k):
                    return semijoin.probe(words, k, part)
            if probes[id(node)] == "full":
                s.and_mask(test(eval_expr(node.key, s.cols, pv)))
                return s
            def keys(idx, _cols=s.cols, _k=node.key, _pv=pv):
                # decoded here, not cached in the stream: a branch of the
                # probe's capacity switch traces this
                return eval_expr(_k, {c: _col_at(_cols.raw(c), idx)
                                      for c in expr_columns(_k)}, _pv)

            bits, tier = semijoin.probe_survivors(s.mask, s.words, keys, test)
            s.and_mask(bits)
            s.probe += (tier,)
            return s

        if isinstance(node, Exists):
            inner = _LazyCols(t[node.table])
            bits = eval_expr(node.pred, inner, pv)
            rows = ctx.part(s.base).rows_per_node
            fk_local = _local_index(ctx, s.base, inner[node.key])
            if keyed[id(node)] == "clustered":
                has = aggregation.group_sum_clustered(
                    bits, fk_local, t, node.table, rows,
                    catalog.clustered[node.table]) > 0
            else:
                has = jnp.zeros(rows, bool).at[fk_local].max(bits)
            s.and_mask(has)
            return s

        if isinstance(node, GroupAggByKey):
            key = eval_expr(node.key, s.cols, pv)
            parent_part = ctx.part(node.into)
            rows = parent_part.rows_per_node
            idx = _local_index(ctx, node.into, key)
            derived = {}
            for a in node.aggs:
                if a.agg == "count":
                    v = jnp.ones(key.shape[0], jnp.float32)
                else:
                    v = eval_expr(a.expr, s.cols, pv).astype(jnp.float32)
                if s.mask is not None:
                    v = jnp.where(s.mask, v, 0.0)
                if keyed[id(node)] == "clustered":
                    derived[a.name] = aggregation.group_sum_clustered(
                        v, idx, t, s.base, rows, catalog.clustered[s.base])
                else:
                    derived[a.name] = aggregation.group_sum_dense(v, idx, rows)
            cols = _LazyCols(t[node.into])
            cols.update(derived)
            return _Stream(base=node.into, cols=cols, mask=None,
                           overflow=s.overflow, probe=s.probe)

    def _run(ctx, t, pv):
        s = _eval(root.child, ctx, t, pv)
        with layer("aggregate" if isinstance(root, GroupAgg) else "topk"):
            return _root(s, ctx, t, pv)

    def _root(s: _Stream, ctx, t, pv):
        """The root's own work (grouped aggregate or top-k) on ``s``."""
        if isinstance(root, GroupAgg):
            if root.method == "kernel":
                from repro.kernels import ops

                gid = _group_ids(root, s, pv, clip=True)  # kernel indexes by gid
                stacked = _measure_stack(root.aggs, s.cols, mask=None, pv=pv)
                local = ops.filtered_group_sum(
                    stacked, gid, s.cols[kernel_col],
                    cutoff=kernel_cutoff, num_groups=num_groups,
                )
            else:
                method = root.method
                if method == "auto":
                    method = "onehot" if num_groups <= ONEHOT_MAX_GROUPS else "dense"
                    if (batched and lanes_vmapped
                            and _maskgemm_eligible(root, num_groups)):
                        method = "maskgemm"
                if num_groups == 1:
                    # global aggregate: per-measure masked tree-sums (the
                    # hand-plan shape), no one-hot detour
                    n = next(iter(s.cols.values())).shape[0]
                    outs = []
                    for a in root.aggs:
                        v = (jnp.ones(n, jnp.float32) if a.agg == "count"
                             else eval_expr(a.expr, s.cols, pv).astype(jnp.float32))
                        if s.mask is not None:
                            v = jnp.where(s.mask, v, 0.0)
                        outs.append(jnp.sum(v))
                    local = jnp.stack(outs)[None, :]
                elif method == "maskgemm":
                    # batched-lowering form: group codes and measures are
                    # parameter-independent, only the filter mask varies
                    # per lane, so vmap batches one GEMM per row block,
                    # not the whole pipeline
                    gid = _group_ids(root, s, pv, clip=False)
                    stacked = _measure_stack(root.aggs, s.cols, None, pv)
                    local = aggregation.group_sum_maskgemm(
                        stacked, gid, num_groups, s.mask)
                elif method == "onehot":
                    # out-of-range codes match no one-hot row and drop out,
                    # so no clamp pass is needed (keeps the HLO identical
                    # to the hand-written plans)
                    gid = _group_ids(root, s, pv, clip=False)
                    stacked = _measure_stack(root.aggs, s.cols, s.mask, pv)
                    local = aggregation.group_sum_onehot(stacked, gid, num_groups)
                else:
                    gid = _group_ids(root, s, pv, clip=True)  # scatter safety
                    stacked = _measure_stack(root.aggs, s.cols, s.mask, pv)
                    local = jnp.stack(
                        [aggregation.group_sum_dense(stacked[:, c], gid, num_groups)
                         for c in range(stacked.shape[1])],
                        axis=1,
                    )
            out = {"value": lax.psum(local, ctx.axis)}
            return _flags(out, s)

        # TopK root
        if root.pred is not None:
            s.and_mask(eval_expr(root.pred, s.cols, pv))
        values = eval_expr(root.value, s.cols, pv)
        keys = ctx.part(s.base).global_keys(ctx.axis)
        local = topk.local_topk(values, keys, root.k, s.mask)
        winners = topk.topk_allreduce(local, ctx.axis)
        out = {"values": winners.values, "keys": winners.keys,
               "valid": winners.valid}
        own = [f for f in root.fetch if f.table is None]
        if own:
            # hand materialize the RESIDENT form: packed fetch attributes
            # stay packed and only the k winners are gathered + decoded
            attrs = late_materialization.materialize(
                winners.keys, winners.valid, ctx.part(s.base),
                {f.name: s.cols.raw(f.name) for f in own}, axis=ctx.axis,
            )
            out.update(attrs)
        for f in root.fetch:
            if f.table is None:
                continue
            attrs = late_materialization.materialize(
                out[f.key], winners.valid, ctx.part(f.table),
                {f.name: t[f.table][f.name]}, axis=ctx.axis,
            )
            out.update(attrs)
        return _flags(out, s)

    def _flags(out, s: _Stream):
        """The stream's exchange-overflow flag and the tier of each
        compacted probe, beside the result (each as its node computed
        it)."""
        if s.overflow is not False:
            out["overflow"] = s.overflow
        if s.probe:
            out["probe_tier"] = jnp.stack(s.probe)
        return out

    def _group_ids(node: GroupAgg, s: _Stream, pv, *, clip: bool):
        n = next(iter(s.cols.values())).shape[0]
        if not node.keys:
            return jnp.zeros(n, jnp.int32)
        gid = None
        for k in node.keys:
            code = eval_expr(k.expr, s.cols, pv).astype(jnp.int32)
            if clip:
                code = jnp.clip(code, 0, k.cardinality - 1)
            gid = code if gid is None else gid * k.cardinality + code
        return gid

    if params:
        def plan(ctx, t, pvals):
            return _run(ctx, t, pvals)
    else:
        def plan(ctx, t):
            return _run(ctx, t, None)
    plan.params = params
    # the static semi-join decisions, in chain order (observability /
    # EXPLAIN attribute per-exchange collective bytes against these)
    plan.semijoins = tuple(sj_plans.values())
    # per-column scan strategies (chain order) — the driver's
    # storage.bytes_scanned accounting and EXPLAIN read these
    plan.scans = tuple(d for per in scan_plans.values()
                       for _, ds in per for d in ds)
    # the path of each keyed reduction into a parent (chain order): the
    # driver's plan.keyed.* counters and EXPLAIN read these
    plan.keyed = tuple(keyed.values())
    # the path of each local or bitset probe (chain order): the driver's
    # semijoin.probe.* counters and EXPLAIN read these; a "compact" probe
    # also returns the tier it took as the plan's "probe_tier" output
    plan.probes = tuple(probes.values())
    # lowered plans consume packed-resident columns directly (lazy decode,
    # predicate-on-packed, gather-based late materialization) — the engine
    # must NOT expand them at entry
    plan.handles_packed = True
    return plan
