"""End-to-end TPC-H driver: generate -> place -> route/compile -> check.

Used by tests, benchmarks and the serving example; this is the paper's
"prototype running a subset of TPC-H" in one object, redesigned around the
declarative Query IR: ``query()`` takes ONE type (an IR ``Query``, or a
registered name as sugar for its definition) and routes it

  Tier 1  to the finest covering rollup cube (the router matches the
          ``GroupAgg`` root structurally — no hand-named fallback), else
  Tier 2  to the SPMD executable LOWERED from the IR itself, so one
          logical query has one result schema on every path (the
          hand-written plans stay reachable via ``run(name)``).

Prepared statements (the paper's §2/§3.1 compile-once model): every IR
query is canonicalized into a parameterized SHAPE plus a literal binding
(``repro.query.params``), and the plan cache keys on the shape alone — two
queries differing only in predicate literals share ONE compiled executable
and differ only in the scalars passed at run time.  ``prepare()`` exposes
that seam directly: ``prepare(q).execute(binding)`` re-runs the compiled
plan for any literals (Tier-1 routing re-checks bin-edge exactness per
binding), and ``execute_batch`` vmaps the plan over a stacked parameter
axis so N instances of one prepared shape run as a single device dispatch.

Exchange buffer capacities come from the §3.2.2 selectivity model
(``repro.tpch.capacities`` for the hand plans, ``repro.query.stats``
inside the lowering) instead of per-query magic constants; explicit
overrides still win.  For a prepared shape the capacities are sized from
the prepare-time binding (auto-parameterized literals) or the worst
binding in each parameter's declared range — the runtime ``overflow`` flag
surfaces any binding that exceeds them.
"""
from __future__ import annotations

import contextlib
import dataclasses
import itertools
import os
import threading
import time
from typing import Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import Cluster, Table
from repro.core import plans as plan_registry
from repro.core import semijoin, wirecal
from repro.core.columnar import PackedColumn
from repro.core.aggregation import blocks_table
from repro.kernels import clustered_sum
from repro.query.ir import PackedInfo
from repro.cube import CubeRouter, build_cube
from repro.obs import (
    ExplainReport,
    Observer,
    SemiJoinInfo,
    attribute_semijoin_bytes,
)
from repro.query import (
    LoweringError,
    Query,
    QueryError,
    UnboundParamError,
    UncoveredQueryError,
    build_catalog,
    explain_chain,
    lower,
    parameterize,
    query_params,
    same_query,
    validate,
)
from repro.tpch import capacities as tpch_capacities
from repro.tpch import dbgen, reference


class ResidentBudgetError(MemoryError):
    """The resident dataset exceeds the node memory budget
    (``REPRO_RESIDENT_BUDGET_BYTES`` / ``resident_budget=``) — the cluster
    cannot hold this scale factor in the chosen storage format.  The
    message reports both formats' footprints; switching to
    ``storage="packed"`` is the usual fix."""


def _resident_bytes(table: Table) -> int:
    """Resident footprint of one table (packed columns at their packed
    size, raw columns at array size)."""
    return sum(int(c.nbytes) for c in table.columns.values())


def _raw_bytes(table: Table) -> int:
    """What the same table would occupy fully decoded."""
    return sum(int(c.raw_nbytes) if isinstance(c, PackedColumn)
               else int(c.nbytes) for c in table.columns.values())


@dataclasses.dataclass
class QueryAnswer:
    """Result of router-first execution: which tier served the query.
    ``overflow`` is a scalar bool for single executions and a per-lane
    ``(B,)`` bool array for ``execute_batch`` (one overflowing lane never
    poisons its batch siblings)."""

    value: object
    tier: int            # 1 = rollup cube, 2 = compiled SPMD plan
    source: str          # cube name (tier 1) or plan/query name (tier 2)
    overflow: object = False  # a Tier-2 exchange buffer overflowed


def _split_overflow(out):
    """Surface a plan's exchange-overflow flag instead of leaving it buried
    in the raw result: hand plans return either a dict with an ``overflow``
    entry or an ``(value, overflow)`` pair (``bucket_by_destination``'s
    flag, threaded through every request/owner-routed exchange)."""
    if isinstance(out, dict):
        return out, bool(np.asarray(out.pop("overflow", False)))
    if (isinstance(out, tuple) and len(out) == 2
            and np.ndim(out[1]) == 0
            and np.asarray(out[1]).dtype == np.bool_):
        return out[0], bool(np.asarray(out[1]))
    return out, False


class _PlanEntry:
    """One cached prepared SHAPE: the parameterized canonical query, its
    ordered parameter signature, and the lazily compiled executables
    (scalar + vmap-batched).  Shared by every query that canonicalizes to
    this shape — the compile happens once.

    ``lock``/``warm`` serialize the FIRST call of each compiled
    specialization: ``jax.jit`` defers the XLA trace to the first call,
    so two threads racing into an un-warmed executable would both pay the
    trace (and double-count ``compile_events``).  Once a specialization
    ("scalar" or ``("batch", B)``) is in ``warm``, calls skip the entry
    lock (execution itself is serialized by the driver's dispatch gate —
    see ``TPCHDriver._guarded_call``)."""

    def __init__(self, shape: Query, stats_binding: dict):
        self.shape = shape
        self.params = query_params(shape.root)
        self.stats_binding = dict(stats_binding)
        self.fn = None          # compiled scalar executable
        self.batched_fn = None  # compiled vmapped executable (jit re-
                                # specializes per batch size)
        self.bound = {}         # binding signature -> fn(columns) closure
        self.route = (None, None)  # (router identity, Match|None) memo
        self.semijoins = ()     # static semi-join decisions of the lowering
        self.scans = ()         # static per-column scan strategies
        self.probes = {}        # batched? -> path of each local/bitset probe
        self.profile = None     # lazy HLO CollectiveStats (explain_analyze)
        self.lock = threading.Lock()  # guards lazy compile + first trace
        self.warm = set()       # specializations already traced once


class PreparedQuery:
    """A query prepared against one driver: compile once, execute for any
    parameter binding (``execute``), or run many bindings as one vmapped
    device dispatch (``execute_batch``).

    ``params`` is the ordered parameter signature; ``defaults`` carries the
    literal values extracted by auto-parameterization, so a prepared
    literal query executes with no arguments and any subset can be
    overridden per call.  Tier-1 cube routing happens at EXECUTE time —
    the shape is matched once, but bin-edge exactness is re-checked per
    binding, falling back to the compiled Tier-2 plan for off-edge or
    out-of-range values.

    Each execution records a root span (``query`` / ``query.batch``,
    carrying the driver's request number) whose Tier-2 children are
    ``bind`` (cast the binding to device scalars and transfer them),
    ``dispatch`` (``_guarded_call``, with any wait at the dispatch gate
    and, on a specialization's first call, its trace and compile) and
    ``fetch`` (``jax.device_get`` of the result); a single execution's
    ``route`` probe precedes ``bind``.
    """

    def __init__(self, driver: "TPCHDriver", entry: _PlanEntry,
                 defaults: dict, source: str, cache_hit: bool = False):
        self.driver = driver
        self.entry = entry
        self.defaults = dict(defaults)
        self.source = source
        self.cache_hit = cache_hit  # structural plan cache: shape was reused

    @property
    def params(self) -> tuple:
        return self.entry.params

    @property
    def query(self) -> Query:
        return self.entry.shape

    @property
    def shape_key(self) -> int:
        """Identity of the prepared shape: two handles carry the same key
        iff they share one ``_PlanEntry`` (and therefore one compiled
        executable).  The serving engine coalesces submissions by this
        key — same key means their bindings can stack into one
        ``execute_batch`` dispatch."""
        return id(self.entry)

    # -- binding ------------------------------------------------------------
    def binding(self, params=None) -> dict:
        """Defaults merged with per-call overrides; raises
        :class:`UnboundParamError` for missing or unknown names."""
        b = dict(self.defaults)
        if params:
            b.update(params)
        names = {p.name for p in self.entry.params}
        missing = sorted(names - set(b))
        if missing:
            raise UnboundParamError(
                f"missing binding(s) {missing} for prepared query "
                f"{self.source!r} (parameters: {sorted(names)})"
            )
        unknown = sorted(set(b) - names)
        if unknown:
            raise UnboundParamError(
                f"unknown parameter(s) {unknown} for prepared query "
                f"{self.source!r} (parameters: {sorted(names)})"
            )
        # eager castability: a bad value must fail HERE, naming the key,
        # not as a bare ValueError deep inside tracing
        for p in self.entry.params:
            try:
                np.asarray(b[p.name], np.dtype(p.dtype))
            except (TypeError, ValueError) as e:
                raise UnboundParamError(
                    f"binding {p.name}={b[p.name]!r} for prepared query "
                    f"{self.source!r} is not castable to {p.dtype}: {e}"
                ) from None
        return b

    def _cast(self, b: dict) -> dict:
        """Binding -> traced-argument pytree with STABLE dtypes (one aval
        set per shape, so re-executions never retrace)."""
        return {p.name: jnp.asarray(np.asarray(b[p.name], np.dtype(p.dtype)))
                for p in self.entry.params}

    # -- execution ----------------------------------------------------------
    def answer_tier1(self, b: dict) -> Optional[QueryAnswer]:
        """Tier-1 (rollup cube) answer for a FULL binding, or None when no
        cube covers this shape or the binding is off-edge/out-of-range.
        This is the serving engine's microsecond admission probe: pure
        host-side numpy, no device dispatch, safe to call inline on the
        event loop (the route match is memoized per entry; the
        re-assignment is an atomic tuple store, so concurrent probes at
        worst redo the match)."""
        router = self.driver.router
        if router is None:
            return None
        if self.entry.route[0] is not router:
            self.entry.route = (router, router.route_query(self.entry.shape))
        match = self.entry.route[1]
        if match is None:
            return None
        value = router.answer_bound(match, b)
        if value is None:  # off-edge / out-of-range binding -> Tier 2
            return None
        value = np.asarray(value).reshape(-1, value.shape[-1])
        return QueryAnswer(value, tier=1, source=match.route.cube.spec.name)

    _tier1 = answer_tier1

    def _tier2_fn(self):
        try:
            return self.driver._ensure_compiled(self.entry)
        except LoweringError as e:
            raise UncoveredQueryError(
                f"no rollup cube covers query {self.source} for this "
                f"binding and it has no lowerable Tier-2 form: {e}"
            ) from e

    def execute(self, params=None) -> QueryAnswer:
        obs = self.driver.obs
        mreg = obs.metrics
        t_start = time.perf_counter()
        with obs.span("query", source=self.source,
                      cache="hit" if self.cache_hit else "miss",
                      request=self.driver._next_request()) as sp:
            b = self.binding(params)
            with obs.span("route", cat="route"):
                ans = self._tier1(b)
            if ans is not None:
                sp.set(tier=1, route=ans.source)
                mreg.counter("driver.tier1").inc()
                mreg.histogram("query.tier1_us").record(
                    (time.perf_counter() - t_start) * 1e6)
                return ans
            fn = self._tier2_fn()
            with obs.span("bind", cat="exec"):
                args = [self.driver._columns()]
                if self.entry.params:
                    args.append(self._cast(b))
            with obs.span("dispatch", cat="exec"):
                out = self.driver._guarded_call(
                    self.entry, "scalar", fn, *args)
            with obs.span("fetch", cat="exec"):
                out = jax.device_get(out)
            overflow = bool(np.asarray(out.pop("overflow", False)))
            tiers = out.pop("probe_tier", None)
            value = out["value"] if set(out) == {"value"} else out
            sp.set(tier=2, route=self.source, overflow=overflow)
            mreg.counter("driver.tier2").inc()
            self.driver._count_scan_bytes(self.entry)
            self.driver._count_probes(self.entry, False, tiers)
            if overflow:
                mreg.counter("exchange.overflow").inc()
            mreg.histogram("query.tier2_us").record(
                (time.perf_counter() - t_start) * 1e6)
            return QueryAnswer(value, tier=2, source=self.source,
                               overflow=overflow)

    def execute_batch(self, param_table, pad_to: Optional[int] = None
                      ) -> QueryAnswer:
        """Run many bindings of this prepared shape as ONE vmapped SPMD
        dispatch.  ``param_table`` is a mapping name -> length-B sequence
        (missing names fall back to the defaults) or a sequence of B
        binding dicts.  Every output gains a leading lane axis; the
        ``overflow`` flag comes back per lane.  Batches always run the
        compiled Tier-2 plan (Tier-1 exactness is a per-binding decision —
        route single executions for that).

        ``pad_to`` pads the batch to a fixed lane count by repeating the
        last binding (outputs are sliced back to the real B).  The jitted
        batched executable re-specializes per DISTINCT lane count, so a
        continuous-batching caller whose batch sizes vary per tick pads
        to a few fixed bucket sizes instead of tracing one executable per
        observed size; the wasted duplicate lanes are counted in the
        ``driver.batch_pad_lanes`` metric."""
        if not self.entry.params:
            raise QueryError(
                f"prepared query {self.source!r} has no parameters — "
                f"execute_batch needs a parameterized shape"
            )
        if isinstance(param_table, Mapping):
            seqs = {k: list(v) for k, v in param_table.items()}
            sizes = {len(v) for v in seqs.values()}
            if len(sizes) != 1:
                raise QueryError(
                    f"ragged param_table: column lengths {sorted(sizes)}"
                )
            B = sizes.pop()
            rows = [{k: seqs[k][i] for k in seqs} for i in range(B)]
        else:
            rows = [dict(r) for r in param_table]
            B = len(rows)
        if B == 0:
            raise QueryError("execute_batch needs at least one binding")
        obs = self.driver.obs
        mreg = obs.metrics
        lanes = max(B, pad_to or 0)
        with obs.span("query.batch", source=self.source, lanes=B,
                      padded=lanes,
                      request=self.driver._next_request()) as sp:
            with obs.span("bind", cat="exec"):
                merged = [self.binding(r) for r in rows]
                if lanes > B:
                    merged = merged + [merged[-1]] * (lanes - B)
                    mreg.counter("driver.batch_pad_lanes").inc(lanes - B)
                stacked = {
                    p.name: jnp.asarray(np.asarray(
                        [m[p.name] for m in merged], np.dtype(p.dtype)))
                    for p in self.entry.params
                }
            self._tier2_fn()  # surface LoweringError as UncoveredQueryError
            fn = self.driver._ensure_batched(self.entry)
            with obs.span("dispatch", cat="exec"):
                out = self.driver._guarded_call(
                    self.entry, ("batch", lanes), fn,
                    self.driver._columns(), stacked)
            with obs.span("fetch", cat="exec"):
                out = jax.device_get(out)
            overflow = out.pop("overflow", None)
            overflow = (np.zeros(lanes, bool) if overflow is None
                        else np.asarray(overflow))
            tiers = out.pop("probe_tier", None)
            value = out["value"] if set(out) == {"value"} else out
            if lanes != B:  # drop the padding lanes from every output
                value = jax.tree.map(lambda a: a[:B], value)
                overflow = overflow[:B]
            self.driver._count_probes(
                self.entry, True, None if tiers is None else tiers[:B], B)
            n_ovf = int(np.asarray(overflow).sum())
            sp.set(tier=2, overflow_lanes=n_ovf)
            mreg.counter("driver.batch").inc()
            mreg.counter("driver.batch_lanes").inc(B)
            self.driver._count_scan_bytes(self.entry, lanes=B)
            if n_ovf:
                mreg.counter("exchange.overflow").inc(n_ovf)
            return QueryAnswer(value, tier=2, source=self.source,
                               overflow=overflow)


class TPCHDriver:
    """One TPC-H instance resident on a cluster (see the module docstring).

    ``load_seconds`` is the set-up timer: host seconds of each set-up step
    the driver runs, keyed by step.  The steps are disjoint, so their sum
    is the driver's share of set-up:

    - ``generate``, ``pack``, ``place``, ``catalog``: the data generator,
      column packing, placement on the devices (until the arrays are
      ready) and the catalog statistics, at construction;
    - ``compile``: summed over every prepared shape and specialization,
      lowering and jit-wrapping a shape (``_ensure_compiled`` /
      ``_ensure_batched``) plus the first dispatch of each
      specialization, which traces it and compiles it (or loads it from
      the persistent cache); added on first use;
    - ``cubes``: ``build_cubes``, its compiles included.

    Steps run from several threads (the serving tier warms shapes
    concurrently) add thread-seconds."""

    def __init__(self, sf: float, cluster: Cluster | None = None, seed: int = 0,
                 capacities=None, backend: str = "xla", wire: str = "packed",
                 obs: Observer | None = None, storage: str = "packed",
                 resident_budget: Optional[int] = None):
        # one lock for every cache the driver mutates (_compiled,
        # _prepared + its LRU order, per-entry bound-closure LRUs) and for
        # load_seconds: the serving tier calls prepare()/query() from the
        # event loop and executor threads concurrently.  Reentrant because
        # prepare() is reached from compile()/compile_query() which may
        # already hold it.
        self._lock = threading.RLock()
        self.load_seconds = {}
        self._request_ids = itertools.count(1)  # root spans' request numbers
        self.cluster = cluster or Cluster()
        self.sf = sf
        self.seed = seed
        self.backend = backend
        self.wire = wire
        self.storage = storage
        # machine calibration for EXPLAIN's roofline predictions (persisted
        # by `python -m repro.core.wirecal`; builtin defaults otherwise)
        self.wire_cal = wirecal.load()
        # the observability hub: threaded (never global) through routing,
        # lowering and the exchange layer; on by default — pass
        # Observer(enabled=False) to drop tracing (metrics stay live)
        self.obs = obs if obs is not None else Observer()
        # §3.2.2-derived capacities for the hand plans; explicit overrides win
        self.capacities = tpch_capacities.derive(sf, self.cluster.num_nodes)
        self.capacities.update(capacities or {})
        # self.tables is the raw host-side view for the oracle and catalog
        # stats; self.resident is what the cluster holds and places — with
        # storage="packed", eligible columns in the compressed PackedColumn
        # form (lossless, so the host view is bit-identical to the codes).
        with self._setup_step("generate"):
            self.tables = dbgen.generate(sf, self.cluster.num_nodes, seed)
        with self._setup_step("pack"):
            self.resident = (dbgen.pack_tables(self.tables,
                                               self.cluster.num_nodes)
                             if storage == "packed" else dict(self.tables))
        # pad the supplier key space so §3.2.5 groups divide evenly
        self._extend_derived_tables()
        for extra in set(self.tables) - set(self.resident):
            self.resident[extra] = self.tables[extra]
        packed_meta = {
            n: {c: PackedInfo(width=col.width, offset=col.offset,
                              values=col.values, dtype=col.dtype)
                for c, col in t.columns.items()
                if isinstance(col, PackedColumn)}
            for n, t in self.resident.items()
        }
        with self._setup_step("catalog"):
            self.catalog = build_catalog(self.tables,
                                         num_nodes=self.cluster.num_nodes,
                                         packed=packed_meta,
                                         device_kind=self.cluster.device_kind)
            self._add_cluster_blocks()
        # resident-footprint accounting + node memory budget: the budget
        # models per-node main memory; exceeding it is the OOM the packed
        # format exists to push out by ~the compression ratio
        if resident_budget is None:
            env = os.environ.get("REPRO_RESIDENT_BUDGET_BYTES")
            resident_budget = int(env) if env else None
        mreg = self.obs.metrics
        total = 0
        for n, t in self.resident.items():
            b = _resident_bytes(t)
            total += b
            mreg.gauge(f"storage.bytes_resident.{n}").set(b)
        mreg.gauge("storage.bytes_resident").set(total)
        self.resident_bytes = total
        if resident_budget is not None and total > resident_budget:
            raw = sum(_raw_bytes(t) for t in self.resident.values())
            raise ResidentBudgetError(
                f"resident dataset at sf={sf} needs {total} bytes in "
                f"{storage!r} storage but the node budget is "
                f"{resident_budget} bytes (fully decoded it would be "
                f"{raw}); use storage='packed' or a smaller scale factor")
        with self._setup_step("place"):
            self.placed = {n: self.cluster.load(t)
                           for n, t in self.resident.items()}
            jax.block_until_ready([t.columns for t in self.placed.values()])
        self.ctx = self.cluster.context(
            self.placed, self.capacities, backend=backend, scale_factor=sf,
            wire=wire,
            wires=tpch_capacities.wire_formats(self.tables,
                                               self.cluster.num_nodes),
            obs=self.obs,
        )
        self._compiled = {}       # registry name -> compiled hand plan
        self._prepared = {}       # STRUCTURAL shape key -> _PlanEntry (LRU)
        # Device executions are globally serialized: XLA's host-platform
        # collectives rendezvous on the 8 shared device threads, so TWO
        # multi-device programs dispatched concurrently each wait for all
        # of their participants and neither set can assemble (observed as
        # "waiting for all participants to arrive at rendezvous" hangs).
        # One dispatch at a time is also the honest model of one shared
        # cluster — concurrency comes from batching lanes into a dispatch,
        # not from overlapping dispatches.
        self._dispatch_gate = threading.Lock()
        self._profiling = False   # True while explain_analyze dumps HLO —
                                  # that re-trace is an artifact, not a
                                  # compile event

        self.compile_events = []  # one label per XLA trace of a prepared
                                  # plan ("<shape>" / "<shape>@batch") —
                                  # the compile-once contract is testable
        self.cubes = {}
        self.router: CubeRouter | None = None

    def _extend_derived_tables(self):
        # q3_repl needs the replicated remote join attribute, built at load
        # time (paper's 'repl' variant)
        cust = self.tables["customer"]
        self.tables["customer_seg_repl"] = Table(
            "customer_seg_repl",
            {"c_mktsegment": np.asarray(cust.columns["c_mktsegment"])},
            replicated=True,
        )

    def _add_cluster_blocks(self):
        """Resident block starts of every child the catalog finds clustered
        by its foreign key (read by the lowering's clustered keyed
        reductions), partitioned like the parent.  Placed for every such
        child, whether or not a prepared query reduces it: the clustering
        fact holds for any ``Query`` the driver is later given, and the
        starts are small (partsupp's about 62 KB at SF 10, lineitem's
        0.5 MB)."""
        nodes = self.cluster.num_nodes
        for child in self.catalog.clustered:
            parent, fk = self.catalog.copartitioned[child]
            starts = clustered_sum.block_starts(
                self.tables[child].columns[fk],
                self.tables[parent].num_rows // nodes, nodes)
            name = blocks_table(child)
            self.resident[name] = Table(name, {"first_row": starts})

    def _columns(self):
        return {n: t.columns for n, t in self.placed.items()}

    def _add_setup_seconds(self, step: str, seconds: float) -> None:
        with self._lock:
            self.load_seconds[step] = self.load_seconds.get(step, 0.0) + seconds

    @contextlib.contextmanager
    def _setup_step(self, step: str):
        """Add the block's host seconds to ``load_seconds[step]``."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self._add_setup_seconds(step, time.perf_counter() - t0)

    def _next_request(self) -> int:
        return next(self._request_ids)

    def _count_scan_bytes(self, entry: _PlanEntry, lanes: int = 1) -> None:
        """Account one execution's predicted scan traffic against the
        ``storage.bytes_scanned`` counters (cluster-wide bytes: per-node
        prediction x nodes x batch lanes)."""
        if not entry.scans:
            return
        mreg = self.obs.metrics
        nn = max(self.cluster.num_nodes, 1)
        total = 0
        for d in entry.scans:
            b = d.scan_bytes * nn * lanes
            mreg.counter(f"storage.bytes_scanned.{d.table}").inc(b)
            total += b
        mreg.counter("storage.bytes_scanned").inc(total)

    def _count_probes(self, entry: _PlanEntry, batched: bool, tiers,
                      lanes: int = 1) -> None:
        """Count each local or bitset probe of an execution (of each lane
        of a batch) in ``semijoin.probe.compact`` or ``.full``: a compacted
        probe counts as full where its survivors filled every capacity
        (``tiers``, the plan's ``probe_tier`` output)."""
        paths = entry.probes.get(batched, ())
        if not paths:
            return
        tiers = np.asarray(() if tiers is None else tiers).reshape(lanes, -1)
        full = int((tiers >= semijoin.FULL_WIDTH).sum())
        mreg = self.obs.metrics
        mreg.counter("semijoin.probe.compact").inc(tiers.size - full)
        mreg.counter("semijoin.probe.full").inc(
            full + paths.count("full") * lanes)

    def _guarded_call(self, entry, key, fn, *args):
        """Run one device dispatch of ``entry``'s specialization ``key``.

        Two separate serializations, both required for threaded callers:
        the FIRST call per specialization holds ``entry.lock`` so exactly
        one thread pays the deferred XLA trace, and EVERY call holds the
        driver's ``_dispatch_gate`` so two collective programs never
        rendezvous concurrently on the shared host-platform devices (see
        the gate's comment in ``__init__``).  The first call's time, gate
        wait excluded, is set-up (``load_seconds["compile"]``)."""
        if key in entry.warm:
            with self._dispatch_gate:
                return fn(*args)
        with entry.lock:
            cold = key not in entry.warm
            with self._dispatch_gate:
                t0 = time.perf_counter()
                out = fn(*args)
                seconds = time.perf_counter() - t0
            entry.warm.add(key)
        if cold:  # added outside entry.lock, which nests inside _lock
            self._add_setup_seconds("compile", seconds)
        return out

    # -- physical layer (hand plans / lowered IR by registry name) ---------
    def compile(self, name: str):
        """Compiled plan for a registered query: the hand-written physical
        plan when one exists, else the lowered IR (shared with the
        structural query cache — one executable per query)."""
        with self._lock:
            if name not in self._compiled:
                entry = plan_registry.get(name)
                if entry.plan is not None:
                    self._compiled[name] = self.cluster.compile(
                        entry.plan, self.ctx, self.placed)
                elif entry.ir is not None:
                    self._compiled[name] = self.compile_query(entry.ir)
                else:  # pragma: no cover — registry invariant
                    raise LoweringError(f"{name!r} has neither plan nor IR")
            return self._compiled[name]

    def run(self, name: str):
        return self.compile(name)(self._columns())

    def use_backend(self, backend: str) -> None:
        """Compile every later plan with the ``backend`` all-to-all
        ("xla" | "one_factor") over the same placed data; compiled plans
        are dropped, cubes and data stay."""
        with self._lock:
            self.backend = backend
            self.ctx = dataclasses.replace(self.ctx, backend=backend)
            self._compiled.clear()
            self._prepared.clear()

    def compile_ir(self, name: str):
        """Compiled LOWERED plan for a registered query's IR (even when a
        hand plan exists — used to compare the two)."""
        entry = plan_registry.get(name)
        if entry.ir is None:
            raise LoweringError(
                f"{name!r} has no IR definition — only the hand-written "
                f"plan; express it in the algebra first"
            )
        return self.compile_query(entry.ir)

    def run_ir(self, name: str):
        return self.compile_ir(name)(self._columns())

    IR_CACHE_MAX = 32    # compiled-executable LRU bound for ad-hoc queries
    BOUND_CACHE_MAX = 8  # per-shape LRU bound for literal-bound closures

    # -- prepared statements (compile once, execute for any literals) ------
    def prepare(self, q) -> PreparedQuery:
        """Prepare an IR query (or a registered name): canonicalize it into
        a parameterized shape + default binding, and return the (possibly
        cached) :class:`PreparedQuery`.  The structural cache keys on the
        SHAPE alone, so queries differing only in predicate literals share
        one compiled executable; compilation itself is lazy — the first
        Tier-2 execution pays it, Tier-1-served queries never do."""
        if isinstance(q, str):
            entry = plan_registry.get(q)
            if entry.ir is None:
                raise LoweringError(
                    f"{q!r} has no IR definition — only the hand-written "
                    f"plan; express it in the algebra first"
                )
            q = entry.ir
        if not isinstance(q, Query):
            raise TypeError(
                f"prepare() takes a repro.query.Query (or a registered "
                f"plan name), got {type(q)}"
            )
        validate(q.root, self.catalog)  # typed errors at prepare time
        shape, defaults = parameterize(q, obs=self.obs)
        source = q.name or "<lowered-ir>"
        key = repr(shape.root)  # structural; same_query guards collisions
        # lookup-or-insert is atomic: two threads preparing the same shape
        # concurrently must converge on ONE entry (one miss, one hit), or
        # each would compile its own executable
        with self._lock:
            hit = self._prepared.get(key)
            if hit is not None and same_query(hit.shape, shape):
                self._prepared[key] = self._prepared.pop(key)  # LRU touch
                self.obs.metrics.counter("plan_cache.hit").inc()
                return PreparedQuery(self, hit, defaults, source,
                                     cache_hit=True)
            entry = _PlanEntry(shape, stats_binding=defaults)
            self._prepared[key] = entry
            while len(self._prepared) > self.IR_CACHE_MAX:
                self._prepared.pop(next(iter(self._prepared)))
            self.obs.metrics.counter("plan_cache.miss").inc()
            return PreparedQuery(self, entry, defaults, source)

    def _lowered_plan(self, entry: _PlanEntry, label: str,
                      batched: bool = False):
        """Lower the shape and wrap it so every XLA trace is counted in
        ``compile_events`` (jit executes the wrapper body only when it
        traces, i.e. exactly once per compiled specialization); the same
        wrapper feeds the ``plan.compile_events`` registry counter and an
        ``xla.trace`` event, so re-trace regressions show up in
        ``explain_analyze`` and ``--metrics``."""
        plan = lower(entry.shape, self.catalog, wire=self.wire,
                     binding=entry.stats_binding, batched=batched,
                     obs=self.obs)
        entry.semijoins = tuple(getattr(plan, "semijoins", ()))
        entry.scans = tuple(getattr(plan, "scans", ()))
        entry.probes[batched] = plan.probes
        for path in plan.keyed:
            self.obs.metrics.counter(f"plan.keyed.{path}").inc()
        events = self.compile_events
        obs = self.obs
        drv = self

        def on_trace():
            if drv._profiling:
                return
            events.append(label)
            obs.metrics.counter("plan.compile_events").inc()
            obs.event("xla.trace", cat="plan", label=label)

        if plan.params:
            def wrapped(ctx, t, pvals):
                on_trace()
                return plan(ctx, t, pvals)
        else:
            def wrapped(ctx, t):
                on_trace()
                return plan(ctx, t)
        wrapped.params = plan.params
        # the lowering scans packed columns itself; without this flag
        # Cluster.compile would decode every column at plan entry
        wrapped.handles_packed = plan.handles_packed
        return wrapped

    def _ensure_compiled(self, entry: _PlanEntry):
        if entry.fn is None:
            seconds = 0.0
            with entry.lock:  # double-checked: lower+jit-wrap once
                if entry.fn is None:
                    t0 = time.perf_counter()
                    label = entry.shape.name or "<lowered-ir>"
                    with self.obs.span("lower", cat="plan", label=label):
                        entry.fn = self.cluster.compile(
                            self._lowered_plan(entry, label),
                            self.ctx, self.placed)
                    seconds = time.perf_counter() - t0
            self._add_setup_seconds("compile", seconds)
        return entry.fn

    def _ensure_batched(self, entry: _PlanEntry):
        if entry.batched_fn is None:
            seconds = 0.0
            with entry.lock:
                if entry.batched_fn is None:
                    t0 = time.perf_counter()
                    label = f"{entry.shape.name or '<lowered-ir>'}@batch"
                    with self.obs.span("lower", cat="plan", label=label):
                        entry.batched_fn = self.cluster.compile(
                            self._lowered_plan(entry, label, batched=True),
                            self.ctx, self.placed, batch=True)
                    seconds = time.perf_counter() - t0
            self._add_setup_seconds("compile", seconds)
        return entry.batched_fn

    def compile_query(self, q: Query):
        """Lower + compile an arbitrary IR query, returning a plain
        ``fn(columns)`` with the query's own literals bound (the prepared
        executable is shared structurally; the returned closure is
        memoized per binding, so reconstructing the same query per request
        reuses BOTH).  Parameterized queries without full defaults need
        :meth:`prepare` instead."""
        prep = self.prepare(q)
        entry = prep.entry
        fn = self._ensure_compiled(entry)  # eager typed errors
        if not entry.params:
            return fn
        b = prep.binding()
        key = tuple(sorted(b.items()))
        with self._lock:
            if key in entry.bound:
                entry.bound[key] = entry.bound.pop(key)  # LRU touch
            else:
                pvals = prep._cast(b)
                entry.bound[key] = (
                    lambda columns, _fn=fn, _pv=pvals: _fn(columns, _pv))
                # closures hold device scalars; a literal-streaming caller
                # must not grow this without bound (the executable is shared
                # regardless — evicted bindings just rebuild a closure)
                while len(entry.bound) > self.BOUND_CACHE_MAX:
                    entry.bound.pop(next(iter(entry.bound)))
            return entry.bound[key]

    # -- two-tier execution (repro.cube) -----------------------------------
    def build_cubes(self, specs=None):
        """Materialize Tier-1 rollup cubes (one distributed scan per spec)
        and install the query router.  Defaults to the TPC-H presets."""
        if specs is None:
            from repro.tpch import cubes as tpch_cubes

            specs = tpch_cubes.default_specs()
        for spec in specs:
            with (self.obs.span("cube.build", cat="plan", cube=spec.name),
                  self._setup_step("cubes")):
                self.cubes[spec.name] = build_cube(
                    self.cluster, self.ctx, self.placed, spec
                )
        self.obs.metrics.gauge("router.cubes").set(len(self.cubes))
        self.router = CubeRouter(list(self.cubes.values()), obs=self.obs)
        return self.cubes

    def query(self, q, params=None) -> QueryAnswer:
        """Router-first execution of ONE query type.

        ``q`` is an IR ``Query`` (a registered name is accepted as sugar
        for its definition); ``params`` optionally binds/overrides its
        runtime parameters.  A ``GroupAgg`` root covered by a rollup is
        answered from the cube (Tier 1, host microseconds) with bin-edge
        exactness checked against THIS call's binding; anything else runs
        as the compiled SPMD plan lowered from the parameterized shape
        (Tier 2) — one executable per shape, re-executed for any literals.
        Raises :class:`UncoveredQueryError` when no cube covers the query
        and the IR has no lowerable form (e.g. min/max measures
        off-edge)."""
        if isinstance(q, str):
            entry = plan_registry.get(q)
            if entry.ir is None:
                if params:
                    raise UnboundParamError(
                        f"{q!r} resolves to a hand-written physical plan "
                        f"with no runtime parameters — binding(s) "
                        f"{sorted(params)} cannot be applied; use an IR "
                        f"form or drop params"
                    )
                value, overflow = _split_overflow(jax.device_get(self.run(q)))
                return QueryAnswer(value, tier=2, source=q, overflow=overflow)
            q = entry.ir
        if not isinstance(q, Query):
            raise TypeError(
                f"query() takes a repro.query.Query (or a registered plan "
                f"name), got {type(q)}"
            )
        return self.prepare(q).execute(params)

    # -- static verification (repro.query.verify) ---------------------------
    def check(self, q, params=None):
        """Statically verify a query (or registered IR name) against this
        driver's catalog, wire format, and capacity overrides — nothing is
        compiled or executed.  ``params`` optionally overrides the
        prepared defaults, so a binding can be vetted BEFORE
        ``prepare(q).execute(params)`` pays for it (an undersized exchange
        shows up as a ``CAP001`` error naming the worst-case binding).
        Returns a :class:`repro.query.verify.VerifyReport`; rule catalog
        in ``docs/RULES.md``."""
        from repro.query.verify import verify

        prep = self.prepare(q)
        if params:
            names = {p.name for p in prep.params}
            unknown = sorted(set(params) - names)
            if unknown:
                raise UnboundParamError(
                    f"unknown parameter(s) {unknown} for query "
                    f"{prep.source!r} (parameters: {sorted(names)})"
                )
        binding = dict(prep.defaults)
        binding.update(params or {})
        return verify(
            prep.entry.shape, self.catalog, wire=self.wire,
            binding=binding, stats_binding=prep.entry.stats_binding,
            capacities=self.capacities,
        )

    # -- EXPLAIN / EXPLAIN ANALYZE (repro.obs) ------------------------------
    def _explain(self, q, params=None):
        """Shared front half: prepare, route-match, predicted plan rows."""
        prep = self.prepare(q)
        entry = prep.entry
        binding = dict(prep.defaults)
        if params:
            binding.update(params)
        match = None
        if self.router is not None:
            if entry.route[0] is not self.router:
                entry.route = (self.router,
                               self.router.route_query(entry.shape))
            match = entry.route[1]
        tier = 1 if match is not None else 2
        source = (match.route.cube.spec.name if match is not None
                  else prep.source)
        rows, sjs, err = [], [], None
        try:
            rows = explain_chain(entry.shape, self.catalog, wire=self.wire,
                                 binding=binding, predict_cal=self.wire_cal)
        except (LoweringError, QueryError) as e:
            err = str(e)
        for r in rows:
            if r["op"] != "SemiJoin":
                continue
            wf = r["wire"]
            kind = "packed" if (self.wire != "raw" and wf.packed) else "raw"
            sjs.append(SemiJoinInfo(
                index=len(sjs), table=r["table"], alt=r["alt"],
                capacity=r["capacity"], capacity_key=r["capacity_key"],
                wire_kind=kind, key_bits=wf.key_bits, gamma=r["gamma"],
                codec_ms=r["codec_ms"], wire_ms=r["wire_ms"],
            ))
        diagnostics = []
        try:
            from repro.query.verify import verify

            diagnostics = list(verify(
                entry.shape, self.catalog, wire=self.wire, binding=binding,
                stats_binding=entry.stats_binding,
                capacities=self.capacities,
            ).diagnostics)
        except QueryError:
            pass  # plan_error already carries the lowering failure
        report = ExplainReport(
            query=prep.source, route_tier=tier, route_source=source,
            cache="hit" if prep.cache_hit else "miss", params=binding,
            plan_rows=rows, semijoins=sjs, plan_error=err,
            diagnostics=diagnostics,
        )
        return report, prep

    def explain(self, q, params=None) -> ExplainReport:
        """Static EXPLAIN: the route the query WOULD take (Tier-1 cube
        match vs Tier-2 compiled plan), plan-cache state, and the cost
        model's per-operator predictions — nothing is compiled or run."""
        report, _ = self._explain(q, params)
        return report

    def explain_analyze(self, q, params=None) -> ExplainReport:
        """EXPLAIN plus one traced execution: observed tier, compile vs
        execute milliseconds (the query runs cold, and again warm when the
        first run traced, so the difference isolates XLA compilation),
        per-execution overflow, registry counters, and — for Tier-2 runs —
        per-collective HLO bytes attributed to the plan's request
        semi-joins in program order."""
        report, prep = self._explain(q, params)
        entry = prep.entry
        mreg = self.obs.metrics
        ev0 = len(self.compile_events)
        t0 = time.perf_counter()
        ans = prep.execute(params)
        cold_s = time.perf_counter() - t0
        traces = len(self.compile_events) - ev0
        observed = {
            "tier": ans.tier,
            "source": ans.source,
            "overflow": bool(np.asarray(ans.overflow).any()),
        }
        if traces:
            t0 = time.perf_counter()
            ans = prep.execute(params)
            warm_s = time.perf_counter() - t0
            observed["compile_ms"] = max(cold_s - warm_s, 0.0) * 1e3
            observed["xla_traces"] = traces
            observed["execute_ms"] = warm_s * 1e3
        else:
            observed["compile_ms"] = None
            observed["xla_traces"] = 0
            observed["execute_ms"] = cold_s * 1e3
        # registry counters BEFORE the profiling compile below, so the
        # report reflects what the measured runs did
        observed["overflow_count"] = mreg.value("exchange.overflow")
        observed["compile_events"] = mreg.value("plan.compile_events")
        observed["bytes_scanned"] = mreg.value("storage.bytes_scanned")
        observed["bytes_resident"] = mreg.value("storage.bytes_resident")
        # trace-time codec predictions accumulated by the exchange layer
        # (one record per compiled exchange specialization)
        for hname in ("exchange.encode_ms", "exchange.decode_ms"):
            h = mreg.get(hname)
            if h is not None and h.count:
                observed[hname] = h.snapshot()
        if ans.tier == 2 and report.plan_error is None:
            try:
                prof = self._collective_profile(entry)
            except Exception as e:
                prof, observed["profile_error"] = None, str(e)
            if prof is not None:
                observed["collective_bytes_by_op"] = dict(prof.bytes_by_op)
                observed["collective_count_by_op"] = dict(prof.count_by_op)
                attribute_semijoin_bytes(prof.instructions, report.semijoins)
        report.observed = observed
        return report

    def lowered_text(self, q) -> str:
        """StableHLO text of the scalar Tier-2 plan of ``q`` (an IR query
        or a registered name) as lowered for the cluster's devices; on a
        TPU each natively compiled Pallas kernel is a ``tpu_custom_call``.
        Nothing is compiled."""
        return self._lowered(self.prepare(q).entry).as_text()

    def _lowered(self, entry: _PlanEntry):
        fn = self._ensure_compiled(entry)
        cols = self._columns()
        self._profiling = True
        try:
            if entry.params:
                pvals = {p.name: jax.ShapeDtypeStruct(
                    (), np.dtype(p.dtype)) for p in entry.params}
                return fn.lower(cols, pvals)
            return fn.lower(cols)
        finally:
            self._profiling = False

    def _collective_profile(self, entry: _PlanEntry):
        """HLO collective stats of the compiled scalar plan, cached per
        entry.  Lazy on purpose: ``jit(...).lower().compile()`` is a second
        XLA compilation that plain query execution must never pay — only
        ``explain_analyze`` materializes it."""
        if entry.profile is None:
            from repro.launch.roofline import parse_collective_bytes

            entry.profile = parse_collective_bytes(
                self._lowered(entry).compile().as_text())
        return entry.profile

    def oracle(self, name: str, **kw):
        """Float64 numpy reference via the registry's EXPLICIT oracle
        binding (``q15_1factor`` -> ``q15`` etc. — no name munging)."""
        entry = plan_registry.get(name)
        if entry.oracle is None:
            raise LoweringError(f"{name!r} has no oracle binding")
        if entry.oracle == "q11":
            kw.setdefault("sf", self.sf)
        return reference.ALL[entry.oracle](self.tables, **kw)
