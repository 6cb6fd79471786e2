"""Query execution driver.

The paper's runtime is "a precompiled function per query, run on every node,
synchronized by collectives".  Here: a plan is a Python function taking
(ctx, **local_table_columns) and running INSIDE shard_map over the ``nodes``
axis; ``Cluster.compile`` wraps it in shard_map + jit — XLA plays the role of
the paper's C++ compiler (and of the commercial JIT query compilers discussed
in §2), so a compiled plan is one SPMD executable, exactly the paper's model.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Mapping

import jax
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core.columnar import Table, decode_columns, shard_table
from repro.core.exchange import WireFormat
from repro.core.partitioning import RangePartitioning


@dataclasses.dataclass(frozen=True)
class PlanContext:
    """Static execution context threaded through every plan."""

    num_nodes: int
    axis: str
    parts: Mapping[str, RangePartitioning]  # table name -> partitioning
    capacities: Mapping[str, int]            # plan-specific buffer capacities
    backend: str = "xla"                     # all-to-all backend
    scale_factor: float = 1.0
    wire: str = "packed"                     # exchange wire format selector
    wires: Mapping[str, WireFormat] = dataclasses.field(default_factory=dict)
    # observability hub (repro.obs.Observer) threaded to the exchange
    # layer: collective exchanges emit one trace-time event per compiled
    # specialization.  None = uninstrumented (hand-built contexts).
    obs: object = None

    def part(self, table: str) -> RangePartitioning:
        return self.parts[table]

    def cap(self, name: str, default: int = 4096) -> int:
        return int(self.capacities.get(name, default))

    def wire_fmt(self, name: str) -> WireFormat:
        """Wire format of the named exchange (derived in
        ``repro.tpch.capacities`` for the hand plans, ``repro.query.stats``
        inside the lowering); raw when the context disables packing or no
        format was derived for this exchange."""
        if self.wire != "packed":
            return WireFormat.raw()
        return self.wires.get(name, WireFormat.raw())


# Largest partition (rows per node) whose batched plans vmap their lanes.
# A vmapped plan holds lanes x rows arrays (per-lane filter masks), which
# past this size outgrow one chip's memory; larger partitions run the lanes
# one after another inside the same dispatch.
BATCH_VMAP_MAX_ROWS = 1 << 22


def vmaps_lanes(rows_per_node: int) -> bool:
    """Whether a batched plan over partitions of ``rows_per_node`` rows
    vmaps its lanes (else it loops over them)."""
    return rows_per_node <= BATCH_VMAP_MAX_ROWS


class Cluster:
    """A shared-nothing cluster on a 1-D device mesh."""

    def __init__(self, devices=None, axis: str = "nodes"):
        devices = list(devices if devices is not None else jax.devices())
        self.axis = axis
        self.mesh = jax.make_mesh((len(devices),), (axis,),
                                  axis_types=(jax.sharding.AxisType.Auto,),
                                  devices=devices)
        self.num_nodes = len(devices)
        self.device_kind = devices[0].device_kind

    # -- data placement ----------------------------------------------------
    def load(self, table: Table) -> Table:
        return shard_table(table, self.mesh, self.axis)

    def context(self, tables: Mapping[str, Table], capacities=None, *,
                backend: str = "xla", scale_factor: float = 1.0,
                wire: str = "packed", wires=None, obs=None) -> PlanContext:
        parts = {
            name: RangePartitioning(t.num_rows, 1 if t.replicated else self.num_nodes)
            for name, t in tables.items()
        }
        return PlanContext(
            num_nodes=self.num_nodes,
            axis=self.axis,
            parts=parts,
            capacities=dict(capacities or {}),
            backend=backend,
            scale_factor=scale_factor,
            wire=wire,
            wires=dict(wires or {}),
            obs=obs,
        )

    # -- compilation -------------------------------------------------------
    def compile(self, plan: Callable, ctx: PlanContext, tables: Mapping[str, Table],
                *, batch: bool = False):
        """Bind a plan to this mesh: returns a jitted function of the sharded
        column pytree.  Partitioned tables are P('nodes') on axis 0;
        replicated tables (and all outputs) are replicated.

        A PARAMETERIZED plan (``plan.params`` non-empty, the lowered form of
        a query with :class:`~repro.query.ir.Param` placeholders) compiles
        to ``fn(columns, params)`` where ``params`` maps each name to a
        replicated scalar — the paper's compile-once/execute-many model:
        the values are traced jit arguments, so ONE executable serves every
        binding.  With ``batch=True`` the params are instead stacked along
        a leading batch axis and the plan body is ``vmap``-ed over it
        INSIDE shard_map — N query instances of the same prepared shape run
        as one SPMD dispatch (collectives batch along the lane axis), and
        every output gains a leading lane axis.  Partitions larger than
        ``BATCH_VMAP_MAX_ROWS`` rows per node loop over the lanes instead
        (``lax.map``): still one dispatch, one lane's working set."""

        in_specs = {
            name: {col: (P() if t.replicated else P(self.axis)) for col in t.columns}
            for name, t in tables.items()
        }
        params = tuple(getattr(plan, "params", ()) or ())
        if batch and not params:
            raise ValueError("batch=True requires a parameterized plan")

        # compressed residency: tables may hold PackedColumn entries.  A
        # plan that declares ``handles_packed`` (the IR lowering) receives
        # them as-is and scans the packed words directly; every other plan
        # (hand plans, cube builds) gets a full decode at plan entry —
        # inside shard_map, so only the local shard is ever decoded.
        if getattr(plan, "handles_packed", False):
            def entry(columns):
                return columns
        else:
            def entry(columns):
                return {t: decode_columns(c) for t, c in columns.items()}

        if params:
            param_specs = {p.name: P() for p in params}
            rows = max((t.num_rows // self.num_nodes for t in tables.values()
                        if not t.replicated), default=0)
            vmapped = vmaps_lanes(rows)

            def run(columns, pvals):
                columns = entry(columns)
                if not batch:
                    return plan(ctx, columns, pvals)
                lane = lambda pv: plan(ctx, columns, pv)  # noqa: E731
                return (jax.vmap(lane)(pvals) if vmapped
                        else lax.map(lane, pvals))

            sharded = jax.shard_map(
                run,
                mesh=self.mesh,
                in_specs=(in_specs, param_specs),
                out_specs=P(),
                check_vma=False,
            )
            return jax.jit(sharded)

        def run(columns):
            return plan(ctx, entry(columns))

        sharded = jax.shard_map(
            run,
            mesh=self.mesh,
            in_specs=(in_specs,),
            out_specs=P(),
            check_vma=False,
        )
        return jax.jit(sharded)

    def run(self, plan: Callable, tables: Mapping[str, Table], capacities=None,
            *, backend: str = "xla", scale_factor: float = 1.0,
            wire: str = "packed", wires=None):
        """Convenience: shard, compile, execute; returns host results."""
        placed = {name: self.load(t) for name, t in tables.items()}
        ctx = self.context(placed, capacities, backend=backend,
                           scale_factor=scale_factor, wire=wire, wires=wires)
        fn = self.compile(plan, ctx, placed)
        columns = {name: t.columns for name, t in placed.items()}
        return jax.tree.map(lambda x: jax.device_get(x), fn(columns))
