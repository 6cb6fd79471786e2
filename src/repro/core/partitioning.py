"""Range partitioning and co-partitioning (paper §3.1).

All tables are range-partitioned on their primary key: node i owns keys
``[i * rows_per_node, (i+1) * rows_per_node)`` (0-based dense keys — the
TPC-H generator emits dense 1-based keys which we shift to 0-based at load).

Co-partitioning: two tables related by a foreign key store corresponding
tuples on the same node (lineitem–orders, partsupp–part), so equi-joins on
those edges are local.  The generator enforces this by construction; the
helpers here map keys to owners and to local indices, which is all a plan
needs to route a remote request (paper Fig. 1 dashed edges).
"""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np
from jax import lax


@dataclasses.dataclass(frozen=True)
class RangePartitioning:
    """Partitioning metadata for one table: ``total_rows`` dense keys split
    evenly over ``num_nodes`` (every node holds exactly rows_per_node —
    synthetic data is balanced, matching the paper's use of range
    partitioning for TPC-H)."""

    total_rows: int
    num_nodes: int

    @property
    def rows_per_node(self) -> int:
        assert self.total_rows % self.num_nodes == 0, (
            f"range partitioning requires divisible sizes, got "
            f"{self.total_rows} rows over {self.num_nodes} nodes"
        )
        return self.total_rows // self.num_nodes

    def owner(self, key):
        """Node that stores the row with this 0-based dense key."""
        return key // self.rows_per_node

    def local_index(self, key):
        """Row index of ``key`` within its owner's partition."""
        return key % self.rows_per_node

    def base(self, node):
        """First key owned by ``node``."""
        return node * self.rows_per_node

    def my_base(self, axis: str = "nodes"):
        """First key owned by the calling device (inside shard_map)."""
        return lax.axis_index(axis) * self.rows_per_node

    def global_keys(self, axis: str = "nodes"):
        """Dense keys of the local partition (inside shard_map)."""
        return self.my_base(axis) + jnp.arange(self.rows_per_node, dtype=jnp.int32)


def copartitioned(parent: RangePartitioning, child_fanout: int) -> RangePartitioning:
    """Partitioning of a child table co-partitioned with ``parent`` where each
    parent row has exactly ``child_fanout`` child rows (partsupp: 4 per part).
    For variable fanout (lineitem per order) the generator pads to a fixed
    per-node row count instead and this helper is not used."""
    return RangePartitioning(parent.total_rows * child_fanout, parent.num_nodes)


def clustered_fanout(fk, parent_rows: int, num_nodes: int) -> int:
    """Longest run of one key in a child's foreign-key column, or 0 unless
    the child is clustered by it: on every node the keys never decrease
    and lie in that node's parent partition (``parent_rows`` per node).
    ``fk`` holds global keys, node-major, as the generator lays out
    lineitem (``np.repeat`` of its orders)."""
    fk = np.asarray(fk)
    if fk.size == 0 or fk.size % num_nodes or parent_rows <= 0:
        return 0
    per_node = fk.reshape(num_nodes, -1)
    base = np.arange(num_nodes) * parent_rows
    if ((np.diff(per_node, axis=1) < 0).any()
            or (per_node[:, 0] < base).any()
            or (per_node[:, -1] >= base + parent_rows).any()):
        return 0
    return int(np.bincount(fk).max())

