"""Grouped aggregation (the paper's local-aggregation substrate, §4.3).

Small-cardinality group-bys (Q1: 6 groups, Q4: 5 groups, Q5: 25 nations) are
computed as *one-hot MXU contractions* — the TPU-native reformulation of the
paper's scalar hash-table inner loop (DESIGN.md §3.2).  Large dense key
spaces (revenue per supplier, orders per customer) use scatter-add into a
dense vector, which is the column-store analogue of the paper's dense
aggregation arrays.

Distributed variants combine local aggregates with a collective reduce —
the paper's "custom reduce operator merges the partial result sets".
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax

# f32 contractions at full precision: the TPU's default rounds f32 MXU
# inputs to bf16, which moves sums of low-cardinality measures (Q1's
# discount and tax) by ~1e-3 relative, far outside the oracle tolerance
EXACT = lax.Precision.HIGHEST

# Rows per MXU contraction.  A contraction accumulates its rows in f32 in
# order; over a whole 60M-row partition that moved Q1's sums by ~3e-3
# relative on a TPU v5e, so rows are contracted in blocks and the block
# sums added.  Wide operands take shorter blocks (about 16 MB of operand).
SUM_BLOCK_ROWS = 1 << 17


def _block_sum(n: int, width: int, partial):
    """Sum over row blocks of ``partial(start, size, keep) -> (width,)``.
    The last block is clamped to end at ``n``; ``keep`` masks off its rows
    that the previous block already summed."""
    size = min(n, SUM_BLOCK_ROWS, max(1024, (1 << 22) // max(width, 1)))

    def step(i, acc):
        start = jnp.minimum(i * size, n - size)
        keep = (start + jnp.arange(size)) >= i * size
        return acc + partial(start, size, keep)

    return lax.fori_loop(0, -(-n // size), step,
                         jnp.zeros(width, jnp.float32))


def _rows(x, start, size):
    return lax.dynamic_slice_in_dim(x, start, size)


def group_sum_onehot(values, group_ids, num_groups: int, mask=None):
    """sum(values) per group via one-hot matmuls: (G, rows) @ (rows, c)
    on the MXU, one row block at a time.

    values: (n,) or (n, c) — c aggregates share one pass.
    Returns (G,) or (G, c) f32.
    """
    v = values if values.ndim == 2 else values[:, None]
    n, c = v.shape
    groups = jnp.arange(num_groups, dtype=group_ids.dtype)[:, None]

    def partial(start, size, keep):
        if mask is not None:
            keep = keep & _rows(mask, start, size)
        vb = jnp.where(keep[:, None], _rows(v, start, size), 0.0)
        onehot = (_rows(group_ids, start, size)[None, :] == groups)
        return jnp.dot(onehot.astype(jnp.float32), vb.astype(jnp.float32),
                       preferred_element_type=jnp.float32,
                       precision=EXACT).reshape(-1)

    out = _block_sum(n, num_groups * c, partial).reshape(num_groups, c)
    return out if values.ndim == 2 else out[:, 0]


def group_sum_maskgemm(values, group_ids, num_groups: int, mask=None):
    """sum(values) per group as ``mask @ (onehot (x) values)``: (G, c) f32.

    The batched lowering's form: group codes and values are
    parameter-independent and only ``mask`` varies per lane, so under
    ``vmap`` each row block is ONE ``(B, rows) x (rows, G*c)`` GEMM over
    the lane masks.  Out-of-range codes match no one-hot column and drop
    out."""
    n, c = values.shape
    groups = jnp.arange(num_groups, dtype=jnp.int32)

    def partial(start, size, keep):
        if mask is not None:
            keep = keep & _rows(mask, start, size)
        onehot = (_rows(group_ids, start, size)[:, None] == groups)
        v = _rows(values, start, size).astype(jnp.float32)
        expanded = (onehot.astype(jnp.float32)[:, :, None] * v[:, None, :]
                    ).reshape(size, num_groups * c)
        return jnp.dot(keep.astype(jnp.float32), expanded,
                       preferred_element_type=jnp.float32, precision=EXACT)

    return _block_sum(n, num_groups * c, partial).reshape(num_groups, c)


def group_count(group_ids, num_groups: int, mask=None):
    ones = jnp.ones(group_ids.shape[0], jnp.float32)
    return group_sum_onehot(ones, group_ids, num_groups, mask)


def group_sum_dense(values, keys, num_keys: int, mask=None):
    """Dense scatter-add aggregation for large key spaces: out[k] += v."""
    v = values.astype(jnp.float32)
    if mask is not None:
        v = jnp.where(mask, v, 0.0)
        keys = jnp.where(mask, keys, 0)
    return jnp.zeros(num_keys, jnp.float32).at[keys].add(v)


def blocks_table(child: str) -> str:
    """Name of the resident table that holds a clustered ``child``'s block
    starts (``clustered_sum.block_starts``, one ``first_row`` column,
    partitioned like the parent)."""
    return f"{child}_blocks"


def group_sum_clustered(values, keys, tables, child: str, num_keys: int,
                        fanout: int):
    """``group_sum_dense`` of the rows of ``child``, which is clustered by
    its foreign key ``keys``: non-decreasing, at most ``fanout`` rows per
    key.  Reads the child's block starts from ``tables`` (table name ->
    columns).  A segmented reduction over contiguous runs (the paper's
    co-partitioned, clustered storage, §3.1): no random write, no sort."""
    from repro.kernels import ops

    return ops.clustered_sum(values.astype(jnp.float32), keys,
                             tables[blocks_table(child)]["first_row"],
                             num_keys=num_keys, fanout=fanout)


def group_count_dense(keys, num_keys: int, mask=None):
    ones = jnp.ones(keys.shape[0], jnp.float32)
    return group_sum_dense(ones, keys, num_keys, mask)


def distributed_group_sum(values, group_ids, num_groups: int, mask=None, axis="nodes"):
    """Local one-hot aggregation + allreduce (paper Q1/Q4 pattern)."""
    return lax.psum(group_sum_onehot(values, group_ids, num_groups, mask), axis)


def segment_run_bounds(sorted_keys):
    """For each element of a sorted key array, the [start, end) bounds of its
    run of equal keys — vectorized run-length probe used by Q21's EXISTS
    logic (count of same-order / same-(order,supplier) lineitems)."""
    n = sorted_keys.shape[0]
    idx = jnp.arange(n, dtype=jnp.int32)
    left = jnp.searchsorted(sorted_keys, sorted_keys, side="left").astype(jnp.int32)
    right = jnp.searchsorted(sorted_keys, sorted_keys, side="right").astype(jnp.int32)
    del idx
    return left, right
