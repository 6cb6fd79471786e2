"""Segmented sum of a clustered child column into its parent rows (TPU).

A child table clustered by its foreign key (lineitem by ``l_orderkey``:
on every node the node-local keys never decrease, and one parent owns at
most ``fanout`` consecutive rows) reduces into its parents without a
scatter.  The children of parent block ``j`` (parents ``[128 j, 128 j +
128)``) are at most ``128 * fanout`` rows starting at ``block_starts[j]``,
so they lie in a window of ``fanout + 1`` lane-dense rows of 128 starting
at row ``block_starts[j] // 128``.  The kernel compares every key of that
window with the block's 128 parents on the VPU (a one-hot select), sums
the selected values in f32 and writes the block's 128 sums as one
lane-dense row.  Rows of neighbouring blocks fall outside ``[0, 128)`` and
drop out, as do keys of -1 (the padding).

Tiling: keys and values are viewed as ``(rows, 128)``.  A grid step
handles ``STEP_BLOCKS`` consecutive blocks, whose windows all lie in one
stretch of ``stretch`` rows that starts at the (8, 128) tile holding the
step's first child row; an element-indexed BlockSpec DMAs that stretch,
and each block's window is a dynamic sublane-offset load inside it.  The
block starts are scalar-prefetched into SMEM, at most ``CALL_BLOCKS`` of
them per ``pallas_call``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK = 128         # parents per block: one output lane each
STEP_BLOCKS = 8     # blocks per grid step: one (8, 128) output tile
CALL_BLOCKS = 1 << 16   # block starts per call: 256 KiB of the 1 MiB SMEM


def block_starts(fk, parent_rows: int, num_nodes: int) -> np.ndarray:
    """The kernel's ``block_starts`` for every node, built on the host:
    the node-local first child row of every block of ``BLOCK`` parent rows,
    node-major (``ceil(parent_rows / BLOCK)`` per node; a block without
    children starts where the next one does).  ``fk`` holds global keys,
    node-major, clustered on every node (``partitioning.clustered_fanout``).
    """
    firsts = np.arange(0, parent_rows, BLOCK)
    return np.concatenate([
        np.searchsorted(keys, node * parent_rows + firsts)
        for node, keys in enumerate(np.asarray(fk).reshape(num_nodes, -1))
    ]).astype(np.int32)


def _stretch_rows(fanout: int) -> int:
    """Rows DMA'd per grid step: from the tile of the step's first child
    row to the end of its last block's window, in whole (8, 128) tiles."""
    last = (1023 + (STEP_BLOCKS - 1) * fanout * BLOCK) // BLOCK
    return -(-(last + fanout + 1) // 8) * 8


def _kernel(starts_ref, keys_ref, vals_ref, out_ref, *, fanout, first_block):
    step = pl.program_id(0)
    top = starts_ref[step * STEP_BLOCKS] // 1024 * 8   # the stretch's row 0
    wr = fanout + 1   # rows of 128 holding a block's children from any lane
    parent = lax.broadcasted_iota(jnp.int32, (BLOCK, 128), 0)
    for k in range(STEP_BLOCKS):
        b = step * STEP_BLOCKS + k
        row = starts_ref[b] // 128 - top
        local = keys_ref[pl.ds(row, wr), :] - (first_block + b) * BLOCK
        vals = vals_ref[pl.ds(row, wr), :]
        acc = jnp.zeros((BLOCK, 128), jnp.float32)   # [parent, lane]
        for r in range(wr):
            hit = jnp.broadcast_to(local[r:r + 1, :], (BLOCK, 128)) == parent
            acc = acc + jnp.where(
                hit, jnp.broadcast_to(vals[r:r + 1, :], (BLOCK, 128)), 0.0)
        out_ref[pl.ds(k, 1), :] = jnp.sum(acc.T, axis=0, keepdims=True)


def clustered_sum(values, keys, block_starts, *, num_keys: int, fanout: int,
                  interpret: bool = False):
    """``out[p] = sum(values[i] for keys[i] == p)`` for ``p < num_keys``.

    values: (n,) f32;  keys: (n,) int32, non-decreasing over the rows whose
    key lies in ``[0, num_keys)``, each key at most ``fanout`` times;
    block_starts: (ceil(num_keys / 128),) int32, the first row whose key is
    at least ``128 j`` (one node's share of :func:`block_starts`).  Returns
    (num_keys,) f32.
    """
    n = keys.shape[0]
    nblk = block_starts.shape[0]
    stretch = _stretch_rows(fanout)
    rows = -(-n // 1024) * 8 + stretch   # every stretch lies in bounds
    keys2 = jnp.pad(keys.astype(jnp.int32), (0, rows * 128 - n),
                    constant_values=-1).reshape(rows, 128)
    vals2 = jnp.pad(values.astype(jnp.float32),
                    (0, rows * 128 - n)).reshape(rows, 128)
    padded = -(-nblk // STEP_BLOCKS) * STEP_BLOCKS
    # blocks past the last start where the rows end: empty windows
    starts = jnp.pad(block_starts.astype(jnp.int32), (0, padded - nblk),
                     constant_values=n)

    def window(step, s):
        return (s[step * STEP_BLOCKS] // 1024 * 8, 0)

    E = pl.Element
    outs = []
    for first in range(0, padded, CALL_BLOCKS):
        count = min(CALL_BLOCKS, padded - first)
        kernel = functools.partial(_kernel, fanout=fanout, first_block=first)
        outs.append(pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(count // STEP_BLOCKS,),
                in_specs=[pl.BlockSpec((E(stretch), E(128)), window),
                          pl.BlockSpec((E(stretch), E(128)), window)],
                out_specs=pl.BlockSpec((STEP_BLOCKS, 128),
                                       lambda step, s: (step, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((count, 128), jnp.float32),
            interpret=interpret,
        )(lax.slice(starts, (first,), (first + count,)), keys2, vals2))
    return jnp.concatenate(outs).reshape(-1)[:num_keys]
