"""Fused filter + grouped aggregation Pallas kernel (TPU).

The paper's dominant inner loop (Q1: predicate + 6-group, 6-measure
aggregate over lineitem) is a scalar hash-table update per row on CPUs.  The
TPU-native formulation: evaluate the predicate on the VPU and contract a
one-hot group matrix against the measure block on the MXU —
``out[g, c] += sum_n onehot[g, n] * measures[n, c]``.

Tiling: the measure block (C, BN) and the one-hot (G, BN) both live in VMEM;
G and C are tiny (<= 64), BN is the streaming dimension.  The (G, C)
accumulator is the kernel output, revisited every grid step (sequential TPU
grid), initialized at step 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 2048  # rows per grid step; (C, BN) f32 tile ~ 8*2048*4 = 64 KiB


def _kernel(measures_ref, keys_ref, out_ref, *, cutoff, num_groups):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    measures = measures_ref[...]          # (C, BN) f32, rows lane-dense
    keys = keys_ref[...]                  # (2, BN) i32: group id, pred
    groups, pred = keys[0:1, :], keys[1:2, :]
    bn = measures.shape[1]
    sel = pred <= cutoff                  # fused predicate (VPU)
    gids = lax.broadcasted_iota(jnp.int32, (num_groups, bn), 0)
    onehot = jnp.where((groups == gids) & sel, 1.0, 0.0).astype(jnp.float32)
    out_ref[...] += lax.dot_general(      # (G, BN) x (C, BN)^T -> (G, C)
        onehot, measures, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=lax.Precision.HIGHEST)


def filtered_group_sum(
    measures,
    groups,
    pred,
    cutoff,
    num_groups: int,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
):
    """sum(measures[n]) per group over rows with pred[n] <= cutoff.

    measures: (N, C) f32;  groups: (N,) i32 in [0, num_groups);
    pred: (N,) i32 (e.g. l_shipdate);  cutoff: static int.
    Returns (num_groups, C) f32.

    The kernel reads the measures as C lane-dense rows and the group ids
    with the predicate column as one (2, N) array: an (N, C) block would
    pad C to the 128 lanes in HBM (8 GB at 16M rows).
    """
    n, c = measures.shape
    pad = (-n) % block
    keys = jnp.stack([groups.astype(jnp.int32), pred.astype(jnp.int32)])
    mt = measures.T
    if pad:
        mt = jnp.pad(mt, ((0, 0), (0, pad)))
        # padded rows fail the predicate
        keys = jnp.pad(keys, ((0, 0), (0, pad)),
                       constant_values=((0, 0), (0, cutoff + 1)))
    n_pad = n + pad
    grid = (n_pad // block,)
    kernel = functools.partial(_kernel, cutoff=cutoff, num_groups=num_groups)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, block), lambda i: (0, i)),
            pl.BlockSpec((2, block), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((num_groups, c), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((num_groups, c), jnp.float32),
        interpret=interpret,
    )(mt, keys)
