"""Predicate-on-packed scan kernel: range tests over bit-packed words.

The resident format packs codes at ``width`` bits into uint32 words
(``core.columnar.PackedColumn``).  Because 32 consecutive values occupy
EXACTLY ``width`` words starting at a word boundary, a ``(R, width)``
reshape of the word stream (R = padded_rows/32) makes every extraction
offset STATIC: value ``j`` of a group lives at word ``(j*width)>>5``, bit
``(j*width)&31``, possibly straddling into the next word — a static
per-``j`` shift/or, no gathers.  The kernel evaluates the
dictionary/FOR-rewritten code-space predicate ``lo <= code <= hi``
(optionally negated) per word group and accumulates the 32 outcomes into
one validity-bitset word per group — the column is never expanded to
one-value-per-lane, so bytes touched stay at the packed footprint.

Same formulation twice: pure-XLA (the CPU path the benchmarks measure)
and a Pallas lane kernel for TPU (interpret-mode on CPU in parity tests).
The oracle lives in ``kernels/ref.py``; dispatch in ``kernels/ops.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_BLOCK = 2048  # bitset words (row groups of 32) per Pallas grid step


def _check(padded_rows: int, width: int) -> int:
    assert padded_rows % 32 == 0, "padded_rows must be a multiple of 32"
    assert 1 <= width <= 30, "code width must fit a non-negative int32"
    return padded_rows // 32


def _group_scan(word, lo, hi, base, *, rows: int, width: int, negate: bool):
    """Shared SWAR body.  ``word(wi)`` returns word ``wi`` of every 32-row
    group, ``base`` (same shape) the first row index of each group; the
    result holds one uint32 bitset word per group."""
    mask = jnp.uint32((1 << width) - 1)
    out = jnp.zeros(base.shape, jnp.uint32)
    for j in range(32):
        bit = j * width
        wi, off = bit >> 5, bit & 31
        va = word(wi) >> jnp.uint32(off)
        if off + width > 32:  # static straddle test
            va = va | (word(wi + 1) << jnp.uint32(32 - off))
        code = (va & mask).astype(jnp.int32)
        ok = (code >= lo) & (code <= hi)
        if negate:
            ok = jnp.logical_not(ok)
        ok = jnp.logical_and(ok, (base + j) < rows)
        out = out | (ok.astype(jnp.uint32) << jnp.uint32(j))
    return out


def scan_filter_xla(words, lo, hi, *, rows: int, padded_rows: int,
                    width: int, negate: bool = False):
    """Pure-XLA formulation; returns (padded_rows/32,) uint32 bitset."""
    R = _check(padded_rows, width)
    W = words.reshape(R, width)
    base = jnp.arange(R, dtype=jnp.int32) * 32
    return _group_scan(lambda wi: W[:, wi], jnp.asarray(lo, jnp.int32),
                       jnp.asarray(hi, jnp.int32), base, rows=rows,
                       width=width, negate=negate)


def _kernel(bounds_ref, w_ref, out_ref, *, rows, width, negate, bc):
    b = bounds_ref[...]                           # (1, 2) int32
    lo, hi = b[0, 0], b[0, 1]
    W = w_ref[...]                                # (width, bc) uint32
    r0 = pl.program_id(0) * bc
    base = (jax.lax.broadcasted_iota(jnp.int32, (1, bc), 1) + r0) * 32
    out_ref[...] = _group_scan(lambda wi: W[wi:wi + 1, :], lo, hi, base,
                               rows=rows, width=width, negate=negate)


def scan_filter_pallas(words, lo, hi, *, rows: int, padded_rows: int,
                       width: int, negate: bool = False,
                       block: int = DEFAULT_BLOCK, interpret: bool = False):
    """Pallas lane-kernel formulation.  The word stream is viewed
    group-transposed, ``(width, groups)``: word ``wi`` of every group is
    one lane-dense row, so the 32 extractions are static row slices and
    the output is one lane-dense row of bitset words.  (A ``(groups,
    width)`` block would pad ``width`` to the 128 lanes in HBM.)"""
    R = _check(padded_rows, width)
    # row wi = word wi of every group: strided slices, not a 2-D reshape
    # (whose (groups, width) layout would be padded to 128 lanes)
    Wt = jnp.stack([jax.lax.slice(words, (wi,), (wi + (R - 1) * width + 1,),
                                  (width,)) for wi in range(width)])
    bc = min(block, R)
    pad = (-R) % bc
    if pad:  # zero groups decode to code 0 but base >= rows masks them off
        Wt = jnp.pad(Wt, ((0, 0), (0, pad)))
    Rp = R + pad
    bounds = jnp.stack([jnp.asarray(lo, jnp.int32),
                        jnp.asarray(hi, jnp.int32)]).reshape(1, 2)
    kernel = functools.partial(_kernel, rows=rows, width=width,
                               negate=negate, bc=bc)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // bc,),
        in_specs=[pl.BlockSpec((1, 2), lambda i: (0, 0)),
                  pl.BlockSpec((width, bc), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, bc), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Rp), jnp.uint32),
        interpret=interpret,
    )(bounds, Wt)
    return out[0, :R]
