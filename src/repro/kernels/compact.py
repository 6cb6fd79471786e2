"""Row indices of a packed mask's set bits, in a fixed capacity (TPU).

A filtered stream often keeps few rows of a large partition: q14_promo's
month window keeps about one lineitem in 80.  Work that only the
survivors need, such as a random-access probe, then runs over their row
indices instead of over every row.  This module writes the node-local
row indices of the set bits of a packed mask
(``compression.pack_bitset`` words) into ``cap`` slots in one streaming
pass, with no gather, scatter or sort over the rows.

Layout.  The words are viewed as ``(words / 128, 128)``.  The (8, 128)
tile ``v`` holds words ``1024 v`` to ``1024 v + 1023``, and bit ``b`` of
every word in it is one *plane*: the 1024 rows ``32 (1024 v + p) + b``
at flat positions ``p = 128 r + c`` of the tile.  Planes are the unit of
work, in order ``(v, b)``.  Each plane's survivors take consecutive slots
in increasing ``p``.  :func:`plane_order` gives that order of the rows.

Two passes.  :func:`plane_offsets` counts each plane's survivors,
streaming over the words, and takes an exclusive cumsum over the plane
counts (about 58K planes for 60M rows): every plane's first slot.  The
kernel then compacts each plane inside its tile and stores it at that
slot:

- the exclusive rank of every survivor within its plane, by log-step
  prefix sums over lane and sublane rolls;
- a shift of each survivor toward the front by its count of dropped rows
  before it, one power of two per step from the lowest, so that no two
  survivors ever meet;
- a rotation by the plane's first slot modulo 1024, and a masked store
  into the one or two (8, 128) tiles of the output it spans.

The output stays in VMEM for the whole grid and is written back once.
The kernel has no branch: a plane with no survivors, or none below
``cap``, stores nothing, and slots past the survivors are left as they
were (undefined).  The plane offsets are scalar-prefetched into SMEM, at
most ``CALL_PLANES`` per ``pallas_call``; later calls own the slots from
their first plane's offset on.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE = 8 * LANES        # words of one (8, 128) tile: the rows of a plane
STEP_TILES = 8          # tiles per grid step
CALL_PLANES = 1 << 16   # plane offsets per call: 256 KiB of the 1 MiB SMEM


def _padded(words):
    """The words, padded with empty words to whole grid steps."""
    pad = (-words.shape[0]) % (TILE * STEP_TILES)
    return jnp.pad(words, (0, pad)) if pad else words


def plane_offsets(words):
    """The first slot of every plane, then the number of survivors:
    ``int32[planes + 1]``, the exclusive cumsum of the plane counts."""
    w = _padded(words).reshape(-1, TILE)
    bit = jnp.arange(32, dtype=jnp.uint32)
    counts = jnp.sum(((w[:, :, None] >> bit) & 1).astype(jnp.int32), axis=1)
    return jnp.concatenate([jnp.zeros(1, jnp.int32),
                            jnp.cumsum(counts.reshape(-1), dtype=jnp.int32)])


def plane_order(n_words: int):
    """Every row of ``n_words`` mask words, padded to whole tiles, in
    slot order (the oracle's view of the layout)."""
    tiles = -(-n_words // TILE)
    order = jnp.arange(tiles * TILE * 32, dtype=jnp.int32)
    return order.reshape(tiles, TILE, 32).transpose(0, 2, 1).reshape(-1)


def _toward_front(x, s, row, lane):
    """``y[p] = x[p + s]`` over the flat positions of an (8, 128) tile,
    0 past its end (``s`` a static power of two below 1024)."""
    if s < LANES:
        a = pltpu.roll(x, LANES - s, 1)          # a[r, c] = x[r, c + s]
        b = pltpu.roll(a, 7, 0)                  # b[r] = a[r + 1]
        return jnp.where(lane < LANES - s, a, jnp.where(row < 7, b, 0))
    t = s // LANES
    return jnp.where(row < 8 - t, pltpu.roll(x, 8 - t, 0), 0)


def _compact_plane(m, row, lane, flat):
    """The flat positions of the set lanes of the 0/1 tile ``m``, moved
    to the front in order; 0 elsewhere.  A survivor is carried as
    ``1 << 30 | shift << 10 | p`` while it moves."""
    x = m   # inclusive prefix sums along the lanes
    s = 1
    while s < LANES:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0)
        s *= 2
    y = jnp.where(row >= 1, pltpu.roll(jnp.broadcast_to(
        jnp.sum(m, axis=1, keepdims=True), m.shape), 1, 0), 0)
    s = 1   # exclusive prefix sums of the row totals along the sublanes
    while s < 8:
        y = y + jnp.where(row >= s, pltpu.roll(y, s, 0), 0)
        s *= 2
    drop = flat - (x + y - m)   # dropped rows before each position
    z = jnp.where(m > 0, (1 << 30) | (drop << 10) | flat, 0)
    for k in range(10):
        move = ((z >> (10 + k)) & 1) > 0
        came = _toward_front(jnp.where(move, z, 0), 1 << k, row, lane)
        z = jnp.where(came != 0, came, jnp.where(move, 0, z))
    return z & (TILE - 1)


def _rotate(x, s, row, lane):
    """``y[p] = x[(p - s) mod 1024]`` over the flat positions of an
    (8, 128) tile, for a traced ``s``."""
    a = pltpu.roll(x, s % LANES, 1)
    y = jnp.where(lane >= s % LANES, a, pltpu.roll(a, 1, 0))
    return pltpu.roll(y, s // LANES, 0)


def _kernel(offs_ref, words_ref, out_ref, *, first_tile, cap):
    step = pl.program_id(0)
    row = lax.broadcasted_iota(jnp.int32, (8, LANES), 0)
    lane = lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
    flat = row * LANES + lane
    for k in range(STEP_TILES):
        tile = step * STEP_TILES + k             # within this call
        w = words_ref[pl.ds(8 * k, 8), :]
        base = (first_tile + tile) * TILE        # the tile's first word

        def plane(b, carry, w=w, tile=tile, base=base):
            off = offs_ref[tile * 32 + b]       # the plane's first slot
            start = off % TILE                  # ... within its tile
            end = jnp.where(off < cap, start + offs_ref[tile * 32 + b + 1]
                            - off, start)       # nothing stored past cap
            m = ((w >> b.astype(jnp.uint32)) & 1).astype(jnp.int32)
            p = _compact_plane(m, row, lane, flat)
            y = _rotate(32 * (base + p) + b, start, row, lane)
            # the plane's tile of slots and the next, which it may reach
            r0 = pl.multiple_of(jnp.minimum(off, cap - 1) // TILE * 8, 8)
            out_ref[pl.ds(r0, 8), :] = jnp.where(
                (flat >= start) & (flat < end), y, out_ref[pl.ds(r0, 8), :])
            r1 = pl.multiple_of(r0 + 8, 8)
            out_ref[pl.ds(r1, 8), :] = jnp.where(
                flat < end - TILE, y, out_ref[pl.ds(r1, 8), :])
            return carry

        lax.fori_loop(0, 32, plane, 0)


def compact_rows(words, offsets, *, cap: int, interpret: bool = False):
    """The row indices of the set bits of ``words`` in slot order:
    ``int32[cap]``, of which the first ``min(cap, offsets[-1])`` are
    defined.  ``offsets`` is :func:`plane_offsets` of the same words."""
    w = _padded(words).reshape(-1, LANES)
    tiles = w.shape[0] // 8
    out_rows = (-(-cap // TILE) + 1) * 8    # a plane may spill one tile
    vmem = 2 * out_rows * LANES * 4 + (4 << 20)
    out = None
    for first in range(0, tiles, CALL_PLANES // 32):
        count = min(CALL_PLANES // 32, tiles - first)
        kernel = functools.partial(_kernel, first_tile=first, cap=cap)
        part = (pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(count // STEP_TILES,),
                in_specs=[pl.BlockSpec((8 * STEP_TILES, LANES),
                                       lambda i, o: (i, 0))],
                out_specs=pl.BlockSpec((out_rows, LANES),
                                       lambda i, o: (0, 0)),
            ),
            out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=max(vmem, 16 << 20)),
            interpret=interpret,
        )(lax.slice(offsets, (32 * first,), (32 * (first + count) + 1,)),
          lax.slice(w, (8 * first, 0), (8 * (first + count), LANES)))
            .reshape(-1)[:cap])
        out = part if out is None else jnp.where(
            jnp.arange(cap) >= offsets[32 * first], part, out)
    return out
