"""Wire-codec kernels (§3.2.1): blockwise Elias–Fano bucket encode/decode
and the fused mask-fold/bitset-append stage.

The exchange layer's packed wire format splits every destination-relative
key into ``l`` fixed-width low bits and a unary-coded high part whose
universe is bounded to ``compression.EF_UNIVERSE`` values; this module is
the FAST implementation of that codec, pinned bit-for-bit to the pure-jnp
oracles in :mod:`repro.kernels.ref` by the parity tests.

Two tiers, selected by ``use_pallas``:

- The gather-light XLA formulation (default off-TPU).  The oracle's
  per-bit rank pass and big scatters dominate the compiled exchange on
  CPU, so every hot stage here is reformulated around tiny-state work:
  the encoder finds the ``EF_UNIVERSE - 1`` upper-bitvector zero markers
  with a binary search over the bucket (15 columns of state, not
  ``capacity``), builds the bitvector as ``ones-band & ~zero-markers``,
  and lane-packs low bits and mask with reshapes; the decoder locates
  each zero with a per-word popcount prefix + in-word SWAR select, then
  reconstructs all high parts from the 15 marker positions with 15
  one-element-per-row scatters and a single prefix sum.  No stage gathers
  or scatters a ``capacity``-sized index set.

- Pallas kernels for the bandwidth-bound lane stages (mask fold/unfold,
  EF lower-bits pack/unpack when ``32 % l == 0``), tiled over blocks of
  packed words.  ``interpret=True`` runs them anywhere for parity
  testing; the compiled path is for real accelerator backends —
  interpret mode executes Python per grid step and would lose the
  exchange latency gate, so CPU dispatch (``kernels.ops``) uses the XLA
  formulation above as its fast path.

Straddling low-bit widths (``32 % l != 0``) always take the XLA
formulation — the word-straddle gather is the wrong shape for a lane
kernel and those widths do not occur for power-of-two domains.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import compression
from repro.core.compression import EF_UNIVERSE

def _popcount(x):
    """SWAR popcount of a uint32 array."""
    x = x - ((x >> 1) & jnp.uint32(0x55555555))
    x = (x & jnp.uint32(0x33333333)) + ((x >> 2) & jnp.uint32(0x33333333))
    x = (x + (x >> 4)) & jnp.uint32(0x0F0F0F0F)
    return (x * jnp.uint32(0x01010101)) >> 24


# ---------------------------------------------------------------------------
# Pallas lane kernels: fixed-width fields <-> uint32 words
#
# Both wire stages that have a kernel are the same operation at two field
# widths: the validity mask packs 32 one-bit fields per word, the EF lower
# bits 32/l l-bit fields.  The kernels see the fields as an (N, k) array,
# one word per row, and tile N in row blocks: a block is (br, k) with k the
# full minor dim, so every block shape the TPU lowering accepts.  Packing
# shifts each field to its place and sums the row in int32 (disjoint bits,
# so the wrapping sum is the OR): Mosaic lowers no reduction over unsigned
# integers, and an OR of shifted one-lane slices came out wrong on a v5e
# for blocks of more than 8 rows (right in interpret mode).
# ---------------------------------------------------------------------------

_BLOCK_WORDS = 1024  # word rows per grid step (multiple of 8)


def _pack_kernel(x_ref, out_ref, *, shift):
    x = x_ref[...]                                # (br, k) uint32 fields
    sh = jax.lax.broadcasted_iota(jnp.uint32, x.shape, 1) * jnp.uint32(shift)
    y = jax.lax.bitcast_convert_type(x << sh, jnp.int32)
    out_ref[...] = jax.lax.bitcast_convert_type(
        jnp.sum(y, axis=1, keepdims=True), jnp.uint32)


def _unpack_kernel(w_ref, out_ref, *, shift):
    w = w_ref[...]                                # (br, 1) uint32 words
    sh = jax.lax.broadcasted_iota(jnp.uint32, out_ref.shape, 1) \
        * jnp.uint32(shift)
    out_ref[...] = (w >> sh) & jnp.uint32((1 << shift) - 1)


def _word_call(kernel, x, out_cols, interpret):
    """Run ``kernel`` over the rows of ``x`` (N, in_cols) uint32 in blocks
    of ``_BLOCK_WORDS`` rows (zero-padded); returns (N, out_cols) uint32."""
    n, in_cols = x.shape
    br = min(_BLOCK_WORDS, n)
    pad = (-n) % br
    if pad:
        x = jnp.pad(x, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        kernel,
        grid=((n + pad) // br,),
        in_specs=[pl.BlockSpec((br, in_cols), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((br, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n + pad, out_cols), jnp.uint32),
        interpret=interpret,
    )(x)
    return out[:n]


def _pack_fields(fields, shift: int, interpret: bool):
    """(..., W, k) uint32 fields of ``shift`` bits -> (..., W) words."""
    *lead, k = fields.shape
    kernel = functools.partial(_pack_kernel, shift=shift)
    out = _word_call(kernel, fields.reshape(-1, k), 1, interpret)
    return out.reshape(lead)


def _unpack_fields(words, k: int, shift: int, interpret: bool):
    """Inverse of :func:`_pack_fields`: (..., W) words -> (..., W, k)."""
    kernel = functools.partial(_unpack_kernel, shift=shift)
    out = _word_call(kernel, words.reshape(-1, 1), k, interpret)
    return out.reshape(*words.shape, k)


# ---------------------------------------------------------------------------
# mask fold/unfold (the validity bitset appended to every packed row)
# ---------------------------------------------------------------------------


def mask_fold(mask, *, use_pallas: bool = False, interpret: bool = False):
    """(P, c) bool -> (P, ceil(c/32)) uint32 bitset rows."""
    rows, c = mask.shape
    pad = (-c) % 32
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    cw = mask.shape[1] // 32
    x = mask.reshape(rows, cw, 32).astype(jnp.uint32)
    if use_pallas:
        return _pack_fields(x, 1, interpret)
    w = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    return jnp.sum(x * w, axis=2, dtype=jnp.uint32)


def mask_unfold(words, n: int, *, use_pallas: bool = False,
                interpret: bool = False):
    """Inverse of :func:`mask_fold`: (P, w) uint32 -> (P, n) bool."""
    rows, cw = words.shape
    if use_pallas:
        bits = _unpack_fields(words, 32, 1, interpret).astype(bool)
    else:
        lane = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
        bits = ((words[:, :, None] >> lane) & jnp.uint32(1)).astype(bool)
    return bits.reshape(rows, cw * 32)[:, :n]


# ---------------------------------------------------------------------------
# EF lower-bits lane pack/unpack
# ---------------------------------------------------------------------------


def _lower_pack(lov, l: int, lw: int, use_pallas, interpret):
    """(P, cap) uint32 values < 2^l -> (P, lw) packed words."""
    rows, cap = lov.shape
    if 32 % l == 0:
        k = 32 // l
        pad = lw * k - cap
        if pad:
            lov = jnp.pad(lov, ((0, 0), (0, pad)))
        x = lov.reshape(rows, lw, k)
        if use_pallas:
            return _pack_fields(x, l, interpret)
        sh = (jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(l))[None, None, :]
        return jnp.sum(x << sh, axis=2, dtype=jnp.uint32)
    # straddling width: each word collects the <= ceil(32/l)+1 values that
    # overlap it, via a short unrolled loop of one-column gathers
    K = 32 // l + 1
    wk = jnp.arange(lw, dtype=jnp.int32)[None, :]
    word = jnp.zeros((rows, lw), jnp.uint32)
    j0 = (wk * 32) // l
    for k in range(K + 1):
        jv = j0 + k
        valid = ((jv * l < (wk + 1) * 32) & ((jv + 1) * l > wk * 32)
                 & (jv < cap))
        v = jnp.take_along_axis(lov, jnp.minimum(jv, cap - 1), axis=1)
        sh = jv * l - wk * 32
        contrib = jnp.where(
            sh >= 0,
            v << jnp.minimum(sh, 31).astype(jnp.uint32),
            v >> jnp.minimum(-sh, 31).astype(jnp.uint32),
        )
        word = word | jnp.where(valid, contrib, 0)
    return word


def _lower_unpack(lower, l: int, cap: int, use_pallas, interpret):
    """(P, lw) packed words -> (P, cap) uint32 values < 2^l."""
    rows, lw = lower.shape
    if 32 % l == 0:
        k = 32 // l
        if use_pallas:
            vals = _unpack_fields(lower, k, l, interpret)
            return vals.reshape(rows, lw * k)[:, :cap]
        sh = (jnp.arange(k, dtype=jnp.uint32) * jnp.uint32(l))[None, None, :]
        vals = (lower[:, :, None] >> sh) & jnp.uint32((1 << l) - 1)
        return vals.reshape(rows, lw * k)[:, :cap]
    j = jnp.arange(cap, dtype=jnp.int32)[None, :]
    bit = j * l
    wk = bit >> 5
    sh = (bit & 31).astype(jnp.uint32)
    w0 = jnp.take_along_axis(lower, jnp.minimum(wk, lw - 1), axis=1)
    w1 = jnp.take_along_axis(lower, jnp.minimum(wk + 1, lw - 1), axis=1)
    return ((w0 >> sh) | jnp.where(sh > 0, w1 << (jnp.uint32(32) - sh), 0)) \
        & jnp.uint32((1 << l) - 1)


# ---------------------------------------------------------------------------
# blockwise EF bucket encode
# ---------------------------------------------------------------------------


def ef_encode(buckets, bucket_mask, domain: int, *, use_pallas: bool = False,
              interpret: bool = False):
    """Encode (P, capacity) sorted key buckets into packed wire rows
    (P, ``compression.packed_request_words(capacity, domain)``) uint32.
    Bit-identical to :func:`repro.kernels.ref.ef_encode`."""
    rows, cap = buckets.shape
    l, uw, lw = compression.ef_params(cap, domain)
    base = (jnp.arange(rows, dtype=jnp.int32) * domain)[:, None]
    offs = jnp.clip(jnp.where(bucket_mask, buckets - base, 0),
                    0, domain - 1).astype(jnp.uint32)
    hi = (offs >> l).astype(jnp.int32)
    n = jnp.sum(bucket_mask, axis=1, dtype=jnp.int32)[:, None]
    # v-th zero marker position: (#keys with high part < v) + v - 1, found
    # by binary-searching the sorted high parts — 15 columns of state
    him = jnp.where(bucket_mask, hi, jnp.int32(1 << 30))
    vq = jnp.arange(1, EF_UNIVERSE, dtype=jnp.int32)[None, :]
    lo_b = jnp.zeros((rows, EF_UNIVERSE - 1), jnp.int32)
    hi_b = jnp.full((rows, EF_UNIVERSE - 1), cap, jnp.int32)
    for _ in range(int(cap).bit_length()):
        mid = (lo_b + hi_b) >> 1
        am = jnp.take_along_axis(him, jnp.minimum(mid, cap - 1), axis=1)
        go = am < vq
        lo_b = jnp.where(go, mid + 1, lo_b)
        hi_b = jnp.where(go, hi_b, mid)
    z = lo_b + vq - 1
    hlast = jnp.take_along_axis(hi, jnp.maximum(n - 1, 0), axis=1)
    hlast = jnp.where(n > 0, hlast, 0)
    end = n + hlast                      # bits used by the unary coding
    w = jnp.arange(uw, dtype=jnp.int32)[None, :]
    rem = jnp.clip(end - w * 32, 0, 32)
    band = jnp.where(rem >= 32, jnp.uint32(0xFFFFFFFF),
                     (jnp.uint32(1) << rem.astype(jnp.uint32)) - 1)
    zb = jnp.zeros((rows, uw), jnp.uint32)
    for v in range(EF_UNIVERSE - 1):
        zv = z[:, v][:, None]
        inw = (zv >> 5) == w
        zb = zb | jnp.where(
            inw & (zv < end),
            jnp.uint32(1) << (zv & 31).astype(jnp.uint32), 0)
    parts = [band & ~zb]
    if l:
        lov = jnp.where(bucket_mask, offs & jnp.uint32((1 << l) - 1),
                        jnp.uint32(0))
        parts.append(_lower_pack(lov, l, lw, use_pallas, interpret))
    parts.append(mask_fold(bucket_mask, use_pallas=use_pallas,
                           interpret=interpret))
    return jnp.concatenate(parts, axis=1)


# ---------------------------------------------------------------------------
# blockwise EF bucket decode
# ---------------------------------------------------------------------------


def ef_decode(words, capacity: int, domain: int, my_base, *,
              use_pallas: bool = False, interpret: bool = False):
    """Inverse of :func:`ef_encode` on the receiving node: returns
    (global keys (P, capacity) int32, mask (P, capacity) bool).
    Bit-identical to :func:`repro.kernels.ref.ef_decode`."""
    rows = words.shape[0]
    l, uw, lw = compression.ef_params(capacity, domain)
    upper = words[:, :uw]
    mk = mask_unfold(
        words[:, uw + lw:uw + lw + compression.bitset_words(capacity)],
        capacity, use_pallas=use_pallas, interpret=interpret)
    # word-granular zero-rank prefix, then binary search for the word
    # holding each of the 15 zero markers
    pc0 = (32 - _popcount(upper)).astype(jnp.int32)
    W0 = jnp.cumsum(pc0, axis=1, dtype=jnp.int32)
    vq = jnp.arange(1, EF_UNIVERSE, dtype=jnp.int32)[None, :]
    lo_b = jnp.zeros((rows, EF_UNIVERSE - 1), jnp.int32)
    hi_b = jnp.full((rows, EF_UNIVERSE - 1), uw, jnp.int32)
    for _ in range(int(uw).bit_length()):
        mid = (lo_b + hi_b) >> 1
        am = jnp.take_along_axis(W0, jnp.minimum(mid, uw - 1), axis=1)
        go = am < vq
        lo_b = jnp.where(go, mid + 1, lo_b)
        hi_b = jnp.where(go, hi_b, mid)
    wz = jnp.minimum(lo_b, uw - 1)
    W0pad = jnp.concatenate([jnp.zeros((rows, 1), jnp.int32), W0], axis=1)
    r = vq - 1 - jnp.take_along_axis(W0pad, wz, axis=1)
    # in-word select of the r-th zero: SWAR halving on the inverted word
    word = ~jnp.take_along_axis(upper, wz, axis=1)
    pos = jnp.zeros(word.shape, jnp.int32)
    for half in (16, 8, 4, 2, 1):
        low = word & jnp.uint32((1 << half) - 1)
        c = _popcount(low).astype(jnp.int32)
        go = r >= c
        r = jnp.where(go, r - c, r)
        pos = pos + jnp.where(go, half, 0)
        word = jnp.where(go, word >> half, low)
    Hi = wz * 32 + pos - vq + 1          # (rows, 15), non-decreasing
    # hi[j] = #{v : Hi[v] <= j}: run-length deltas via 15 one-element
    # row scatters, then one prefix sum — never a capacity-sized scatter
    ridx = jnp.arange(rows, dtype=jnp.int32)
    d = jnp.zeros((rows, capacity + 1), jnp.int32)
    for v in range(EF_UNIVERSE - 1):
        d = d.at[ridx, jnp.clip(Hi[:, v], 0, capacity)].add(1)
    hi = jnp.cumsum(d[:, :capacity], axis=1, dtype=jnp.int32)
    if l:
        lo = _lower_unpack(words[:, uw:uw + lw], l, capacity,
                           use_pallas, interpret).astype(jnp.int32)
    else:
        lo = jnp.zeros((rows, capacity), jnp.int32)
    keys = jnp.where(mk, my_base + ((hi << l) | lo), 0).astype(jnp.int32)
    return keys, mk
