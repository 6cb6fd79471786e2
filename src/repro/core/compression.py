"""Communication compression (paper §3.2.1).

The paper compresses exchanged integer sets (keys, dictionary positions,
sparse bitsets) with delta encoding + vectorized variable-length codes
(FastPFor) and LZ4 for unsorted data.  On TPU we keep the paper's two cheap,
branch-free building blocks and drop the exception path of PFor (replaced by
a widened fixed width — the branchless variant):

- ``delta_encode / delta_decode``: increasing key sequences -> small deltas.
- ``pack_bits / unpack_bits``: fixed-width bit packing of non-negative ints
  into uint32 words (the "frame" part of PFor).  Packed words are what the
  exchange layer actually ships, so the byte reduction is visible in the
  lowered HLO, not just in an analytic model.

Also provides the paper's §3.2.2 analytic cost model for choosing between
semi-join alternatives (information-theoretic bits communicated).
"""
from __future__ import annotations

import jax.numpy as jnp
from jax import lax
import numpy as np

# ---------------------------------------------------------------------------
# delta coding for sorted key sets
# ---------------------------------------------------------------------------


def delta_encode(sorted_vals):
    """First element kept, then differences.  Input must be non-decreasing
    (the engine sorts key sets before shipping them, as the paper does for
    better compression — §5.3 discusses exactly this trade-off)."""
    first = sorted_vals[:1]
    deltas = sorted_vals[1:] - sorted_vals[:-1]
    return jnp.concatenate([first, deltas])


def delta_decode(deltas):
    return jnp.cumsum(deltas)


# ---------------------------------------------------------------------------
# fixed-width bit packing into uint32 words
# ---------------------------------------------------------------------------


def packed_words(n: int, width: int) -> int:
    """Number of uint32 words needed for n values of `width` bits."""
    return (n * width + 31) // 32


def _width_mask(width: int):
    return jnp.uint32((1 << width) - 1 if width < 32 else 0xFFFFFFFF)


def pack_bits(vals, width: int):
    """Pack ``vals`` (non-negative int32/uint32, < 2**width) into uint32
    words, little-endian bit order.  Values may straddle a word boundary;
    both halves are deposited with disjoint-bit scatters (adds of disjoint
    bits == or, which keeps this a pure vectorized gather/scatter — the
    TPU-friendly reformulation of SIMD shuffles).

    ``width == 0`` is the constant-column degenerate: every value is 0
    (after frame-of-reference subtraction) and the packed form is the
    empty word array — it round-trips through :func:`unpack_bits`."""
    assert 0 <= width <= 32
    n = vals.shape[0]
    if width == 0:
        return jnp.zeros(0, jnp.uint32)
    v = vals.astype(jnp.uint32) & _width_mask(width)
    bitpos = jnp.arange(n, dtype=jnp.uint32) * jnp.uint32(width)
    word = (bitpos >> 5).astype(jnp.int32)
    off = bitpos & jnp.uint32(31)
    nwords = packed_words(n, width)
    lo = (v << off).astype(jnp.uint32)
    # high part: bits that spill into the next word; shift by (32 - off)
    # guarded against off == 0 (shift by 32 is undefined) via two-step shift
    hi = jnp.where(off > 0, (v >> (jnp.uint32(32) - jnp.where(off > 0, off, 1))), 0)
    words = jnp.zeros(nwords, jnp.uint32)
    words = words.at[word].add(lo)  # disjoint bits -> add == or
    words = words.at[jnp.minimum(word + 1, nwords - 1)].add(
        jnp.where(word + 1 < nwords, hi, 0)
    )
    return words


def gather_bits(words, idx, width: int):
    """Random-access extract: value at each row index ``idx`` of a
    :func:`pack_bits` stream (the late-materialization primitive — decode
    only the surviving rows, never the full column)."""
    assert 0 <= width <= 32
    if width == 0:
        return jnp.zeros(idx.shape, jnp.uint32)
    bitpos = idx.astype(jnp.uint32) * jnp.uint32(width)
    word = (bitpos >> 5).astype(jnp.int32)
    off = bitpos & jnp.uint32(31)
    nwords = words.shape[0]
    lo = words[word] >> off
    nxt = words[jnp.minimum(word + 1, nwords - 1)]
    hi = jnp.where(off > 0, nxt << (jnp.uint32(32) - jnp.where(off > 0, off, 1)), 0)
    return (lo | hi) & _width_mask(width)


def unpack_bits(words, n: int, width: int):
    """Inverse of pack_bits; returns uint32 array of length n.

    Gather-free: 32 consecutive values occupy exactly ``width`` words, so
    value ``j`` of every 32-value group sits at a static word and bit
    offset — one strided word column per ``j``, shifted and masked.  (A
    gather per value compiles to code that grows with ``n`` on TPU.)"""
    assert 0 <= width <= 32
    if width == 0:
        return jnp.zeros(n, jnp.uint32)
    groups = -(-n // 32)
    need = groups * width
    if words.shape[0] < need:
        words = jnp.pad(words, (0, need - words.shape[0]))
    mask = _width_mask(width)

    def column(wi):  # word wi of every group: a strided 1-D slice
        return lax.slice(words, (wi,), (wi + (groups - 1) * width + 1,),
                         (width,))

    vals = []
    for j in range(32):
        bit = j * width
        wi, off = bit >> 5, bit & 31
        v = column(wi) >> jnp.uint32(off)
        if off + width > 32:  # static straddle into the next word
            v = v | (column(wi + 1) << jnp.uint32(32 - off))
        vals.append(v & mask)
    return jnp.stack(vals).T.reshape(-1)[:n]


def required_width(max_val: int) -> int:
    """Smallest width that can represent max_val (host-side helper).
    ``required_width(0) == 0``: a constant-zero column needs no bits —
    width-0 columns round-trip through pack/unpack as empty word arrays."""
    return int(max_val).bit_length()


# ---------------------------------------------------------------------------
# packed bitsets (paper §3.2.2 Alt-2 ships compressed bitsets)
# ---------------------------------------------------------------------------


def pack_bitset(bits):
    """bool[n] -> uint32[ceil(n/32)] (n must be a multiple of 32 for the
    engine's fixed shapes; callers pad).  Bit ``j`` of every word is one
    strided slice: the ``(n/32, 32)`` form compiles to code that grows
    with ``n`` on a TPU (112 s for 60M bits, against 3 s)."""
    n = bits.shape[0]
    assert n % 32 == 0, f"bitset length must be multiple of 32, got {n}"
    b = bits.astype(jnp.uint32)
    return sum(lax.slice(b, (j,), (n - 31 + j,), (32,)) << j
               for j in range(32))


def unpack_bitset(words, n: int):
    w = words[:, None]
    bits = (w >> jnp.arange(32, dtype=jnp.uint32)[None, :]) & jnp.uint32(1)
    return bits.reshape(-1)[:n].astype(bool)


def probe_bitset(words, idx):
    """Test bit ``idx`` of a packed bitset (vectorized)."""
    word = words[idx >> 5]
    return ((word >> (idx.astype(jnp.uint32) & jnp.uint32(31))) & jnp.uint32(1)).astype(bool)


# ---------------------------------------------------------------------------
# §3.2.2 analytic cost model (bits communicated per node)
# ---------------------------------------------------------------------------


def alt1_bits(n: float, m: float, P: int) -> float:
    """Request-based semi-join: n requests after local filtering (n/P per
    node), remote table of m rows: n/P * log2(m*P/n) bits per node."""
    if n <= 0:
        return 0.0
    return (n / P) * float(np.log2(max(m * P / n, 2.0)))


def alt2_bits(m: float, gamma: float) -> float:
    """Replicated-bitset semi-join: γm qualifying rows of an m-row table:
    γ·m·log2(1/γ) bits (information content of the bitset).

    Degenerate selectivities are explicit branches, not a fused ternary:
    γ <= 0 selects nothing — an all-zero bitset carries no information,
    0 bits; γ >= 1 selects everything — the entropy is also ~0, but the
    engine still ships the m-bit bitset, so the model charges the m raw
    bits actually communicated (the paper's curve is only defined on the
    open interval)."""
    if gamma <= 0:
        return 0.0
    if gamma >= 1:
        return float(m)
    return gamma * m * float(np.log2(1.0 / gamma))


def choose_semijoin(n: float, m: float, gamma: float, P: int) -> int:
    """Return 1 or 2 — the cheaper alternative under the paper's model.
    (Footnote 2: for n/P > m Alternative 2 is better anyway.)"""
    if n / P > m:
        return 2
    return 1 if alt1_bits(n, m, P) <= alt2_bits(m, gamma) else 2


# ---------------------------------------------------------------------------
# packed wire format parameters (shared by the exchange codec and the
# byte-accurate cost model, so the model is exact by construction)
# ---------------------------------------------------------------------------


def bitset_words(n: int) -> int:
    """uint32 words of an n-bit packed bitset."""
    return (max(n, 0) + 31) // 32


# The EF high parts live in a BOUNDED universe: the split always leaves at
# most EF_UNIVERSE distinct high values, so a decoder can reconstruct every
# high part from a fixed EF_UNIVERSE-1 zero-rank queries over the upper
# bitvector — static shape AND constant query count, no per-bit rank pass.
EF_UNIVERSE = 16


def ef_params(capacity: int, domain: int) -> tuple:
    """Elias–Fano split for ``capacity`` SORTED keys drawn from a
    per-destination domain of ``domain`` values: returns
    ``(l, upper_words, lower_words)``.

    Each key splits into ``l = max(0, ceil(log2(domain)) - 4)`` low bits
    (fixed-width packed — the "catalog-derived width" part) and a high
    part in the bounded universe ``[0, (domain-1) >> l] ⊆ [0, 15]``,
    encoded in unary in a bitvector of ``capacity + high_domain + 1``
    bits (the delta part: ~1 bit/key + at most 16 zero markers).  The
    bitvector keeps ``EF_UNIVERSE - 1`` structural spare zeros so the
    v-th-zero decode query always has an answer, for ANY capacity and
    ANY bucket fill.  Static shapes by construction — valid for any
    sorted bucket content, no exception path."""
    c = max(1, int(capacity))
    d = max(1, int(domain))
    l = max(0, (d - 1).bit_length() - 4) if d > 1 else 0
    hd = (d - 1) >> l  # largest high part, < EF_UNIVERSE by construction
    upper_bits = c + hd + 1 + (EF_UNIVERSE - 1)
    lw = packed_words(c, l) if l else 0
    return l, (upper_bits + 31) // 32, lw


def packed_request_words(capacity: int, domain: int) -> int:
    """uint32 words of one packed request row: EF upper bitvector + EF
    lower bits + the folded validity-mask bitset."""
    l, uw, lw = ef_params(capacity, domain)
    return uw + lw + bitset_words(capacity)


# ---------------------------------------------------------------------------
# byte-accurate §3.2.2 model: STATIC wire bytes of the compiled exchanges
# (what the lowered HLO actually ships), not the information bound above
# ---------------------------------------------------------------------------


def alt1_wire_bytes(capacity: int, P: int, domain: int = 0, *,
                    packed: bool = True, reply_bytes: int = 1) -> float:
    """Per-node bytes injected by the Alt-1 request/reply exchange at the
    plan's static buffer shapes: P-1 remote destination rows of
    ``capacity`` slots, requests plus replies.  raw = int32 key + bool
    mask + reply byte(s) per slot; packed = EF-coded keys with the mask
    folded in.  On packed wire only 1-byte (boolean) replies ship as a
    bitset — wider replies travel raw, exactly as ``request_reply``
    compiles them."""
    rows = max(P - 1, 1)
    if packed and domain > 0:
        reply_words = (bitset_words(capacity) if reply_bytes == 1
                       else -(-capacity * reply_bytes // 4))
        words = packed_request_words(capacity, domain) + reply_words
        return float(rows * words * 4)
    return float(rows * capacity * (4 + 1 + reply_bytes))


def alt2_wire_bytes(m: float, P: int) -> float:
    """Per-node bytes of the Alt-2 replicated bitset: the local partition's
    packed predicate bits (m/P rows), allgathered to the other P-1 nodes.
    Identical under raw and packed wire — Alt-2 always ships packed words."""
    local = (int(m) + max(P, 1) - 1) // max(P, 1)
    return float(max(P - 1, 1) * bitset_words(local) * 4)


def choose_semijoin_wire(capacity: int, m: float, P: int, *,
                         domain: int = 0, packed: bool = True,
                         cal=None) -> int:
    """Alternative choice at the plan's STATIC exchange shapes.  Returns
    1 or 2.

    Without a calibration this is the byte-accurate model: compare the
    wire bytes of the compiled Alt-1 exchange (at its derived capacity and
    actual packed widths) against the Alt-2 bitset allgather.  With a
    :class:`repro.core.wirecal.WireCalibration` it is LATENCY-accurate:
    codec time + link time + per-collective latency on both sides, so a
    cheap-bytes-but-extra-collectives alternative no longer wins on a
    latency-dominated link."""
    if cal is not None:
        from repro.core import wirecal

        c1, w1 = wirecal.predict_alt1_ms(capacity, P, domain,
                                         packed=packed and domain > 0,
                                         cal=cal)
        c2, w2 = wirecal.predict_alt2_ms(m, P, cal=cal)
        return 1 if c1 + w1 <= c2 + w2 else 2
    a1 = alt1_wire_bytes(capacity, P, domain, packed=packed)
    return 1 if a1 <= alt2_wire_bytes(m, P) else 2
