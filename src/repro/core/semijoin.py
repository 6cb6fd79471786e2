"""Remote-attribute filters / semi-joins (paper §3.2.2).

A query's WHERE clause references an attribute of a remote relation
("x.nation = :n" with x on another node).  Two alternatives:

Alt-1 (request): after all local filtering, ship the still-needed keys to
their owners; owners answer one bit per key.  ~n/P·log2(mP/n) bits per node.

Alt-2 (bitset): owners evaluate the predicate over their whole partition and
allgather the resulting bitset (packed, so the volume is visible in HLO);
every node then probes locally.  ~γm·log2(1/γ) bits.

A local probe after a filter (Alt-2's, or a co-partitioned one) touches
only the rows the filter keeps: :func:`probe_survivors` compacts their
row indices on the device and probes those alone.

``choose_alternative`` applies the paper's cost model; the plans pin the
choice the paper made per query and the benchmark sweeps both.
"""
from __future__ import annotations

from typing import Callable

import jax.numpy as jnp
from jax import lax

from repro.core import compression, exchange
from repro.core.partitioning import RangePartitioning


def alt1_request(
    keys,
    mask,
    part: RangePartitioning,
    local_predicate: Callable,
    *,
    capacity: int,
    axis: str = "nodes",
    backend: str = "xla",
    wire=None,
    observer=None,
    label: str = "",
):
    """Request-based semi-join: returns (bits aligned with keys, overflow).

    ``local_predicate(local_indices, mask) -> bool bits`` evaluates the
    remote predicate on the OWNER's partition, given local row indices.
    ``wire`` selects the exchange encoding (``exchange.WireFormat``;
    default raw) — a packed format ships EF-coded requests with the mask
    folded in and bitset-packed reply bits.  ``observer``/``label`` are
    forwarded to the exchange layer, which emits one trace-time event per
    compiled specialization.
    """
    def lookup(req_keys, req_mask):
        local_idx = part.local_index(req_keys)
        return local_predicate(local_idx, req_mask)

    bits, overflow = exchange.request_reply(
        keys,
        mask,
        part.owner(keys),
        lookup,
        capacity=capacity,
        axis=axis,
        backend=backend,
        reply_dtype=jnp.bool_,
        wire=wire,
        observer=observer,
        label=label,
    )
    return bits & mask, overflow


def alt2_bitset(
    local_bits,
    *,
    axis: str = "nodes",
):
    """Bitset-replication semi-join: every node contributes the predicate
    bits of its own partition; the packed bitset is allgathered so any node
    can probe any key locally.  Returns packed uint32 words covering the
    GLOBAL key space (row-major by node)."""
    n = local_bits.shape[0]
    pad = (-n) % 32
    if pad:
        local_bits = jnp.concatenate([local_bits, jnp.zeros(pad, bool)])
    packed = compression.pack_bitset(local_bits)
    return lax.all_gather(packed, axis, tiled=True)


# The compacted probe's capacities, as fractions of a node's rows.
PROBE_FRACTIONS = (64, 16)
FULL_WIDTH = len(PROBE_FRACTIONS)   # the tier of the probe over every row


def probe_capacities(rows: int) -> tuple:
    """The slot counts of the compacted probe over a ``rows``-row
    partition: for each fraction ``f``, the power of two at or above
    ``rows / f``."""
    return tuple(1 << max(-(-rows // f) - 1, 0).bit_length()
                 for f in PROBE_FRACTIONS)


def probe_survivors(mask, words, keys, test):
    """``test(keys(None)) & mask``, probing only the rows ``mask`` keeps.

    ``words`` is the mask's bitset (``compression.pack_bitset`` layout) or
    None; ``keys(idx)`` gives the join keys of the node-local rows ``idx``
    (every row's for ``None``) and ``test(keys)`` the semi-join's bits for
    them.  The survivors' row indices are compacted on the device
    (``kernels/compact.py``) into the smallest of :func:`probe_capacities`
    that holds them, and the bits of the hits go back into a bitset by a
    scatter of that many slots.  Where the survivors fill the largest
    capacity every row is probed.  Returns ``(bits, tier)``: the index of
    the capacity taken, ``FULL_WIDTH`` for every row."""
    from repro.kernels import compact, ops

    rows = mask.shape[0]
    if words is None:
        words = compression.pack_bitset(jnp.pad(mask, (0, (-rows) % 32)))
    offsets = compact.plane_offsets(words)
    caps = probe_capacities(rows)
    tier = sum((offsets[-1] > cap).astype(jnp.int32) for cap in caps)

    def compacted(cap):
        def run():
            idx = ops.compact_rows(words, offsets, cap=cap)
            kept = jnp.arange(cap) < offsets[-1]
            hit = test(keys(jnp.where(kept, idx, 0))) & kept
            bit = jnp.uint32(1) << (idx & 31).astype(jnp.uint32)
            hits = jnp.zeros_like(words).at[
                jnp.where(hit, idx >> 5, words.shape[0])].add(bit, mode="drop")
            return compression.unpack_bitset(hits, rows)
        return run

    def every_row():
        return test(keys(None)) & mask

    return lax.switch(tier, [compacted(c) for c in caps] + [every_row]), tier


def probe(global_bitset_words, keys, part: RangePartitioning):
    """Probe the replicated bitset for arbitrary global keys."""
    rows = part.rows_per_node
    padded = rows + ((-rows) % 32)
    owner = part.owner(keys)
    local = part.local_index(keys)
    bit_index = owner * padded + local
    return compression.probe_bitset(global_bitset_words, bit_index)


# re-export the paper's cost model (info-theoretic + byte-accurate wire)
alt1_bits = compression.alt1_bits
alt2_bits = compression.alt2_bits
choose_alternative = compression.choose_semijoin
alt1_wire_bytes = compression.alt1_wire_bytes
alt2_wire_bytes = compression.alt2_wire_bytes
choose_alternative_wire = compression.choose_semijoin_wire
