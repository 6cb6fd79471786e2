"""Top-k selection on distributed partial aggregates (paper §3.2.5).

The hard case: aggregate values are NOT partitioned by key — every node
holds a partial sum for (potentially) every key, and the total per key is
the sum over all nodes.  Threshold algorithms (Fagin's TA, TPUT) degrade to
shipping nearly everything when partial sums are i.i.d. across nodes, so the
paper contributes a new algorithm that ships only a few BITS per partial sum:

  1. encode each partial sum with m bits starting at a bit offset shared by a
     group of keys (group = 1024); the offset is the highest one-bit position
     of the group maximum,
  2. personalized all-to-all routes the codes to each key's owner node,
  3. owners decode per-source lower/upper bounds and sum them per key,
  4. a merging reduction finds the global k-th highest LOWER bound — every
     key whose UPPER bound is below it can never reach the top-k and is
     pruned (safe: the k highest lower bounds witness k totals >= threshold),
  5. exact partial sums are fetched only for the few surviving candidates,
  6. a final merging reduction selects the global top-k.

Float adaptation: the paper's values are fixed-point integers (TPC-H money
in cents).  Our engine stores f32, so the codec first derives a fixed-point
scale from the global max partial (one scalar pmax — negligible traffic),
quantizes each partial to a 30-bit integer, and applies the paper's integer
scheme verbatim; the quantization error is absorbed into the lower/upper
bounds (widened by one quantum + a float-rounding epsilon), so pruning
remains SAFE for float totals.

The m-bit codes are physically bit-packed (``repro.core.compression``) before
the all-to-all, so the communication-volume reduction (8x at m=8 vs 64-bit
values in the paper; 4x vs our f32) is visible in the lowered HLO.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import compression, exchange, topk as topk_mod


class ApproxTopKStats(NamedTuple):
    naive_bits_per_node: jax.Array   # what the simple solution ships
    approx_bits_per_node: jax.Array  # step-2 codes + step-5 exact fetch
    num_candidates: jax.Array        # survivors after pruning (global)


def _significant_bits(x_u32):
    """Number of significant bits of a uint32 (0 for 0)."""
    # floor(log2(x)) + 1 via bit-length: count leading zeros through shifts
    x = x_u32
    bits = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        above = x >= (jnp.uint32(1) << shift)
        bits = jnp.where(above, bits + shift, bits)
        x = jnp.where(above, x >> shift, x)
    return bits + (x > 0).astype(jnp.uint32)


def encode_partials(partials_u32, m: int, group: int):
    """Step 1: m-bit codes with a group-shared shift.

    partials_u32: (K,) uint32, monotone encoding of the values.
    Returns codes (K,) uint32 in [0, 2^m) and shifts (K//group,) uint32.
    """
    K = partials_u32.shape[0]
    assert K % group == 0
    g = partials_u32.reshape(K // group, group)
    gmax = jnp.max(g, axis=1)
    nbits = _significant_bits(gmax)
    shift = jnp.maximum(nbits.astype(jnp.int32) - m, 0).astype(jnp.uint32)
    codes = (g >> shift[:, None]).reshape(K)
    return codes, shift


def decode_bounds(codes, shifts, group: int):
    """Lower/upper uint32 bounds from codes + group shifts."""
    K = codes.shape[0]
    s = jnp.repeat(shifts, group, total_repeat_length=K)
    lower = codes << s
    upper = lower + ((jnp.uint32(1) << s) - jnp.uint32(1))
    return lower, upper


_QUANT_BITS = 30
_EPS = jnp.float32(1e-6)


def approx_topk_distributed(
    partials,
    k: int,
    *,
    m: int = 8,
    group: int = 1024,
    candidate_capacity: int,
    axis: str = "nodes",
    backend: str = "xla",
):
    """§3.2.5 end to end, inside shard_map.

    partials: (K,) f32 per node, NON-NEGATIVE partial sums over the global
        key space (K divisible by P*group, keys range-partitioned).
    Returns (TopK over global totals, stats, overflow).
    """
    K = partials.shape[0]
    P = lax.axis_size(axis)
    assert K % P == 0, "key space must be divisible by node count"
    Kp = K // P
    assert Kp % group == 0, "per-node key range must hold whole groups"

    # ---- step 0: fixed-point quantization (float adaptation) ------------
    # one scalar pmax fixes the quantum; q <= 2^30 always fits uint32
    partials = partials.astype(jnp.float32)
    gmax = lax.pmax(jnp.max(partials), axis)
    scale = jnp.float32(1 << _QUANT_BITS) / jnp.maximum(gmax, jnp.float32(1e-30))
    q = jnp.clip(jnp.floor(partials * scale), 0, float(1 << _QUANT_BITS)).astype(
        jnp.uint32
    )

    # ---- step 1: encode -------------------------------------------------
    codes, shifts = encode_partials(q, m, group)

    # ---- step 2: pack + personalized all-to-all by key range ------------
    codes_by_dest = codes.reshape(P, Kp)
    shifts_by_dest = shifts.reshape(P, Kp // group)
    packed = jax.vmap(lambda c: compression.pack_bits(c, m))(codes_by_dest)
    recv_packed = exchange.all_to_all(packed, axis, backend=backend)
    recv_shifts = exchange.all_to_all(shifts_by_dest, axis, backend=backend)
    recv_codes = jax.vmap(lambda w: compression.unpack_bits(w, Kp, m))(recv_packed)

    # ---- step 3: per-source bounds, summed per key ----------------------
    lo_q, hi_q = jax.vmap(lambda c, s: decode_bounds(c, s, group))(
        recv_codes, recv_shifts
    )
    # back to value space; widen by one quantum (+float eps) so bounds stay
    # valid despite the floor() quantization and f32 rounding
    inv = jnp.float32(1.0) / scale
    lo = jnp.sum(lo_q.astype(jnp.float32) * inv, axis=0) * (1.0 - _EPS)
    hi = jnp.sum((hi_q.astype(jnp.float32) + 1.0) * inv, axis=0) * (1.0 + _EPS)

    # ---- step 4: global k-th highest lower bound ------------------------
    my_keys = lax.axis_index(axis) * Kp + jnp.arange(Kp, dtype=jnp.int32)
    local_lo_topk = topk_mod.local_topk(lo, my_keys, k)
    global_lo_topk = topk_mod.topk_allreduce(local_lo_topk, axis)
    threshold = global_lo_topk.values[k - 1]

    # ---- step 5: prune, fetch exact partials for survivors --------------
    cand_mask = hi >= threshold
    num_candidates = lax.psum(jnp.sum(cand_mask.astype(jnp.int32)), axis)
    C = min(candidate_capacity, Kp)
    # left-pack candidate keys into a fixed buffer
    slots, cand_valid = topk_mod.first_true(cand_mask, C)
    cand_keys = jnp.where(cand_valid, my_keys[slots], 0)
    overflow = jnp.sum(cand_mask.astype(jnp.int32)) > C
    # everyone learns everyone's candidates, answers with its exact partials
    all_cand = lax.all_gather(cand_keys, axis)          # (P, C) key ids
    all_valid = lax.all_gather(cand_valid, axis)        # (P, C)
    replies = jnp.where(all_valid, partials[all_cand.reshape(-1)].reshape(P, C), 0.0)
    exact_parts = exchange.all_to_all(replies, axis, backend=backend)  # (P, C) from each source
    exact_totals = jnp.sum(exact_parts, axis=0)         # (C,) totals for my candidates

    # ---- step 6: global top-k over exact candidate totals ---------------
    local_exact = topk_mod.local_topk(exact_totals, cand_keys, k, cand_valid)
    result = topk_mod.topk_allreduce(local_exact, axis)

    stats = ApproxTopKStats(
        naive_bits_per_node=jnp.float32(K * 32),
        approx_bits_per_node=jnp.float32(K * m + (K // group) * 8)
        + jnp.float32(C * 32) * 2.0,
        num_candidates=num_candidates,
    )
    return result, stats, overflow


def simple_topk_distributed(
    partials,
    k: int,
    *,
    axis: str = "nodes",
    backend: str = "xla",
):
    """The paper's naive baseline (Q15 variants 1/2): all_to_all ALL partial
    sums to each key's owner, aggregate, then select the top-k (backend
    chooses the library all-to-all vs the 1-factor schedule)."""
    K = partials.shape[0]
    P = lax.axis_size(axis)
    Kp = K // P
    by_dest = partials.reshape(P, Kp)
    recv = exchange.all_to_all(by_dest, axis, backend=backend)   # (P, Kp)
    totals = jnp.sum(recv, axis=0)
    my_keys = lax.axis_index(axis) * Kp + jnp.arange(Kp, dtype=jnp.int32)
    local = topk_mod.local_topk(totals, my_keys, k)
    return topk_mod.topk_allreduce(local, axis)
