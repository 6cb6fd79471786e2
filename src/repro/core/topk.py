"""Distributed top-k selection (paper §3.2.3, §3.2.4).

- ``local_topk``: per-node top-k (step 1 of the paper's scheme).
- ``topk_allreduce``: the paper's merging reduction — sorted k-vectors are
  combined pairwise, keeping the best k, in a log2(P)-depth butterfly
  (Θ(k log P) bottleneck volume vs Θ(kP) for the naive gather).
- ``topk_gather``: the naive gather baseline the paper compares against.
- ``lazy_filtered_topk``: §3.2.4 — when a remote filter disqualifies keys,
  request filter bits only for chunks of locally-best candidates until k
  survivors are found (expected k/p keys communicated instead of all).

Ties: ranking uses (value desc, tiebreak asc) so results are deterministic
and match the numpy oracle — the paper sorts output rows the same way.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import exchange

NEG_INF = jnp.float32(-jnp.inf)


class TopK(NamedTuple):
    values: jax.Array  # (k,) f32, descending
    keys: jax.Array    # (k,) i32 — payload (row key) per entry
    valid: jax.Array   # (k,) bool


def _rank_order(values, tiebreak, valid):
    """Sort order: valid desc, value desc, tiebreak asc."""
    v = jnp.where(valid, values.astype(jnp.float32), NEG_INF)
    # composite: sort by (-v, tiebreak) — use lexsort via argsort of keys
    order = jnp.lexsort((tiebreak, -v, ~valid))
    return order


# Partitions of at least this many rows select their top-k candidates
# without sorting the partition: a TPU sort of millions of rows takes
# minutes to compile, a select costs 64 counting passes.
SELECT_MIN_ROWS = 1 << 16


def _select(values, tiebreak, valid, k: int):
    """Row indices (k,) of the top-k rows in rank order's SET (valid
    first, value desc, tiebreak asc), unordered: the k-th largest order
    key is found by a 32-step bitwise search of counts, then ties at it
    by a second search over the tiebreak; a prefix count places the
    selected rows."""
    v = values.astype(jnp.float32) + jnp.float32(0.0)  # -0.0 ranks as 0.0
    b = lax.bitcast_convert_type(v, jnp.uint32)
    u = jnp.where((b >> 31) == 1, ~b, b | jnp.uint32(1 << 31))
    u = jnp.where(valid, jnp.maximum(u, 1), jnp.uint32(0))
    kk = lax.bitcast_convert_type(tiebreak.astype(jnp.int32),
                                  jnp.uint32) ^ jnp.uint32(1 << 31)

    def search(holds):
        # largest uint32 t with holds(t), holds monotone and true at 0:
        # one bit per step from the top
        def step(i, t):
            cand = t | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
            return jnp.where(holds(cand), cand, t)
        return lax.fori_loop(0, 32, step, jnp.uint32(0))

    def count(m):
        return jnp.sum(m, dtype=jnp.int32)

    t_k = search(lambda t: count(u >= t) >= k)    # the k-th largest key
    need = k - count(u > t_k)                     # >= 1 rows tied at t_k
    tied = u == t_k
    k_tie = search(lambda t: count(tied & (kk < t)) < need)
    sel = (u > t_k) | (tied & (kk <= k_tie))
    return first_true(sel, k)[0]


def first_true(mask, size: int):
    """Indices of the first ``size`` true entries of ``mask``, in order,
    and which of those slots exist — a left-pack by prefix count and
    binary search, not a sort."""
    pos = jnp.cumsum(mask, dtype=jnp.int32)
    idx = jnp.searchsorted(pos, jnp.arange(1, size + 1, dtype=jnp.int32))
    return jnp.minimum(idx, mask.shape[0] - 1), idx < mask.shape[0]


def local_topk(values, keys, k: int, mask=None) -> TopK:
    """Top-k rows of the local partition by value (desc), key asc tiebreak."""
    n = values.shape[0]
    valid = jnp.ones(n, bool) if mask is None else mask
    if n >= SELECT_MIN_ROWS and k < n:
        rows = _select(values, keys, valid, k)
        values, keys, valid = values[rows], keys[rows], valid[rows]
    order = _rank_order(values, keys, valid)[:k]
    return TopK(
        values=jnp.where(valid[order], values[order].astype(jnp.float32), NEG_INF),
        keys=keys[order],
        valid=valid[order],
    )


def merge_topk(a: TopK, b: TopK) -> TopK:
    """The paper's user-defined reduce operator: merge two sorted k-lists,
    keep the best k."""
    k = a.values.shape[0]
    values = jnp.concatenate([a.values, b.values])
    keys = jnp.concatenate([a.keys, b.keys])
    valid = jnp.concatenate([a.valid, b.valid])
    order = _rank_order(values, keys, valid)[:k]
    return TopK(values[order], keys[order], valid[order])


def topk_allreduce(local: TopK, axis: str = "nodes") -> TopK:
    """§3.2.3 merging reduction as a recursive-doubling butterfly; every node
    ends with the global top-k."""
    return exchange.butterfly_allreduce(local, merge_topk, axis)


def topk_gather(local: TopK, axis: str = "nodes") -> TopK:
    """Naive baseline: allgather all P·k candidates, then select k."""
    k = local.values.shape[0]
    values = lax.all_gather(local.values, axis, tiled=True)
    keys = lax.all_gather(local.keys, axis, tiled=True)
    valid = lax.all_gather(local.valid, axis, tiled=True)
    order = _rank_order(values, keys, valid)[:k]
    return TopK(values[order], keys[order], valid[order])


def lazy_filtered_topk(
    values,
    keys,
    mask,
    remote_filter: Callable,
    k: int,
    *,
    chunk: int,
    max_rounds: int,
    axis: str = "nodes",
) -> TopK:
    """§3.2.4: top-k where a remote predicate disqualifies keys.

    ``remote_filter(keys, mask) -> (bits, overflow)`` evaluates the remote
    predicate for a masked chunk of keys (an Alt-1 request under the hood).
    Rounds proceed over chunks of locally-best unfiltered candidates until k
    local survivors are found (or the candidate pool is exhausted), then one
    merging reduction finds the global winners.

    Static shapes: the candidate pool is sorted once; round i examines
    slots [i*chunk, (i+1)*chunk).  max_rounds bounds the lax.while_loop,
    so only the best ``max_rounds * chunk`` candidates can ever be
    examined: a large partition selects those first and sorts only them.
    """
    pool = max_rounds * chunk
    if values.shape[0] >= SELECT_MIN_ROWS and pool < values.shape[0]:
        rows = _select(values, keys, mask, pool)
        values, keys, mask = values[rows], keys[rows], mask[rows]
    n = values.shape[0]
    order = _rank_order(values, keys, mask)
    sv = jnp.where(mask[order], values[order].astype(jnp.float32), NEG_INF)
    sk = keys[order]
    svalid = mask[order]

    pass_bits = jnp.zeros(n, bool)     # passed remote filter
    examined = jnp.zeros(n, bool)

    def cond(state):
        i, pass_bits, examined, overflow = state
        survivors = jnp.sum((pass_bits & examined).astype(jnp.int32))
        # every node keeps requesting until IT has k survivors or no
        # unexamined valid candidates remain; all nodes iterate in lockstep
        # (collectives inside), so reduce the condition globally.
        more_local = (survivors < k) & jnp.any(svalid & ~examined)
        more = lax.psum(more_local.astype(jnp.int32), axis) > 0
        return (i < max_rounds) & more

    def body(state):
        i, pass_bits, examined, overflow = state
        start = i * chunk
        idx = start + jnp.arange(chunk, dtype=jnp.int32)
        idx = jnp.minimum(idx, n - 1)
        ck = sk[idx]
        cm = svalid[idx] & (start + jnp.arange(chunk) < n)
        # nodes that already found k survivors still participate with an
        # empty request (collectives must be uniform)
        done_local = jnp.sum((pass_bits & examined).astype(jnp.int32)) >= k
        cm = cm & ~done_local
        bits, ovf = remote_filter(ck, cm)
        pass_bits = pass_bits.at[idx].set(jnp.where(cm, bits, pass_bits[idx]))
        examined = examined.at[idx].set(examined[idx] | cm)
        return i + 1, pass_bits, examined, overflow | ovf

    i0 = jnp.int32(0)
    _, pass_bits, examined, overflow = lax.while_loop(
        cond, body, (i0, pass_bits, examined, jnp.bool_(False))
    )
    final_mask = pass_bits & examined & svalid
    local = local_topk(sv, sk, k, final_mask)
    return topk_allreduce(local, axis), overflow
