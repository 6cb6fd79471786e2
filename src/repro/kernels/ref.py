"""Pure-jnp oracles for every Pallas kernel (interpret-mode validation)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import compression
from repro.core.aggregation import group_sum_dense
from repro.kernels.compact import plane_order


def filtered_group_sum(measures, groups, pred, cutoff, num_groups):
    sel = pred <= cutoff
    onehot = (
        groups[None, :] == jnp.arange(num_groups, dtype=groups.dtype)[:, None]
    ) & sel[None, :]
    return jnp.dot(
        onehot.astype(jnp.float32),
        measures.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def clustered_sum(values, keys, num_keys):
    return group_sum_dense(values, keys, num_keys)


def compact_rows(words, cap):
    """Set bits of a packed mask in the compaction kernel's slot order;
    -1 in the slots past them."""
    order = plane_order(words.shape[0])
    bits = compression.unpack_bitset(words, words.shape[0] * 32)
    bits = jnp.pad(bits, (0, order.shape[0] - bits.shape[0]))[order]
    (pos,) = jnp.nonzero(bits, size=cap, fill_value=-1)
    return jnp.where(pos >= 0, order[pos], -1)


def block_topk(values, keys, k, mask=None, block: int = 4096):
    v = values.astype(jnp.float32)
    if mask is not None:
        v = jnp.where(mask, v, -jnp.inf)
    n = v.shape[0]
    pad = (-n) % block
    v = jnp.pad(v, (0, pad), constant_values=-jnp.inf)
    keys = jnp.pad(keys, (0, pad), constant_values=jnp.iinfo(jnp.int32).max)
    vb = v.reshape(-1, block)
    kb = keys.reshape(-1, block)
    out_v, out_k = [], []
    for j in range(k):
        m = jnp.max(vb, axis=1)
        am = jnp.argmax(vb, axis=1)
        out_v.append(m)
        out_k.append(jnp.take_along_axis(kb, am[:, None], axis=1)[:, 0])
        vb = vb.at[jnp.arange(vb.shape[0]), am].set(-jnp.inf)
    return jnp.stack(out_v, axis=1), jnp.stack(out_k, axis=1)


def predicate_bitset(column, value):
    bits = column == value
    pad = (-bits.shape[0]) % 32
    bits = jnp.concatenate([bits, jnp.zeros(pad, bool)])
    return compression.pack_bitset(bits)


def scan_filter(words, lo, hi, rows, padded_rows, width, negate=False):
    """Decode-then-compare oracle for the predicate-on-packed kernel:
    unpack the full column, apply the code-space range test, pack the
    validity bitset (rows past ``rows`` are never valid)."""
    codes = compression.unpack_bits(words, padded_rows, width).astype(jnp.int32)
    ok = (codes >= jnp.asarray(lo, jnp.int32)) & (codes <= jnp.asarray(hi, jnp.int32))
    if negate:
        ok = jnp.logical_not(ok)
    ok = jnp.logical_and(ok, jnp.arange(padded_rows) < rows)
    return compression.pack_bitset(ok)


def mbit_encode(q, m, group):
    K = q.shape[0]
    g = q.reshape(K // group, group)
    gmax = jnp.max(g, axis=1)
    # significant bits via log2-free ladder (same as the kernel)
    x = gmax
    bits = jnp.zeros_like(x)
    for shift in (16, 8, 4, 2, 1):
        above = x >= (jnp.uint32(1) << shift)
        bits = jnp.where(above, bits + shift, bits)
        x = jnp.where(above, x >> shift, x)
    nbits = bits + (x > 0).astype(jnp.uint32)
    shiftv = jnp.maximum(nbits.astype(jnp.int32) - m, 0).astype(jnp.uint32)
    codes = (g >> shiftv[:, None]).reshape(K)
    words = compression.pack_bits(codes, m)
    return words, shiftv


def mbit_decode_bounds(words, shifts, m, group):
    K = shifts.shape[0] * group
    codes = compression.unpack_bits(words, K, m)
    s = jnp.repeat(shifts, group, total_repeat_length=K)
    lower = codes << s
    upper = lower + ((jnp.uint32(1) << s) - jnp.uint32(1))
    return lower, upper


def flash_attention(q, k, v, causal=True, window=None, prefix=0):
    """Pure-jnp oracle for the flash kernel: full-materialization GQA
    attention.  q: (B,S,H,D); k,v: (B,Sk,KV,D)."""
    import numpy as np
    import jax

    B, S, H, D = q.shape
    Sk, KV = k.shape[1], k.shape[2]
    G = H // KV
    qf = q.astype(jnp.float32).transpose(0, 2, 1, 3).reshape(B, KV, G, S, D)
    kf = k.astype(jnp.float32).transpose(0, 2, 1, 3)
    vf = v.astype(jnp.float32).transpose(0, 2, 1, 3)
    s = jnp.einsum("bkgsd,bktd->bkgst", qf, kf) / np.sqrt(D)
    if causal:
        q_pos = jnp.arange(S)[:, None]
        k_pos = jnp.arange(Sk)[None, :]
        vis = k_pos <= q_pos
        if window is not None:
            vis &= k_pos > q_pos - window
        if prefix:
            vis |= k_pos < prefix
        s = jnp.where(vis[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgst,bktd->bkgsd", p, vf)
    return (o.reshape(B, H, S, D).transpose(0, 2, 1, 3)).astype(q.dtype)


# ---------------------------------------------------------------------------
# wire codec (§3.2.1): EF key buckets + folded validity mask
# ---------------------------------------------------------------------------


def mask_fold(mask):
    """(P, c) bool -> (P, ceil(c/32)) uint32 bitset rows (little-endian bit
    order within each word, row-major words)."""
    import jax

    c = mask.shape[1]
    pad = (-c) % 32
    if pad:
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    return jax.vmap(compression.pack_bitset)(mask)


def mask_unfold(words, n):
    import jax

    return jax.vmap(lambda w: compression.unpack_bitset(w, n))(words)


def ef_encode(buckets, bucket_mask, domain):
    """Scatter-based EF bucket encoder: row ``d`` of ``buckets`` holds a
    sorted ascending prefix of keys in ``[d*domain, (d+1)*domain)`` under
    ``bucket_mask``; returns the packed wire rows
    (P, ``compression.packed_request_words(capacity, domain)``) uint32.
    One upper-bitvector one per key at position ``(off >> l) + j`` (unary
    high parts), fixed-width packed low bits, appended mask bitset."""
    import jax

    P, cap = buckets.shape
    l, uw, _ = compression.ef_params(cap, domain)
    offs = buckets.astype(jnp.int32) - jnp.arange(P, dtype=jnp.int32)[:, None] * domain
    offs = jnp.clip(jnp.where(bucket_mask, offs, 0), 0, domain - 1).astype(jnp.uint32)
    j = jnp.arange(cap, dtype=jnp.uint32)[None, :]
    pos = (offs >> l) + j                 # strictly increasing per row
    rows = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[:, None], (P, cap))
    word = jnp.where(bucket_mask, (pos >> 5).astype(jnp.int32), uw)
    upper = jnp.zeros((P, uw), jnp.uint32).at[rows, word].add(
        jnp.uint32(1) << (pos & jnp.uint32(31)), mode="drop"
    )
    parts = [upper]
    if l:
        lo = jnp.where(bucket_mask, offs & jnp.uint32((1 << l) - 1), jnp.uint32(0))
        parts.append(jax.vmap(lambda v: compression.pack_bits(v, l))(lo))
    parts.append(mask_fold(bucket_mask))
    return jnp.concatenate(parts, axis=1)


def ef_decode(words, capacity, domain, my_base):
    """Rank/select EF bucket decoder (inverse of :func:`ef_encode` on the
    receiving node): bit-expands the upper bitvector, ranks the set bits
    with one cumsum, and scatters each one's position back to its slot.
    Returns (global keys (P, capacity) int32, mask (P, capacity) bool)."""
    import jax

    P = words.shape[0]
    l, uw, lw = compression.ef_params(capacity, domain)
    upper = words[:, :uw]
    lane = jnp.arange(32, dtype=jnp.uint32)[None, None, :]
    bits = ((upper[:, :, None] >> lane) & jnp.uint32(1)).reshape(P, uw * 32)
    on = bits.astype(bool)
    rank = jnp.cumsum(bits, axis=1).astype(jnp.int32)
    rows = jnp.broadcast_to(jnp.arange(P, dtype=jnp.int32)[:, None], bits.shape)
    tgt = jnp.where(on, rank - 1, capacity)     # <= capacity bits set per row
    posv = jnp.broadcast_to(
        jnp.arange(uw * 32, dtype=jnp.int32)[None, :], bits.shape
    )
    sel = jnp.zeros((P, capacity), jnp.int32).at[rows, tgt].add(posv, mode="drop")
    j = jnp.arange(capacity, dtype=jnp.int32)[None, :]
    hi = sel - j
    if l:
        lo = jax.vmap(lambda w: compression.unpack_bits(w, capacity, l))(
            words[:, uw:uw + lw]
        ).astype(jnp.int32)
    else:
        lo = jnp.zeros((P, capacity), jnp.int32)
    mask = mask_unfold(
        words[:, uw + lw:uw + lw + compression.bitset_words(capacity)], capacity
    )
    keys = jnp.where(mask, my_base + ((hi << l) | lo), 0).astype(jnp.int32)
    return keys, mask
