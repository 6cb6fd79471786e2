"""Unit tests for the distributed primitives: exchange backends, butterfly
reductions, top-k selection, semi-joins, late materialization — each checked
against a host-side oracle on the 8-device mesh."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

pytestmark = pytest.mark.tier1

from repro.core import exchange, late_materialization, semijoin, topk, topk_approx
from repro.core.partitioning import RangePartitioning

AXIS = "nodes"


def spmd(cluster, fn, *arrays, replicated_args=()):
    """Run fn inside shard_map over the cluster's nodes axis; inputs sharded
    on axis 0 unless listed in replicated_args; outputs replicated."""
    in_specs = tuple(
        P() if i in replicated_args else P(AXIS) for i in range(len(arrays))
    )
    f = jax.jit(
        jax.shard_map(fn, mesh=cluster.mesh, in_specs=in_specs, out_specs=P(),
                      check_vma=False)
    )
    return jax.tree.map(np.asarray, f(*arrays))


# ---------------------------------------------------------------------------
# all-to-all backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "one_factor"])
def test_all_to_all_semantics(cluster, backend):
    Pn = cluster.num_nodes
    m = 5
    rng = np.random.default_rng(0)
    # global input: (P*P, m); node s's rows are x[s*P:(s+1)*P] with row d
    # addressed to node d
    x = rng.normal(size=(Pn * Pn, m)).astype(np.float32)

    def fn(local):  # local: (P, m) on each node
        recv = exchange.all_to_all(local, AXIS, backend=backend)
        return jax.lax.all_gather(recv, AXIS)  # (P, P, m) for checking

    out = spmd(cluster, fn, x)
    xg = x.reshape(Pn, Pn, m)
    # node d received from node s the row xg[s, d]
    expect = np.stack([xg[:, d] for d in range(Pn)])
    np.testing.assert_allclose(out, expect, rtol=1e-6)


def test_one_factor_equals_xla(cluster):
    Pn = cluster.num_nodes
    rng = np.random.default_rng(1)
    x = rng.normal(size=(Pn * Pn, 17)).astype(np.float32)

    def fn(local):
        a = exchange.all_to_all(local, AXIS, backend="xla")
        b = exchange.all_to_all(local, AXIS, backend="one_factor")
        return jnp.max(jnp.abs(a - b))

    assert spmd(cluster, fn, x) == 0.0


# ---------------------------------------------------------------------------
# butterfly allreduce with a custom merge
# ---------------------------------------------------------------------------


def test_butterfly_matches_pmax(cluster):
    Pn = cluster.num_nodes
    rng = np.random.default_rng(2)
    x = rng.normal(size=(Pn * 4,)).astype(np.float32)

    def fn(local):
        butter = exchange.butterfly_allreduce(local, jnp.maximum, AXIS)
        direct = jax.lax.pmax(local, AXIS)
        return jnp.max(jnp.abs(butter - direct))

    assert spmd(cluster, fn, x) == 0.0


def test_broadcast_from(cluster):
    Pn = cluster.num_nodes
    x = np.arange(Pn * 3, dtype=np.float32)

    def fn(local):
        return exchange.broadcast_from(local, root=2, axis=AXIS)

    out = spmd(cluster, fn, x)
    np.testing.assert_array_equal(out, x.reshape(Pn, 3)[2])


# ---------------------------------------------------------------------------
# bucketing + request/reply
# ---------------------------------------------------------------------------


def test_bucket_by_destination_properties():
    rng = np.random.default_rng(3)
    n, num_nodes, cap = 200, 8, 64
    keys = jnp.asarray(rng.integers(0, 800, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.7)
    owner = keys // 100
    buckets, bmask, (dest, slot), ovf = exchange.bucket_by_destination(
        keys, mask, owner, num_nodes, cap
    )
    buckets, bmask = np.asarray(buckets), np.asarray(bmask)
    dest, slot = np.asarray(dest), np.asarray(slot)
    assert not bool(ovf)
    kn, mn, on = np.asarray(keys), np.asarray(mask), np.asarray(owner)
    # every masked key appears exactly once at its recorded (dest, slot)
    for i in range(n):
        if mn[i]:
            assert dest[i] == on[i]
            assert buckets[dest[i], slot[i]] == kn[i]
            assert bmask[dest[i], slot[i]]
    # bucket occupancy equals per-destination masked counts
    counts = np.bincount(on[mn], minlength=num_nodes)
    np.testing.assert_array_equal(bmask.sum(axis=1), counts)


def test_bucket_overflow_flag():
    keys = jnp.arange(64, dtype=jnp.int32)
    mask = jnp.ones(64, bool)
    owner = jnp.zeros(64, jnp.int32)  # all to node 0
    _, _, _, ovf = exchange.bucket_by_destination(keys, mask, owner, 8, 16)
    assert bool(ovf)


@pytest.mark.parametrize("backend", ["xla", "one_factor"])
def test_request_reply(cluster, backend):
    """Remote lookup: reply[i] == f(keys[i]) for masked keys, 0 otherwise."""
    Pn = cluster.num_nodes
    rows = 32
    total = Pn * rows
    part = RangePartitioning(total, Pn)
    rng = np.random.default_rng(4)
    n_per = 40
    keys = rng.integers(0, total, Pn * n_per).astype(np.int32)
    mask = rng.random(Pn * n_per) < 0.8
    # the remote attribute: owner's local value = global_key * 3 + 1
    def fn(k_local, m_local):
        def lookup(req, req_mask):
            base = part.my_base(AXIS)
            global_key = base + part.local_index(req)  # == req for owned keys
            return jnp.where(req_mask, global_key * 3 + 1, 0)

        rep, ovf = exchange.request_reply(
            k_local, m_local, part.owner(k_local), lookup,
            capacity=64, axis=AXIS, backend=backend, reply_dtype=jnp.int32,
        )
        return jax.lax.all_gather(rep, AXIS, tiled=True), ovf

    rep, ovf = spmd(cluster, fn, jnp.asarray(keys), jnp.asarray(mask))
    assert not bool(ovf)
    np.testing.assert_array_equal(rep, np.where(mask, keys * 3 + 1, 0))


def test_exchange_by_owner_aggregates(cluster):
    """Sum of routed values per key == global group-by sum."""
    Pn = cluster.num_nodes
    rows = 16
    total = Pn * rows
    part = RangePartitioning(total, Pn)
    rng = np.random.default_rng(5)
    n_per = 64
    keys = rng.integers(0, total, Pn * n_per).astype(np.int32)
    vals = rng.normal(size=Pn * n_per).astype(np.float32)
    mask = rng.random(Pn * n_per) < 0.9

    def fn(k, v, m):
        rk, rv, rm, ovf = exchange.exchange_by_owner(
            k, v, m, part.owner(k), capacity=128, axis=AXIS
        )
        local_idx = jnp.where(rm, rk - part.my_base(AXIS), rows).reshape(-1)
        agg = jnp.zeros(rows, jnp.float32).at[local_idx].add(
            jnp.where(rm, rv, 0.0).reshape(-1), mode="drop"
        )
        return jax.lax.all_gather(agg, AXIS, tiled=True), ovf

    agg, ovf = spmd(cluster, fn, jnp.asarray(keys), jnp.asarray(vals), jnp.asarray(mask))
    assert not bool(ovf)
    expect = np.zeros(total)
    np.add.at(expect, keys[mask], vals[mask].astype(np.float64))
    np.testing.assert_allclose(agg, expect, rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# top-k: local, merge, allreduce == gather == numpy
# ---------------------------------------------------------------------------


def _np_topk(values, keys, k):
    order = np.lexsort((keys, -values))[:k]
    return values[order], keys[order]


def test_local_topk_matches_numpy():
    rng = np.random.default_rng(6)
    v = rng.normal(size=100).astype(np.float32)
    keys = rng.permutation(100).astype(np.int32)
    out = topk.local_topk(jnp.asarray(v), jnp.asarray(keys), 10)
    ev, ek = _np_topk(v.astype(np.float64), keys, 10)
    np.testing.assert_allclose(np.asarray(out.values), ev, rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(out.keys), ek)
    assert np.asarray(out.valid).all()


def test_topk_allreduce_equals_gather_and_numpy(cluster):
    Pn = cluster.num_nodes
    rng = np.random.default_rng(7)
    n = Pn * 50
    v = rng.normal(size=n).astype(np.float32)
    keys = np.arange(n, dtype=np.int32)
    k = 12

    def fn(vl, kl):
        local = topk.local_topk(vl, kl, k)
        a = topk.topk_allreduce(local, AXIS)
        b = topk.topk_gather(local, AXIS)
        return a, b

    (a, b) = spmd(cluster, fn, jnp.asarray(v), jnp.asarray(keys))
    ev, ek = _np_topk(v.astype(np.float64), keys, k)
    for out in (a, b):
        np.testing.assert_allclose(out.values, ev, rtol=1e-6)
        np.testing.assert_array_equal(out.keys, ek)


def test_topk_fewer_than_k_valid(cluster):
    Pn = cluster.num_nodes
    n = Pn * 8
    v = np.zeros(n, np.float32)
    mask = np.zeros(n, bool)
    mask[:3] = True
    v[:3] = [5.0, 7.0, 6.0]
    keys = np.arange(n, dtype=np.int32)

    def fn(vl, kl, ml):
        return topk.topk_allreduce(topk.local_topk(vl, kl, 10, ml), AXIS)

    out = spmd(cluster, fn, jnp.asarray(v), jnp.asarray(keys), jnp.asarray(mask))
    assert out.valid[:3].all() and not out.valid[3:].any()
    np.testing.assert_allclose(out.values[:3], [7.0, 6.0, 5.0])
    np.testing.assert_array_equal(out.keys[:3], [1, 2, 0])


# ---------------------------------------------------------------------------
# approximate distributed top-k (§3.2.5) == exact, on adversarial floats
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m", [4, 8, 16])
@pytest.mark.parametrize("dist", ["uniform", "lognormal", "sparse"])
def test_approx_topk_equals_simple(cluster, m, dist):
    Pn = cluster.num_nodes
    group = 32
    Kp = group * 4
    K = Pn * Kp
    rng = np.random.default_rng(m * 17 + len(dist))
    # per-node partials: (P, K) — i.i.d. partial sums, the adversarial case
    # for TA/TPUT that motivates the paper's algorithm
    if dist == "uniform":
        partials = rng.random((Pn, K)).astype(np.float32)
    elif dist == "lognormal":
        partials = rng.lognormal(0, 2.0, (Pn, K)).astype(np.float32)
    else:
        partials = np.where(
            rng.random((Pn, K)) < 0.05, rng.random((Pn, K)), 0.0
        ).astype(np.float32)
    k = 5

    def fn(p_local):
        p_local = p_local.reshape(K)
        exact = topk_approx.simple_topk_distributed(p_local, k, axis=AXIS)
        approx, stats, ovf = topk_approx.approx_topk_distributed(
            p_local, k, m=m, group=group, candidate_capacity=Kp, axis=AXIS
        )
        return exact, approx, stats, ovf

    exact, approx, stats, ovf = spmd(cluster, fn, jnp.asarray(partials.reshape(Pn * K)))
    assert not bool(ovf)
    np.testing.assert_array_equal(exact.keys, approx.keys)
    np.testing.assert_allclose(exact.values, approx.values, rtol=1e-5)
    # the whole point: fewer bits than the naive exchange
    assert float(stats.approx_bits_per_node) < float(stats.naive_bits_per_node)
    # and the result matches the float64 oracle
    totals = partials.astype(np.float64).sum(axis=0)
    ev, ek = _np_topk(totals, np.arange(K, dtype=np.int32), k)
    np.testing.assert_array_equal(approx.keys, ek)
    np.testing.assert_allclose(approx.values, ev, rtol=1e-4)


# ---------------------------------------------------------------------------
# semi-joins: Alt-1 == Alt-2 == oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("selectivity", [0.02, 0.5, 0.98])
def test_semijoin_alternatives_agree(cluster, selectivity):
    Pn = cluster.num_nodes
    rows = 32
    total = Pn * rows
    part = RangePartitioning(total, Pn)
    rng = np.random.default_rng(int(selectivity * 100))
    attr = (rng.random(total) < selectivity).astype(np.int32)  # remote predicate
    n_per = 48
    keys = rng.integers(0, total, Pn * n_per).astype(np.int32)
    mask = rng.random(Pn * n_per) < 0.75

    def fn(k, m, attr_local):
        def pred(local_idx, req_mask):
            return (attr_local[local_idx] == 1) & req_mask

        bits1, ovf = semijoin.alt1_request(
            k, m, part, pred, capacity=128, axis=AXIS
        )
        words = semijoin.alt2_bitset(attr_local == 1, axis=AXIS)
        bits2 = semijoin.probe(words, k, part) & m
        return (
            jax.lax.all_gather(bits1, AXIS, tiled=True),
            jax.lax.all_gather(bits2, AXIS, tiled=True),
            ovf,
        )

    b1, b2, ovf = spmd(cluster, fn, jnp.asarray(keys), jnp.asarray(mask),
                       jnp.asarray(attr))
    assert not bool(ovf)
    expect = mask & (attr[keys] == 1)
    np.testing.assert_array_equal(b1, expect)
    np.testing.assert_array_equal(b2, expect)


def test_semijoin_cost_model_crossover():
    """Few requests -> Alt-1; near-total access or tiny tables -> Alt-2
    (paper footnote 2)."""
    m, Pn = 1_000_000, 128
    assert semijoin.choose_alternative(n=1000, m=m, gamma=0.5, P=Pn) == 1
    assert semijoin.choose_alternative(n=200 * m, m=m, gamma=0.5, P=Pn) == 2
    # highly selective remote filter favors the bitset too
    assert semijoin.choose_alternative(n=50_000_000, m=m, gamma=1e-5, P=Pn) == 2


# ---------------------------------------------------------------------------
# late materialization
# ---------------------------------------------------------------------------


def test_late_materialization(cluster):
    Pn = cluster.num_nodes
    rows = 8
    total = Pn * rows
    part = RangePartitioning(total, Pn)
    rng = np.random.default_rng(9)
    col = rng.integers(0, 1000, total).astype(np.int32)
    win_keys = np.array([3, 17, 42, 63, 0, 0], np.int32) % total
    valid = np.array([True, True, True, True, False, False])

    def fn(col_local, wk, wv):
        return late_materialization.materialize(
            wk, wv, part, {"attr": col_local}, axis=AXIS
        )

    out = spmd(cluster, fn, jnp.asarray(col), jnp.asarray(win_keys),
               jnp.asarray(valid), replicated_args=(1, 2))
    np.testing.assert_array_equal(out["attr"][:4], col[win_keys[:4]])
    np.testing.assert_array_equal(out["attr"][4:], 0)


def _sorted_topk(values, keys, mask, k):
    order = topk._rank_order(jnp.asarray(values), jnp.asarray(keys),
                             jnp.asarray(mask))[:k]
    return (np.where(mask[order], values[order], -np.inf), keys[order],
            mask[order])


@pytest.mark.parametrize("case", ["distinct", "ties", "all_equal",
                                  "few_valid", "negative"])
def test_local_topk_select_matches_full_sort(case):
    """Large partitions select top-k candidates without sorting the
    partition; the answer must equal the full sort's, ties included."""
    rng = np.random.default_rng(len(case))
    n, k = topk.SELECT_MIN_ROWS + 37, 100
    values = {
        "distinct": rng.permutation(n).astype(np.float32),
        "ties": rng.integers(0, 40, n).astype(np.float32),
        "all_equal": np.full(n, 3.0, np.float32),
        "few_valid": rng.random(n).astype(np.float32),
        "negative": -rng.integers(0, 40, n).astype(np.float32),
    }[case]
    keys = rng.permutation(n).astype(np.int32)
    mask = rng.random(n) < (0.0005 if case == "few_valid" else 0.5)
    got = jax.jit(lambda v, kk, m: topk.local_topk(v, kk, k, m))(
        values, keys, mask)
    want_v, want_k, want_m = _sorted_topk(values, keys, mask, k)
    np.testing.assert_array_equal(np.asarray(got.valid), want_m)
    np.testing.assert_array_equal(np.asarray(got.values), want_v)
    nv = int(want_m.sum())
    np.testing.assert_array_equal(np.asarray(got.keys)[:nv], want_k[:nv])


def test_first_true_left_packs_in_order():
    mask = np.array([0, 1, 1, 0, 0, 1, 0], bool)
    idx, ok = topk.first_true(jnp.asarray(mask), 5)
    np.testing.assert_array_equal(np.asarray(ok), [1, 1, 1, 0, 0])
    np.testing.assert_array_equal(np.asarray(idx)[:3], [1, 2, 5])


@pytest.mark.parametrize("n", [64, 1000, 1027])
def test_group_sums_over_row_blocks_match_numpy(monkeypatch, n):
    """One-hot and mask-GEMM sums contract rows in blocks, the last one
    clamped; any block size gives the exact group sums."""
    from repro.core import aggregation

    monkeypatch.setattr(aggregation, "SUM_BLOCK_ROWS", 96)
    rng = np.random.default_rng(n)
    vals = rng.integers(0, 100, (n, 3)).astype(np.float32)
    gid = rng.integers(0, 7, n).astype(np.int32)   # code 6 is out of range
    mask = rng.random(n) < 0.6
    want = np.zeros((6, 3))
    np.add.at(want, gid[mask & (gid < 6)], vals[mask & (gid < 6)])
    for fn in (aggregation.group_sum_maskgemm, aggregation.group_sum_onehot):
        got = fn(jnp.asarray(vals), jnp.asarray(gid), 6, jnp.asarray(mask))
        np.testing.assert_array_equal(np.asarray(got), want)
