"""Keyed reductions of a clustered child into its parents.

The primitive (``kernels/clustered_sum.py``, interpret mode here, and its
oracle in ``kernels/ref.py``) against JAX's scatters over sum, count and
EXISTS; then the lowered plans that use it (q4's EXISTS, q18's
group-by-key and their semi-join forms) against ``tpch/reference.py`` on
one node and on the eight-node CPU mesh, and the scatter they keep when
the child is not clustered.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Cluster
from repro.core.partitioning import clustered_fanout
from repro.kernels import clustered_sum as clustered_kernel
from repro.kernels import ref
from repro.query.lower import lower
from repro.tpch import dbgen, reference
from repro.tpch import queries as tq


def _clustered(num_keys, fanout, seed):
    """Keys 0..num_keys-1, each 0..fanout times in order, with a stream
    mask over the rows and non-integer values."""
    rng = np.random.default_rng(seed)
    counts = rng.integers(0, fanout + 1, num_keys)
    counts[rng.integers(0, num_keys)] = fanout   # the longest run is hit
    keys = np.repeat(np.arange(num_keys, dtype=np.int32), counts)
    values = rng.uniform(-100.0, 100.0, keys.shape[0]).astype(np.float32)
    mask = rng.random(keys.shape[0]) < 0.7
    return keys, values, mask


def _reduce(impl, values, keys, num_keys, fanout):
    if impl == "oracle":
        return ref.clustered_sum(values, keys, num_keys)
    starts = jnp.asarray(clustered_kernel.block_starts(np.asarray(keys), num_keys, 1))
    return clustered_kernel.clustered_sum(values, keys, starts,
                                          num_keys=num_keys, fanout=fanout,
                                          interpret=True)


@pytest.mark.parametrize("impl", ["kernel", "oracle"])
@pytest.mark.parametrize("op", ["sum", "count", "exists"])
@pytest.mark.parametrize("num_keys,fanout", [
    (1000, 7),   # partial last block (1000 = 7 * 128 + 104)
    (2048, 7),   # whole blocks, two grid steps
    (300, 4),    # partsupp's fanout
    (77, 1),     # one block, all of it partial
])
def test_clustered_sum_matches_scatter(impl, op, num_keys, fanout):
    keys, values, mask = _clustered(num_keys, fanout, num_keys + fanout)
    assert clustered_fanout(keys, num_keys, 1) == fanout
    k = jnp.asarray(keys)
    if op == "exists":
        bits = jnp.asarray(mask)
        got = _reduce(impl, bits.astype(jnp.float32), k, num_keys, fanout) > 0
        want = jnp.zeros(num_keys, bool).at[k].max(bits)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        return
    v = (jnp.ones(keys.shape[0], jnp.float32) if op == "count"
         else jnp.asarray(values))
    v = jnp.where(jnp.asarray(mask), v, 0.0)   # dropped rows add 0
    got = np.asarray(_reduce(impl, v, k, num_keys, fanout))
    want = np.asarray(jnp.zeros(num_keys, jnp.float32).at[k].add(v))
    if op == "count":
        np.testing.assert_array_equal(got, want)
    else:
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-4)


def test_clustered_sum_across_calls(monkeypatch):
    """Block starts beyond one call's SMEM share go to further calls."""
    monkeypatch.setattr(clustered_kernel, "CALL_BLOCKS", 16)
    num_keys, fanout = 5000, 7      # 40 blocks: calls of 16, 16 and 8
    keys, values, _ = _clustered(num_keys, fanout, 5)
    got = _reduce("kernel", jnp.asarray(values), jnp.asarray(keys),
                  num_keys, fanout)
    want = np.zeros(num_keys)
    np.add.at(want, keys, values.astype(np.float64))
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-4)


def test_clustered_fanout_needs_sorted_in_range_keys():
    keys = np.repeat(np.arange(256, dtype=np.int32), 3)    # 2 nodes of 128
    assert clustered_fanout(keys, 128, 2) == 3
    assert clustered_fanout(keys[::-1].copy(), 128, 2) == 0
    shifted = np.concatenate([keys[384:], keys[:384]])   # wrong owners
    assert clustered_fanout(shifted, 128, 2) == 0
    starts = clustered_kernel.block_starts(keys, 128, 2)
    np.testing.assert_array_equal(starts, [0, 0])
    starts = clustered_kernel.block_starts(
        np.repeat(np.arange(300, dtype=np.int32), 2), 300, 1)
    np.testing.assert_array_equal(starts, [0, 2 * clustered_kernel.BLOCK,
                                           4 * clustered_kernel.BLOCK])


# ---------------------------------------------------------------------------
# lowered plans
# ---------------------------------------------------------------------------

KEYED_QUERIES = {
    "q4": (tq.q4_ir, reference.q4, lambda out: out["value"][:, 0]),
    "q4_sj_request": (tq.q4_sj_ir, reference.q4,
                      lambda out: out["value"][:, 0]),
    "q18_sj_request": (tq.q18_sj_ir, reference.q18_sj,
                       lambda out: out["value"].reshape(-1)),
    "q18": (tq.q18_ir, reference.q18, None),
}


def _check_answers(driver, name):
    make, oracle, extract = KEYED_QUERIES[name]
    q = make()
    cols = {n: t.columns for n, t in driver.placed.items()}
    out = jax.tree.map(np.asarray, driver.compile_query(q)(cols))
    assert not out.get("overflow", False)
    if extract is None:
        ov, ok = oracle(driver.tables)
        reference.assert_topk_matches(out["values"], out["keys"],
                                      out["valid"], ov, ok)
    else:
        np.testing.assert_allclose(extract(out), oracle(driver.tables),
                                   rtol=1e-6)
    return lower(q, driver.catalog).keyed


@pytest.fixture(scope="module")
def one_node_driver():
    from repro.tpch.driver import TPCHDriver

    return TPCHDriver(sf=0.01, cluster=Cluster(devices=jax.devices()[:1]),
                      seed=0)


@pytest.mark.parametrize("name", sorted(KEYED_QUERIES))
@pytest.mark.parametrize("nodes", [1, 8])
def test_keyed_queries_take_clustered_path(name, nodes, one_node_driver,
                                           tpch_driver):
    driver = one_node_driver if nodes == 1 else tpch_driver
    assert driver.cluster.num_nodes == nodes
    assert driver.catalog.clustered == {"lineitem": 7, "partsupp": 4}
    assert _check_answers(driver, name) == ("clustered",)
    assert driver.obs.metrics.value("plan.keyed.clustered") >= 1
    assert "path=clustered" in driver.explain(KEYED_QUERIES[name][0]()).text()


@pytest.fixture(scope="module")
def shuffled_driver(cluster):
    """Lineitem rows shuffled within each node: still co-partitioned with
    orders, no longer clustered by ``l_orderkey``."""
    from repro.core import Table
    from repro.tpch.driver import TPCHDriver

    generate = dbgen.generate

    def shuffled(sf, num_nodes, seed=0, storage="raw"):
        tables = generate(sf, num_nodes, seed, storage)
        li = tables["lineitem"]
        per = li.num_rows // num_nodes
        rng = np.random.default_rng(seed)
        perm = np.concatenate([n * per + rng.permutation(per)
                               for n in range(num_nodes)])
        tables["lineitem"] = Table(
            "lineitem", {c: np.asarray(v)[perm] for c, v in li.columns.items()},
            li.dictionaries)
        return tables

    mp = pytest.MonkeyPatch()
    mp.setattr(dbgen, "generate", shuffled)
    try:
        yield TPCHDriver(sf=0.01, cluster=cluster, seed=0)
    finally:
        mp.undo()


@pytest.mark.parametrize("name", sorted(KEYED_QUERIES))
def test_shuffled_child_keeps_scatter(name, shuffled_driver):
    assert "lineitem" not in shuffled_driver.catalog.clustered
    assert _check_answers(shuffled_driver, name) == ("scatter",)
    assert shuffled_driver.obs.metrics.value("plan.keyed.scatter") >= 1


# ---------------------------------------------------------------------------
# batched lowering: the kernel under vmap
# ---------------------------------------------------------------------------

def _q4_lane(tables, b):
    return reference.q4(tables, dataclasses.replace(
        reference.DP, q4_date_min=b["_p0"], q4_date_max=b["_p1"]))


BATCHED = {
    # the EXISTS values are the same in every lane
    "q4": (tq.q4_ir, [{"_p0": lo, "_p1": lo + 92} for lo in (547, 100, 1500)],
           _q4_lane),
    # the late-line counts follow each lane's semi-join mask: the
    # clustered sum is vmapped over lanes whose values differ
    "q4_sj_request": (tq.q4_sj_ir,
                      [{"_p0": lo, "_p1": lo + 92} for lo in (547, 100, 1500)],
                      _q4_lane),
    "q18_sj_request": (tq.q18_sj_ir,
                       [{"_p0": 250.0, "_p1": 1}, {"_p0": 150.0, "_p1": 3},
                        {"_p0": 200.0, "_p1": 0}],
                       lambda t, b: reference.q18_sj(t, b["_p0"], b["_p1"])),
}


@pytest.mark.parametrize("name", sorted(BATCHED))
@pytest.mark.parametrize("nodes", [1, 8])
def test_keyed_queries_batched_lanes(name, nodes, one_node_driver,
                                     tpch_driver):
    """``execute_batch`` runs the batched lowering, whose plan ``vmap``s
    the clustered sum over the lanes; every lane must equal the reference
    for its own binding."""
    driver = one_node_driver if nodes == 1 else tpch_driver
    make, bindings, oracle = BATCHED[name]
    prep = driver.prepare(make())
    assert lower(prep.entry.shape, driver.catalog,
                 batched=True).keyed == ("clustered",)
    ans = prep.execute_batch(bindings)
    assert not np.asarray(ans.overflow).any()
    for lane, b in enumerate(bindings):
        np.testing.assert_allclose(np.asarray(ans.value)[lane].reshape(-1),
                                   oracle(driver.tables, b), rtol=1e-6)
