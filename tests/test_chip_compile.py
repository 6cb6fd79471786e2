"""The main path's Pallas kernels compile for a TPU v5e at real sizes.

Interpret mode (every other kernel test) runs a kernel's Python body on
the CPU and accepts block shapes, dtypes and reductions that the chip's
compiler refuses.  Here each kernel is compiled, not run, for one chip of
a described ``v5e:2x2`` topology with the TPU compiler that ships with
jaxlib, at the shapes of TPC-H SF 10 on one chip or a four-chip cluster,
and the compiled HLO must hold the kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers each
import every test file.  The fixture skips where no topology can be
described.
"""
from __future__ import annotations

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import compression
from repro.kernels import (
    clustered_sum,
    compact,
    grouped_agg,
    scan_filter,
    wire_codec,
)

pytestmark = pytest.mark.tier1

LINEITEM_ROWS = 60_000_000    # SF 10 lineitem on one chip
ORDERS_ROWS = 15_000_000      # SF 10 orders on one chip
PART_ROWS = 2_000_000         # SF 10 part: the exchange key domain
CAPACITY = 1 << 18            # keys per destination of a request exchange


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        jax.config.update("jax_enable_compilation_cache", cache_was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield topo
    jax.config.update("jax_enable_compilation_cache", cache_was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("width", [1, 12, 24])
def test_scan_filter_compiles(one_chip, width):
    words = compression.packed_words(LINEITEM_ROWS, width)

    def scan(w, lo, hi):
        return scan_filter.scan_filter_pallas(
            w, lo, hi, rows=LINEITEM_ROWS, padded_rows=LINEITEM_ROWS,
            width=width)

    text = _compiled_text(scan, one_chip, ((words,), jnp.uint32),
                          ((), jnp.int32), ((), jnp.int32))
    assert "tpu_custom_call" in text


def test_filtered_group_sum_compiles(one_chip):
    n = 16_000_000

    def agg(measures, groups, pred):
        return grouped_agg.filtered_group_sum(measures, groups, pred,
                                              cutoff=10_000, num_groups=6)

    text = _compiled_text(agg, one_chip, ((n, 8), jnp.float32),
                          ((n,), jnp.int32), ((n,), jnp.int32))
    assert "tpu_custom_call" in text


def test_clustered_sum_compiles(one_chip):
    """q18's group-by-key and q4's EXISTS: lineitem into orders, longest
    run 7, block starts scalar-prefetched over two calls."""
    def reduce(values, keys, starts):
        return clustered_sum.clustered_sum(values, keys, starts,
                                           num_keys=ORDERS_ROWS, fanout=7)

    blocks = -(-ORDERS_ROWS // clustered_sum.BLOCK)
    text = _compiled_text(reduce, one_chip,
                          ((LINEITEM_ROWS,), jnp.float32),
                          ((LINEITEM_ROWS,), jnp.int32), ((blocks,), jnp.int32))
    assert "tpu_custom_call" in text


def test_clustered_sum_compiles_vmapped(one_chip):
    """The batched lowering vmaps the kernel over lanes whose values differ
    (``q4_sj``'s per-lane semi-join mask) while keys and block starts are
    shared: the batching rule adds a grid axis over the lanes."""
    rows, orders = 1 << 22, 1 << 20   # the largest vmapped partition
    def reduce(values, keys, starts):
        return jax.vmap(lambda v: clustered_sum.clustered_sum(
            v, keys, starts, num_keys=orders, fanout=7))(values)

    blocks = -(-orders // clustered_sum.BLOCK)
    text = _compiled_text(reduce, one_chip, ((4, rows), jnp.float32),
                          ((rows,), jnp.int32), ((blocks,), jnp.int32))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nodes", [1, 4])
@pytest.mark.parametrize("stage", ["mask_fold", "mask_unfold", "ef_encode",
                                   "ef_decode"])
def test_wire_codec_compiles(one_chip, nodes, stage):
    domain = PART_ROWS // nodes
    rows = (nodes, CAPACITY)
    if stage == "mask_fold":
        fn = lambda m: wire_codec.mask_fold(m, use_pallas=True)  # noqa: E731
        shapes = ((rows, jnp.bool_),)
    elif stage == "mask_unfold":
        fn = lambda w: wire_codec.mask_unfold(  # noqa: E731
            w, CAPACITY, use_pallas=True)
        shapes = (((nodes, compression.bitset_words(CAPACITY)), jnp.uint32),)
    elif stage == "ef_encode":
        fn = lambda b, m: wire_codec.ef_encode(  # noqa: E731
            b, m, domain, use_pallas=True)
        shapes = ((rows, jnp.int32), (rows, jnp.bool_))
    else:
        fn = lambda w: wire_codec.ef_decode(  # noqa: E731
            w, CAPACITY, domain, jnp.int32(0), use_pallas=True)
        words = compression.packed_request_words(CAPACITY, domain)
        shapes = (((nodes, words), jnp.uint32),)
    assert "tpu_custom_call" in _compiled_text(fn, one_chip, *shapes)


@pytest.mark.parametrize("cap", [1 << 20, 1 << 22])
def test_compact_rows_compiles(one_chip, cap):
    """q14_promo's survivors: 60M rows of mask words into 2^20 or 2^22
    slots, the output held in VMEM over the whole grid."""
    def survivors(words):
        return compact.compact_rows(words, compact.plane_offsets(words),
                                    cap=cap)

    words = -(-LINEITEM_ROWS // 32)
    assert "tpu_custom_call" in _compiled_text(survivors, one_chip,
                                               ((words,), jnp.uint32))


@pytest.fixture(scope="module")
def q14_tables():
    """Lineitem and part at SF 10 row counts, packed as the generator's
    data at SF 0.01 packs them: the catalog and the packed column layouts,
    with the SF 0.01 statistics."""
    from repro.core.columnar import PackedColumn
    from repro.query.ir import PackedInfo, build_catalog
    from repro.tpch import dbgen

    small = dbgen.generate(0.01, 1, 0)
    small = {n: small[n] for n in ("lineitem", "part")}
    packed = dbgen.pack_tables(small, 1)
    meta = {n: {c: PackedInfo(width=col.width, offset=col.offset,
                              values=col.values, dtype=col.dtype)
                for c, col in t.columns.items()
                if isinstance(col, PackedColumn)}
            for n, t in packed.items()}
    catalog = build_catalog(small, packed=meta, device_kind="TPU v5 lite")
    return packed, catalog


@pytest.mark.parametrize("nodes", [1, 4])
@pytest.mark.parametrize("param", [False, True])
def test_q14_promo_plan_compiles(topo, q14_tables, monkeypatch, nodes,
                                 param):
    """The whole q14_promo plan, literal and parameterized, with its probe
    compacted, on one chip and as a four-node cluster (15M lineitem rows a
    node): the scan and compaction kernels are in the program."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import Cluster, Table
    from repro.core.columnar import PackedColumn
    from repro.kernels import ops
    from repro.query.lower import lower
    from repro.tpch import queries as tq

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    packed, catalog = q14_tables
    cluster = Cluster(devices=topo.devices[:nodes])
    part = NamedSharding(cluster.mesh, P(cluster.axis))
    rows = {"lineitem": LINEITEM_ROWS, "part": PART_ROWS}
    tables = {}
    for name, t in packed.items():
        local = rows[name] // nodes
        padded = -(-local // 32) * 32
        tables[name] = Table(name, {
            c: (dataclasses.replace(
                    col, rows=local, padded_rows=padded, num_nodes=nodes,
                    words=jax.ShapeDtypeStruct(
                        (nodes * compression.packed_words(padded, col.width),),
                        jnp.uint32, sharding=part))
                if isinstance(col, PackedColumn)
                else jax.ShapeDtypeStruct((rows[name],), col.dtype,
                                          sharding=part))
            for c, col in t.columns.items()})
    catalog = dataclasses.replace(
        catalog, num_nodes=nodes,
        tables={n: dataclasses.replace(i, num_rows=rows[n])
                for n, i in catalog.tables.items()})
    q = tq.q14_promo_param_ir() if param else tq.q14_promo_ir()
    plan = lower(q, catalog)
    assert plan.probes == ("compact",)
    fn = cluster.compile(plan, cluster.context(tables), tables)
    args = [{n: t.columns for n, t in tables.items()}]
    if plan.params:
        args.append({p.name: jax.ShapeDtypeStruct(
            (), p.dtype, sharding=NamedSharding(cluster.mesh, P()))
            for p in plan.params})
    text = fn.lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") >= 3   # scan + two capacities
