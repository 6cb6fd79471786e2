"""Compressed-resident columns and predicate-on-packed scans.

Covers the compression width edge cases ({0, 1, 31, 32} round-trips and
random access), the PackedColumn resident format (plan/pack/decode/gather),
kernel parity across the ref / XLA / Pallas-interpret formulations, the
end-to-end property that predicate-on-packed + late decode is bit-identical
to decode-then-filter (hypothesis when installed, a fixed pre-seeded grid
otherwise), packed-vs-raw driver equivalence, the storage byte accounting,
and the resident-budget guard.
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import compression
from repro.core.columnar import PackedColumn, pack_column, plan_packing
from repro.kernels import ops, ref
from repro.kernels.scan_filter import scan_filter_pallas, scan_filter_xla
from repro.query.ir import C, Lit, PackedInfo
from repro.query.stats import scan_rewrite

pytestmark = pytest.mark.tier1


# -- compression width edge cases ({0, 1, 31, 32} plus interior) -------------

@pytest.mark.parametrize("width", [0, 1, 2, 7, 17, 31, 32])
def test_pack_bits_roundtrip_width_edges(width):
    rng = np.random.default_rng(width)
    n = 97  # odd: last word partially filled, straddles exercised
    if width == 0:
        vals = np.zeros(n, np.uint32)
    else:
        vals = rng.integers(0, 1 << width, size=n,
                            dtype=np.uint64).astype(np.uint32)
    words = compression.pack_bits(jnp.asarray(vals, jnp.uint32), width)
    assert words.shape[0] == compression.packed_words(n, width)
    out = np.asarray(compression.unpack_bits(words, n, width))
    np.testing.assert_array_equal(out, vals)
    # random access must agree with the full decode
    idx = rng.permutation(n)[: max(n // 2, 1)]
    got = np.asarray(compression.gather_bits(
        words, jnp.asarray(idx, jnp.uint32), width))
    np.testing.assert_array_equal(got, vals[idx])


def test_width_zero_is_empty_and_width_32_is_identity_sized():
    assert compression.packed_words(64, 0) == 0
    assert compression.pack_bits(jnp.arange(64, dtype=jnp.uint32), 0).shape[0] == 0
    # width 32 packs 1:1 — no compression, but still correct
    assert compression.packed_words(64, 32) == 64
    assert compression.required_width(0) == 0
    assert compression.required_width(1) == 1
    assert compression.required_width((1 << 31) - 1) == 31
    assert compression.required_width((1 << 32) - 1) == 32


def test_pack_bits_extremes_survive_at_full_width():
    # all-ones values at widths 31/32: the straddle's high half carries
    # meaningful bits in every group position
    for width in (31, 32):
        n = 64
        vals = np.full(n, (1 << width) - 1, np.uint64).astype(np.uint32)
        words = compression.pack_bits(jnp.asarray(vals, jnp.uint32), width)
        out = np.asarray(compression.unpack_bits(words, n, width))
        np.testing.assert_array_equal(out, vals)


# -- PackedColumn: plan, pack, decode, gather --------------------------------

def test_plan_packing_eligibility():
    # bool -> width 1
    spec = plan_packing([np.array([True, False, True])])
    assert spec["width"] == 1 and spec["dtype"] == "bool"
    # small-span int -> FOR at required width
    spec = plan_packing([np.arange(1000, 1100, dtype=np.int64)])
    assert spec["width"] == 7 and spec["offset"] == 1000
    # wide-span int -> raw
    assert plan_packing([np.array([0, 1 << 30], np.int64)]) is None
    # all-integral float -> FOR float32
    spec = plan_packing([np.array([3.0, 10.0, 7.0])])
    assert spec["dtype"] == "float32" and spec["values"] is None
    # low-cardinality fractional float -> sorted dictionary
    spec = plan_packing([np.array([0.04, 0.02, 0.04, 0.09])])
    assert spec["values"] == (0.02, 0.04, 0.09)
    # high-cardinality fractional float -> raw
    rng = np.random.default_rng(0)
    assert plan_packing([rng.uniform(size=4096)]) is None
    # NaN/Inf disqualify
    assert plan_packing([np.array([1.0, np.nan])]) is None


@pytest.mark.parametrize("kind", ["bool", "int", "float_for", "float_dict"])
@pytest.mark.parametrize("nodes", [1, 4])
def test_pack_column_roundtrip(kind, nodes):
    rng = np.random.default_rng(7)
    rows = 173  # not a multiple of 32: padding in play
    if kind == "bool":
        chunks = [rng.integers(0, 2, rows).astype(bool) for _ in range(nodes)]
    elif kind == "int":
        chunks = [rng.integers(-50, 2000, rows) for _ in range(nodes)]
    elif kind == "float_for":
        chunks = [rng.integers(0, 300, rows).astype(np.float64)
                  for _ in range(nodes)]
    else:
        pool = np.round(np.sort(rng.uniform(0, 10, 31)), 3)
        chunks = [rng.choice(pool, rows) for _ in range(nodes)]
    spec = plan_packing(chunks)
    col = pack_column(chunks, spec)
    assert col.num_nodes == nodes and col.rows == rows
    assert col.padded_rows % 32 == 0
    expect = np.concatenate(chunks).astype(
        np.dtype(col.dtype) if kind != "bool" else bool)
    got = np.asarray(col.decode())
    np.testing.assert_array_equal(got, expect)
    # gather on a node-local view matches a slice of the decode
    wpn = col.words_per_node
    local = dataclasses.replace(
        col, words=jnp.asarray(np.asarray(col.words)[:wpn]), num_nodes=1)
    idx = rng.permutation(rows)[: rows // 3]
    np.testing.assert_array_equal(
        np.asarray(local.gather(jnp.asarray(idx, jnp.uint32))),
        expect[:rows][idx])
    # compression actually compresses (except bool, whose raw form is 1 B)
    if kind != "bool":
        assert col.nbytes < col.raw_nbytes


# -- scan_filter kernel parity (ref oracle vs XLA vs Pallas-interpret) -------

_IMPLS = {
    "ref": lambda *a, **k: ref.scan_filter(*a, **k),
    "xla": scan_filter_xla,
    "pallas": lambda w, lo, hi, **k: scan_filter_pallas(
        w, lo, hi, interpret=True, **k),
}


def _ref_call(words, lo, hi, *, rows, padded_rows, width, negate=False):
    return ref.scan_filter(words, lo, hi, rows, padded_rows, width, negate)


_IMPLS["ref"] = _ref_call


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("width", [1, 5, 13, 24, 30])
@pytest.mark.parametrize("negate", [False, True])
def test_scan_filter_matches_oracle(impl, width, negate):
    rng = np.random.default_rng(width)
    rows, padded = 173, 192
    codes = np.zeros(padded, np.uint32)
    codes[:rows] = rng.integers(0, 1 << width, rows,
                                dtype=np.uint64).astype(np.uint32)
    words = compression.pack_bits(jnp.asarray(codes), width)
    maxc = (1 << width) - 1
    for lo, hi in [(0, maxc), (0, -1), (maxc // 3, (2 * maxc) // 3),
                   (maxc, maxc)]:
        want = np.asarray(_ref_call(
            words, lo, hi, rows=rows, padded_rows=padded, width=width,
            negate=negate))
        got = np.asarray(_IMPLS[impl](
            words, lo, hi, rows=rows, padded_rows=padded, width=width,
            negate=negate))
        np.testing.assert_array_equal(got, want, err_msg=f"{impl} {lo}..{hi}")
        # rows beyond `rows` must be invalid even under negation
        mask = np.asarray(compression.unpack_bitset(got, padded))
        assert not mask[rows:].any()


def test_ops_scan_filter_dispatch_and_toggle():
    rng = np.random.default_rng(3)
    rows, padded, width = 96, 96, 8
    codes = rng.integers(0, 256, padded, dtype=np.int64).astype(np.uint32)
    words = compression.pack_bits(jnp.asarray(codes), width)
    want = np.asarray(_ref_call(words, 10, 200, rows=rows,
                                padded_rows=padded, width=width))
    got = np.asarray(ops.scan_filter(words, 10, 200, rows=rows,
                                     padded_rows=padded, width=width))
    np.testing.assert_array_equal(got, want)
    ops.use_kernels(False)
    try:
        got_ref = np.asarray(ops.scan_filter(words, 10, 200, rows=rows,
                                             padded_rows=padded, width=width))
    finally:
        ops.use_kernels(True)
    np.testing.assert_array_equal(got_ref, want)


# -- property: predicate-on-packed + late decode == decode-then-filter -------
#
# The tentpole's core claim: rewriting `col <= v` into code space, scanning
# packed words, and gathering only the surviving rows yields EXACTLY the
# rows a full decode followed by the same predicate yields — bit-identical,
# across widths, selectivities, node counts, kernel impls, and both the
# frame-of-reference and dictionary encodings.

def _check_packed_scan_equivalence(width, sel, nodes, impl, kind, seed):
    rng = np.random.default_rng(seed)
    rows = 141
    if kind == "dict":
        pool = np.round(np.sort(rng.uniform(0.0, 50.0,
                                            min(1 << width, 48))), 3)
        pool = np.unique(pool)
        chunks = [rng.choice(pool, rows) for _ in range(nodes)]
    else:
        base = -7
        chunks = [(rng.integers(0, 1 << width, rows,
                                dtype=np.int64) + base).astype(np.int64)
                  for _ in range(nodes)]
    spec = plan_packing(chunks)
    assert spec is not None
    col = pack_column(chunks, spec)
    allv = np.concatenate(chunks)
    if sel <= 0.0:
        v = float(allv.min()) - 1.0
    elif sel >= 1.0:
        v = float(allv.max()) + 1.0
    else:
        v = float(np.quantile(allv, sel))
    info = PackedInfo(width=col.width, offset=col.offset,
                      values=col.values, dtype=col.dtype)
    rw = scan_rewrite(C("x") <= Lit(v), {"x": info})
    assert rw is not None and not rw.negate
    lo, hi = rw.static_bounds()
    wpn = col.words_per_node
    all_words = np.asarray(col.words).reshape(nodes, wpn)
    for i in range(nodes):
        words = jnp.asarray(all_words[i])
        bits = _IMPLS[impl](words, lo, hi, rows=col.rows,
                            padded_rows=col.padded_rows, width=col.width)
        mask = np.asarray(compression.unpack_bitset(
            bits, col.padded_rows))[:col.rows]
        # decode-then-filter on this node
        local = dataclasses.replace(col, words=words, num_nodes=1)
        decoded = np.asarray(local.decode())
        want_mask = decoded <= np.asarray(v, decoded.dtype)
        np.testing.assert_array_equal(mask, want_mask)
        # late materialization: gather survivors only, bit-identical
        idx = np.nonzero(mask)[0]
        got = np.asarray(local.gather(jnp.asarray(idx, jnp.uint32)))
        np.testing.assert_array_equal(got, decoded[want_mask])


_GRID = [
    (w, sel, nodes, impl, kind)
    for w in (1, 6, 11)
    for sel in (0.0, 0.5, 1.0)
    for nodes in (1, 4)
    for impl in ("ref", "xla", "pallas")
    for kind in ("for", "dict")
]

try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=60, deadline=None)
    @given(width=st.integers(1, 16), sel=st.sampled_from([0.0, 0.5, 1.0]),
           nodes=st.sampled_from([1, 2, 4]),
           impl=st.sampled_from(["ref", "xla", "pallas"]),
           kind=st.sampled_from(["for", "dict"]),
           seed=st.integers(0, 2 ** 16))
    def test_packed_scan_equivalence(width, sel, nodes, impl, kind, seed):
        _check_packed_scan_equivalence(width, sel, nodes, impl, kind, seed)
except ImportError:  # fixed pre-seeded grid when hypothesis is absent
    @pytest.mark.parametrize("width,sel,nodes,impl,kind", _GRID)
    def test_packed_scan_equivalence(width, sel, nodes, impl, kind):
        _check_packed_scan_equivalence(width, sel, nodes, impl, kind,
                                       seed=width * 1000 + nodes)


# -- driver: packed residency is the default and matches raw -----------------

@pytest.fixture(scope="module")
def raw_driver(cluster):
    from repro.tpch.driver import TPCHDriver

    return TPCHDriver(sf=0.01, cluster=cluster, seed=0, storage="raw")


def test_packed_driver_matches_raw_and_oracle(tpch_driver, raw_driver):
    import jax

    assert tpch_driver.storage == "packed" and raw_driver.storage == "raw"
    # hand-written plan path: packed tables decode at plan entry
    out_p = jax.tree.map(np.asarray, tpch_driver.run("q1"))
    out_r = jax.tree.map(np.asarray, raw_driver.run("q1"))
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-6),
                 out_p, out_r)
    np.testing.assert_allclose(out_p, tpch_driver.oracle("q1"), rtol=2e-4)
    # lowered IR path: the filter runs predicate-on-packed on the packed
    # driver and eval_expr on the raw one — results must agree
    a = jax.tree.map(np.asarray, tpch_driver.query("q6").value)
    b = jax.tree.map(np.asarray, raw_driver.query("q6").value)
    jax.tree.map(lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-6),
                 a, b)


def test_packed_residency_shrinks_footprint(tpch_driver, raw_driver):
    assert tpch_driver.resident_bytes < raw_driver.resident_bytes
    # the decoded host views stay bit-identical to the raw generation
    for tname, rt in raw_driver.tables.items():
        pt = tpch_driver.tables[tname]
        for cname, col in rt.columns.items():
            np.testing.assert_array_equal(
                np.asarray(pt.columns[cname]), np.asarray(col),
                err_msg=f"{tname}.{cname}")


def test_storage_metrics_and_explain(tpch_driver):
    m = tpch_driver.obs.metrics
    assert m.value("storage.bytes_resident") == tpch_driver.resident_bytes
    assert m.value("storage.bytes_resident.lineitem") > 0
    before = m.value("storage.bytes_scanned")
    prep = tpch_driver.prepare("q6")
    prep.execute()
    assert m.value("storage.bytes_scanned") > before
    assert m.value("storage.bytes_scanned.lineitem") > 0
    txt = tpch_driver.explain("q6").text()
    assert "packed" in txt and "scan l_" in txt
    txt = tpch_driver.explain_analyze("q6").text()
    assert "storage: resident" in txt and "scanned (cumulative)" in txt


def test_resident_budget_guard(cluster):
    from repro.tpch.driver import ResidentBudgetError, TPCHDriver

    with pytest.raises(ResidentBudgetError, match="resident"):
        TPCHDriver(sf=0.01, cluster=cluster, seed=0, resident_budget=1024)


# -- scan-roofline rates by device kind ------------------------------------


def test_scan_calibration_table_names_its_sources():
    from repro.core import scancal

    for kind, cal in scancal.TABLE.items():
        assert cal.source, kind
    assert scancal.for_device("cpu") is scancal.TABLE["cpu"]
    with pytest.raises(scancal.ScanCalError, match="TPU v99"):
        scancal.for_device("TPU v99")


def test_lowering_takes_rates_from_the_catalog_device_kind(tpch_driver):
    """An uncalibrated device kind is an error at lowering, not a silent
    default; the catalog carries the kind of the driver's devices."""
    from repro.core import scancal
    from repro.query import lower
    from repro.tpch import queries as tq

    assert tpch_driver.catalog.device_kind == "cpu"
    cat = dataclasses.replace(tpch_driver.catalog, device_kind="TPU v99")
    with pytest.raises(scancal.ScanCalError):
        lower(tq.PARAM_QUERIES["q6"](), cat)


def test_generated_data_is_pinned_by_the_seed():
    """A seed names one dataset in every process: these sums were taken in
    other processes (Python's string hash, salted per process, no longer
    keys the per-table streams)."""
    from repro.tpch import dbgen

    t = dbgen.generate(0.001, 2, 3)
    got = (int(np.asarray(t["lineitem"].columns["l_shipdate"]).sum()),
           int(np.asarray(t["orders"].columns["o_custkey"]).sum()),
           int(np.asarray(t["part"].columns["p_type"]).sum()))
    assert got == (7700888, 109305, 15029)


def test_driver_plans_scan_packed_columns(tpch_driver, monkeypatch):
    """A query the driver lowers filters packed columns with the scan
    kernel: the compiled plan receives the packed words, not columns
    decoded at plan entry."""
    from repro.query import Q

    calls = []
    real = ops.scan_filter
    monkeypatch.setattr(ops, "scan_filter",
                        lambda *a, **k: calls.append(k["width"]) or real(*a, **k))
    q = (Q.scan("lineitem").filter(C("l_quantity") < 17.0)
         .group_agg(aggs=[("n", "count")]).named("packed_probe"))
    ans = tpch_driver.query(q)
    assert calls, "the lowered plan decoded l_quantity instead of scanning it"
    want = (tpch_driver.tables["lineitem"].columns["l_quantity"] < 17.0).sum()
    assert float(np.asarray(ans.value).reshape(())) == float(want)
