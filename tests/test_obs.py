"""Observability layer: metrics registry math, structured traces, and
EXPLAIN ANALYZE.

- histogram percentiles on fixed distributions with known quantiles (the
  log-bucket scheme guarantees ~2.2% relative error),
- span nesting, and the spans in a ``jax.profiler`` trace on the
  profiler's clock: its Chrome trace-event JSON (the shape Perfetto loads)
  round-trips, the driver's ``query`` span holds ``bind`` / ``dispatch`` /
  ``fetch``, and a sample trace artifact is written for CI,
- the driver's set-up timers (``load_seconds``) and the layer scopes of
  lowered plans (``op_name`` metadata that leaves the compiled program as
  it is),
- ``explain_analyze`` golden checks on q6 (predicted plan fields next to
  observed timings/counters) and on a Tier-1 cube-served query,
- per-semijoin all-to-all attribution against synthetic instruction
  streams,
- routing/caching/overflow counters emitted by the driver paths, and the
  serving-layer trimmed-median/p99 statistics.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import glob
import gzip
import importlib
import json
import os
import re
import shutil

import jax
import pytest

from repro.launch.roofline import CollectiveInstr
from repro.obs import (
    Histogram,
    MetricsRegistry,
    Observer,
    SemiJoinInfo,
    attribute_semijoin_bytes,
)
from repro.query import Q, C
from repro.tpch.driver import _PlanEntry

pytestmark = pytest.mark.tier1


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------


def test_histogram_percentiles_uniform():
    h = Histogram("t")
    for v in range(1, 1001):  # uniform 1..1000
        h.record(float(v))
    assert h.count == 1000
    # log-bucketing guarantees ~2.2% relative error; allow 5% headroom
    assert h.quantile(0.50) == pytest.approx(500, rel=0.05)
    assert h.quantile(0.95) == pytest.approx(950, rel=0.05)
    assert h.quantile(0.99) == pytest.approx(990, rel=0.05)
    assert h.quantile(0.0) == pytest.approx(1, rel=0.05)
    assert h.quantile(1.0) == 1000  # clamped to observed max


def test_histogram_bimodal_and_zeros():
    h = Histogram("t")
    for _ in range(50):
        h.record(1.0)
    for _ in range(50):
        h.record(1000.0)
    assert h.quantile(0.25) == pytest.approx(1.0, rel=0.05)
    assert h.quantile(0.75) == pytest.approx(1000.0, rel=0.05)
    z = Histogram("z")
    for _ in range(90):
        z.record(0.0)
    for _ in range(10):
        z.record(100.0)
    assert z.quantile(0.5) == 0.0
    assert z.quantile(0.95) == pytest.approx(100.0, rel=0.05)
    s = z.snapshot()
    assert s["count"] == 100 and s["max"] == 100.0


def test_registry_counters_gauges_and_report():
    reg = MetricsRegistry()
    reg.counter("a.hits").inc()
    reg.counter("a.hits").inc(4)
    reg.gauge("a.size").set(7)
    reg.histogram("a.lat").record(3.0)
    assert reg.value("a.hits") == 5
    assert reg.value("never.touched") == 0
    snap = reg.snapshot()
    assert snap["a.hits"] == 5 and snap["a.size"] == 7.0
    assert snap["a.lat"]["count"] == 1
    report = reg.report()
    assert "a.hits" in report and "p99" in report
    with pytest.raises(TypeError):
        reg.gauge("a.hits")  # type collision is a bug, not a silent rebind


# ---------------------------------------------------------------------------
# trace layer
# ---------------------------------------------------------------------------


def _profile_file(log_dir, pattern: str) -> str:
    """The newest file of a ``jax.profiler`` trace under ``log_dir``."""
    paths = sorted(glob.glob(os.path.join(str(log_dir), "plugins", "profile",
                                          "*", pattern)))
    assert paths, f"no {pattern} under {log_dir}"
    return paths[-1]


def _chrome_events(log_dir, names) -> dict:
    """name -> its complete/instant events in the trace's Chrome JSON."""
    with gzip.open(_profile_file(log_dir, "perfetto_trace.json.gz")) as f:
        doc = json.load(f)  # round-trip through disk
    out = collections.defaultdict(list)
    for e in doc["traceEvents"]:
        if e.get("name") in names:
            out[e["name"]].append(e)
    return out


def _host_spans(log_dir, names) -> dict:
    """name -> [(start_ns, end_ns)] of the host events in the .xplane.pb."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(_profile_file(log_dir, "*.xplane.pb"))
    out = collections.defaultdict(list)
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    s0 = int(e.start_ns)
                    out[e.name].append((s0, s0 + int(e.duration_ns)))
    return out


def test_span_nesting_and_chrome_export_roundtrip(tmp_path):
    obs = Observer()
    with jax.profiler.trace(str(tmp_path), create_perfetto_trace=True):
        with obs.span("query", source="qX", request=7) as sp:
            sp.set(tier=2)
            with obs.span("route", cat="route"):
                pass
            obs.event("xla.trace", cat="plan", label="qX")
    roots = list(obs.spans)
    assert len(roots) == 1
    root = roots[0]
    assert [c.name for c in root.children] == ["route", "xla.trace"]
    assert root.attrs["tier"] == 2
    assert root.dur >= root.children[0].dur >= 0

    # the spans, and no instant event, in the profiler's Chrome trace JSON
    events = _chrome_events(tmp_path, {"query", "route", "xla.trace"})
    assert set(events) == {"query", "route"}
    (q,), (r,) = events["query"], events["route"]
    for e in (q, r):
        assert e["ph"] == "X" and e["dur"] >= 0.0
        assert isinstance(e["ts"], float) and "pid" in e and "tid" in e
    assert q["ts"] <= r["ts"] <= r["ts"] + r["dur"] <= q["ts"] + q["dur"]
    # plain attributes given at open become the annotation's arguments
    assert q["args"]["source"] == "qX" and q["args"]["request"] == "7"


def test_disabled_observer_swallows_spans_keeps_metrics():
    obs = Observer(enabled=False)
    with obs.span("query") as sp:
        sp.set(tier=1)
        obs.event("nested")
    assert len(obs.spans) == 0
    obs.metrics.counter("still.live").inc()
    assert obs.metrics.value("still.live") == 1


def test_span_records_exception():
    obs = Observer()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("no")
    assert "ValueError" in obs.last("boom").attrs["error"]


# ---------------------------------------------------------------------------
# per-semijoin byte attribution
# ---------------------------------------------------------------------------


def _sj(alt, wire_kind="packed", index=0):
    return SemiJoinInfo(index=index, table="part", alt=alt, capacity=256,
                        capacity_key="sj", wire_kind=wire_kind, key_bits=11,
                        gamma=0.1)


def _a2a(n, nbytes=100):
    return [CollectiveInstr(name=f"a2a.{i}", kind="all-to-all", bytes=nbytes)
            for i in range(n)]


def test_attribution_packed_and_raw_chunks():
    sjs = [_sj("request", "packed", 0), _sj("bitset", index=1),
           _sj("request", "raw", 2)]
    instrs = ([CollectiveInstr("ar", "all-reduce", 999)]  # non-a2a: ignored
              + _a2a(5))
    assert attribute_semijoin_bytes(instrs, sjs)
    assert sjs[0].a2a_bytes == 200 and sjs[0].a2a_count == 2
    assert sjs[1].a2a_bytes is None  # bitset semi-join owns no all-to-all
    assert sjs[2].a2a_bytes == 300 and sjs[2].a2a_count == 3


def test_attribution_refuses_count_mismatch():
    sjs = [_sj("request", "packed")]
    assert not attribute_semijoin_bytes(_a2a(3), sjs)  # packed expects 2
    assert sjs[0].a2a_bytes is None  # untouched — totals-only fallback


# ---------------------------------------------------------------------------
# serving statistics
# ---------------------------------------------------------------------------


def test_trimmed_median_and_p99():
    from repro.cube.serving import _p99, _trimmed_median

    # an outlier that min-of-N would hide and a mean would absorb
    xs = [1.0] * 9 + [100.0]
    assert _trimmed_median(xs) == 1.0
    assert _p99(xs) == 100.0
    assert _trimmed_median([3.0, 1.0, 2.0]) == 2.0  # n<5: no trim


# ---------------------------------------------------------------------------
# EXPLAIN / EXPLAIN ANALYZE against the real driver
# ---------------------------------------------------------------------------


def test_explain_is_static(tpch_driver):
    ev0 = len(tpch_driver.compile_events)
    rep = tpch_driver.explain("q6")
    assert not rep.analyzed
    assert len(tpch_driver.compile_events) == ev0  # nothing compiled
    text = rep.text()
    assert text.startswith("EXPLAIN q6")
    assert "Scan[lineitem" in text and "Filter[" in text
    assert "parameters:" in text


def test_explain_analyze_q6_golden(tpch_driver):
    rep = tpch_driver.explain_analyze("q6")
    assert rep.analyzed
    obs = rep.observed
    # predicted side: plan rows with selectivities, auto-extracted params
    assert [r["op"] for r in rep.plan_rows] == ["Scan", "Filter", "GroupAgg"]
    assert 0.0 < rep.plan_rows[1]["sel"] <= 1.0
    assert rep.params and all(k.startswith("_p") for k in rep.params)
    assert rep.cache in ("hit", "miss")
    # observed side: tier, timings, counters
    assert obs["tier"] == 2 and obs["source"] == "q6"
    assert obs["execute_ms"] > 0.0
    assert (obs["compile_ms"] is not None) == (obs["xla_traces"] > 0)
    assert obs["overflow"] is False
    assert "overflow_count" in obs and "compile_events" in obs
    # tier-2 plans carry the HLO collective profile
    assert obs["collective_bytes_by_op"]
    text = rep.text()
    assert "EXPLAIN ANALYZE q6" in text
    assert "route: tier 2" in text
    assert "timings:" in text and "collectives/device:" in text
    assert "exchange.overflow=" in text and "plan.compile_events=" in text


def test_explain_analyze_fresh_shape_reports_compile_time(tpch_driver):
    # a shape no other test prepares: the first execution must trace, so
    # compile vs execute time separate
    q = (Q.scan("lineitem")
         .filter((C("l_quantity") < 7.0) & (C("l_tax") >= 0.0)
                 & (C("l_discount") > 0.001))
         .group_agg(keys=(), aggs=[("obs_rev", "sum",
                                    C("l_extendedprice") * C("l_discount"))])
         .named("obs_fresh"))
    rep = tpch_driver.explain_analyze(q)
    obs = rep.observed
    assert obs["xla_traces"] >= 1
    assert obs["compile_ms"] is not None and obs["compile_ms"] >= 0.0
    assert obs["execute_ms"] > 0.0
    assert "XLA trace" in rep.text()


def test_explain_analyze_all_ir_queries(tpch_driver):
    """Acceptance sweep: every registered IR query explains with route
    tier, cache state, timings, and (tier 2) per-op collective bytes."""
    for name in ("q1", "q4", "q6", "q14_promo", "q18"):
        rep = tpch_driver.explain_analyze(name)
        assert rep.analyzed, name
        obs = rep.observed
        assert obs["tier"] in (1, 2), name
        assert obs["execute_ms"] > 0.0, name
        assert rep.plan_rows, name
        if obs["tier"] == 2:
            assert obs["collective_bytes_by_op"], name
        text = rep.text()
        assert f"EXPLAIN ANALYZE {name}" in text
        assert "plan cache" in text


def test_explain_analyze_tier1_route(tpch_driver):
    if tpch_driver.router is None:
        tpch_driver.build_cubes()
    rep = tpch_driver.explain_analyze("q1")
    assert rep.observed["tier"] == 1
    assert rep.observed["compile_ms"] is None  # cube slice, nothing compiled
    assert "rollup cube" in rep.text()


def test_driver_counters_and_spans(tpch_driver):
    d = tpch_driver
    if d.router is None:
        d.build_cubes()
    m = d.obs.metrics
    t1, t2 = m.value("driver.tier1"), m.value("driver.tier2")
    hits = m.value("plan_cache.hit")
    d.query("q1")   # cube-served
    d.query("q6")   # compiled plan
    d.query("q6")   # same shape again -> structural cache hit
    assert m.value("driver.tier1") == t1 + 1
    assert m.value("driver.tier2") == t2 + 2
    assert m.value("plan_cache.hit") >= hits + 1
    assert m.value("router.match") >= 1
    # spans: the last tier-2 query recorded a query->route(+execute) tree
    span = d.obs.last("query")
    assert span is not None and span.attrs["tier"] == 2
    assert span.find("route")
    # latency histograms feed the p99 gates
    assert m.histogram("query.tier2_us").count >= 2


def test_sample_trace_artifact(tpch_driver):
    """Write the CI trace artifact (uploaded by the workflow): a profiler
    trace of one query whose Perfetto JSON holds the driver's spans."""
    tpch_driver.query("q6")  # compiled before the trace
    log_dir = os.path.join("experiments", "trace")
    shutil.rmtree(log_dir, ignore_errors=True)
    with jax.profiler.trace(log_dir, create_perfetto_trace=True):
        tpch_driver.query("q6")
    names = {"query", "route", "bind", "dispatch", "fetch"}
    events = _chrome_events(log_dir, names)
    assert set(events) == names
    assert all(set(e) >= {"name", "ph", "ts", "pid", "tid"}
               for evs in events.values() for e in evs)
    (q,) = events["query"]
    assert q["args"]["source"] == "q6" and int(q["args"]["request"]) >= 1


def test_profiler_trace_holds_driver_spans(tpch_driver, tmp_path):
    prep = tpch_driver.prepare("q6")
    prep.execute()  # warm: the traced execution only dispatches
    with jax.profiler.trace(str(tmp_path)):
        prep.execute()
    spans = _host_spans(tmp_path, {"query", "bind", "dispatch", "fetch"})
    (q0, q1) = spans["query"][0]
    assert len(spans["query"]) == 1
    parts = []
    for name in ("bind", "dispatch", "fetch"):
        (part,) = spans[name]
        assert q0 <= part[0] <= part[1] <= q1, name
        parts.append(part)
    (b, d, f) = parts
    assert b[1] <= d[0] and d[1] <= f[0]  # in that order, disjoint
    # the span tree holds the same split, numbered per request
    last = tpch_driver.obs.last("query")
    assert [c.name for c in last.children] == ["route", "bind", "dispatch",
                                               "fetch"]
    assert last.attrs["request"] > 1


# filters no other test prepares, so the plan cache misses and the first
# execution compiles
FRESH_FILTERS = {
    "execute": (C("l_quantity") < 9.0) & (C("l_tax") <= 0.07),
    "execute_batch": (C("l_discount") >= 0.02) & (C("l_tax") < 0.05),
}


@pytest.mark.parametrize("how", sorted(FRESH_FILTERS))
def test_load_seconds_times_setup_steps(tpch_driver, how):
    ls = tpch_driver.load_seconds
    assert {"generate", "pack", "place", "catalog"} <= set(ls)
    assert all(v >= 0.0 for v in ls.values())
    prep = tpch_driver.prepare(
        Q.scan("lineitem").filter(FRESH_FILTERS[how])
        .group_agg(keys=(), aggs=[("obs_tax", "sum", C("l_tax"))])
        .named(f"obs_{how}"))
    run = (prep.execute if how == "execute"
           else lambda: prep.execute_batch([prep.defaults] * 2))
    before = ls.get("compile", 0.0)
    run()
    first = ls["compile"]
    assert first > before  # lowered, traced and compiled: set-up
    run()
    assert ls["compile"] == first  # a warm execution adds nothing


LAYERS = ("scan", "semijoin", "aggregate", "topk")
# HLO instruction: "[ROOT] %name = <shape> opcode(operands), ..."
HLO_OPCODE = re.compile(r"^\s*(?:ROOT\s+)?%\S+\s*=\s*.*?\s([a-z][a-z0-9_\-]*)\(")


def _compiled_hlo(driver, entry) -> str:
    return driver._lowered(entry).compile().as_text()


def _opcodes(hlo: str) -> collections.Counter:
    return collections.Counter(m.group(1) for m in map(HLO_OPCODE.match,
                                                        hlo.splitlines())
                               if m)


@pytest.mark.parametrize("name,want", [
    ("q1", {"scan", "aggregate"}),
    ("q6", {"scan", "aggregate"}),
    ("q14_promo", {"scan", "semijoin", "aggregate"}),
    ("q4", {"scan", "semijoin", "aggregate"}),
    ("q18", {"aggregate", "scan", "topk"}),
])
def test_lowered_plans_carry_layer_scopes(tpch_driver, monkeypatch, name,
                                          want):
    entry = tpch_driver.prepare(name).entry
    hlo = _compiled_hlo(tpch_driver, entry)
    found = set()
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        scoped = [p for p in op_name.split("/") if p in LAYERS]
        assert len(scoped) <= 1, op_name  # layers never nest
        found.update(scoped)
    assert found == want
    # the scopes are metadata only: the same program without them
    lower_mod = importlib.import_module("repro.query.lower")
    monkeypatch.setattr(lower_mod, "layer",
                        lambda layer_name: contextlib.nullcontext())
    bare = _compiled_hlo(tpch_driver,
                         _PlanEntry(entry.shape, entry.stats_binding))
    assert not any(p in LAYERS for op_name in
                   re.findall(r'op_name="([^"]*)"', bare)
                   for p in op_name.split("/"))
    assert _opcodes(bare) == _opcodes(hlo) and sum(_opcodes(hlo).values())


def test_semijoin_info_describes_roofline_prediction():
    info = SemiJoinInfo(index=0, table="orders", alt="request", capacity=4096,
                        capacity_key="sj", wire_kind="packed", key_bits=12,
                        gamma=0.2, codec_ms=0.143, wire_ms=0.674)
    s = info.describe()
    assert "predict codec 0.143ms+wire 0.674ms" in s
    # without a prediction (or on a local semi-join) the line is unchanged
    assert "predict" not in dataclasses.replace(info, codec_ms=None).describe()
    assert "predict" not in dataclasses.replace(info, alt="local").describe()


def test_explain_text_renders_codec_histograms():
    from repro.obs.explain import ExplainReport

    base = dict(query="x", route_tier=2, route_source="x", cache="miss",
                params={})
    obs = {"tier": 2, "source": "x", "execute_ms": 1.0, "compile_ms": None,
           "xla_traces": 0, "overflow": False, "overflow_count": 0,
           "compile_events": 0,
           "exchange.encode_ms": {"count": 3, "mean": 0.07},
           "exchange.decode_ms": {"count": 3, "mean": 0.12}}
    txt = ExplainReport(**base, observed=obs).text()
    assert "codec predicted/exchange: encode mean 0.07 ms (n=3), " \
           "decode mean 0.12 ms (n=3)" in txt
    # absent histograms (raw wire, cached plan): no codec line at all
    obs2 = {k: v for k, v in obs.items() if not k.startswith("exchange.")}
    assert "codec predicted" not in ExplainReport(**base, observed=obs2).text()
