#!/usr/bin/env python3
"""Bring-up check of the TPC-H engine on TPU chips, oracle-checked.

    python chip_smoke.py                  # one chip, SF 10
    python chip_smoke.py --chips 4        # a four-node cluster on four chips

One chip runs these phases in order, in one process, through the library's
own entry points: device check, load (generate, pack, place), prepared
statements (``prepare().execute()`` and a 16-lane ``execute_batch``),
registry plans (``run``/``run_ir``), serving (``build_cubes`` and
``OLAPEngine.submit``) and native kernels (the Pallas kernels compiled for
the chip, not interpreted).  ``--chips 4`` runs only the cluster path: the
exchange queries on both all-to-all backends.

Every answer is checked against the float64 numpy oracles of
``repro.tpch.reference``.  Nothing catches a failure and carries on: any
failed check exits nonzero.  Seconds printed before the last line are
bring-up observations (first call, which compiles, and one warm call), not
benchmark results.  The last line of standard output is one JSON object
naming the device, printed only when every phase passed.
"""
from __future__ import annotations

import argparse
import asyncio
import importlib.metadata
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
PARAM_NAMES = ("q1", "q6", "q14_promo")
BATCH_LANES = 16


class SmokeError(RuntimeError):
    """A phase found the system not working on the chip."""


def say(msg: str) -> None:
    print(msg, flush=True)


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def _version(pkg: str) -> str:
    try:
        return importlib.metadata.version(pkg)
    except importlib.metadata.PackageNotFoundError:
        return "not installed"


# ---------------------------------------------------------------------------
# oracle checks
# ---------------------------------------------------------------------------


class ParamOracle:
    """Float64 oracle of a PARAM_QUERIES binding, memoized per binding
    (draws repeat, and each oracle call scans the host tables)."""

    def __init__(self, driver):
        self.driver = driver
        self.memo = {}

    def __call__(self, name: str, binding: dict):
        from repro.tpch import queries as tq
        from repro.tpch.reference import ALL

        key = (name, tuple(sorted(binding.items())))
        if key not in self.memo:
            p = tq.oracle_params(name, binding)
            if name == "q14_promo":  # the promo revenue term of Q14
                self.memo[key] = ALL["q14"](self.driver.tables, p=p)[1]
            else:
                self.memo[key] = ALL[name](self.driver.tables, p=p)
        return self.memo[key]


def check_param(name: str, value, want) -> None:
    got = np.asarray(value, np.float64)
    if name == "q1":
        np.testing.assert_allclose(got.reshape(np.shape(want)), want,
                                   rtol=2e-4)
    else:
        np.testing.assert_allclose(got.reshape(()), want, rtol=2e-4,
                                   atol=1e-2)


def _topk_check(values, keys, valid, want) -> None:
    from repro.tpch.reference import assert_topk_matches

    assert_topk_matches(values, keys, valid, *want)


def check_registry(name: str, out, want) -> None:
    """Compare a ``run``/``run_ir`` result with its registry oracle."""
    import jax

    out = jax.tree.map(np.asarray, out)
    if name in ("q1", "q6"):
        np.testing.assert_allclose(
            np.asarray(out["value"]).reshape(np.shape(want)), want,
            rtol=2e-4)
    elif name == "q4":
        np.testing.assert_array_equal(out["value"][:, 0], want)
    elif name == "q18":
        ov, okeys = want
        n = int(out["valid"].sum())
        if n != int(np.isfinite(ov).sum()):
            raise AssertionError(f"q18 found {n} rows, oracle "
                                 f"{int(np.isfinite(ov).sum())}")
        np.testing.assert_allclose(out["values"][:n], ov[:n], rtol=2e-3,
                                   atol=1e-2)
        np.testing.assert_array_equal(out["keys"][:n], okeys[:n])
    elif name == "q3_lazy":
        winners, overflow = out
        if bool(overflow):
            raise AssertionError("q3_lazy request exchange overflowed")
        _topk_check(winners.values, winners.keys, winners.valid, want)
    elif name in ("q15", "q15_approx"):
        if bool(out.get("overflow", False)):
            raise AssertionError(f"{name} exchange overflowed")
        _topk_check(out["total_revenue"], out["s_suppkey"], out["valid"],
                    want)
    else:
        raise KeyError(name)
    if isinstance(out, dict) and bool(out.get("overflow", False)):
        raise AssertionError(f"{name} exchange overflowed")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def phase_device(chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise SmokeError(
            f"no TPU: JAX's default platform is {d0.platform!r} "
            f"({d0.device_kind}); this check runs on the chip only")
    if os.environ.get("REPRO_NO_KERNELS") == "1":
        raise SmokeError("REPRO_NO_KERNELS=1 would replace the Pallas "
                         "kernels with the reference ops; unset it")
    if len(devices) < chips:
        raise SmokeError(f"--chips {chips} needs {chips} devices, JAX "
                         f"sees {len(devices)}")
    say(f"device: {d0.device_kind} x{len(devices)} visible, using {chips}; "
        f"jax {jax.__version__}, jaxlib {_version('jaxlib')}, "
        f"libtpu {_version('libtpu')}")
    return devices[:chips]


def phase_load(sf: float, seed: int, devices):
    from repro.core import Cluster
    from repro.tpch.driver import TPCHDriver

    cluster = Cluster(devices=devices)
    d, secs = timed(lambda: TPCHDriver(sf, cluster=cluster, seed=seed,
                                       storage="packed"))
    ls = d.load_seconds
    say(f"load: SF {sf} seed {seed} on {cluster.num_nodes} node(s): "
        f"generate {ls['generate']:.1f}s, pack "
        f"{ls['pack']:.1f}s, place {ls['place']:.1f}s "
        f"(driver total {secs:.1f}s); resident_bytes {d.resident_bytes} "
        f"({d.resident_bytes / 2**30:.3f} GiB), lineitem "
        f"{d.catalog.table('lineitem').num_rows} rows")
    for name, t in d.placed.items():
        shards = {}
        for col in t.columns.values():
            arr = getattr(col, "words", col)
            for s in arr.addressable_shards:
                shards.setdefault(s.device.id, 0)
                shards[s.device.id] += s.data.nbytes
        if cluster.num_nodes > 1 or name == "lineitem":
            say(f"  placement {name}: bytes by device id "
                f"{dict(sorted(shards.items()))}")
    return d


def phase_scan_plan(d) -> None:
    from repro.core import scancal

    cal = scancal.for_device(d.catalog.device_kind)
    say(f"scan calibration [{d.catalog.device_kind}]: mem "
        f"{cal.mem_gbps:.1f} GB/s, scan {cal.scan_gvps:.1f} Gv/s, unpack "
        f"{cal.unpack_gvps:.1f} Gv/s ({cal.source})")
    for row in d.explain("q6").plan_rows:
        for s in row.get("scans", ()):
            say(f"  q6 scan {s.table}.{s.column}: {s.mode} (width "
                f"{s.width}, {s.scan_bytes} B/node predicted; {s.reason})")


def phase_prepared(d, seed: int, oracle: ParamOracle) -> None:
    from repro.tpch import queries as tq

    rng = np.random.default_rng(seed)
    for name in PARAM_NAMES:
        prep = d.prepare(tq.PARAM_QUERIES[name]())
        bindings = [tq.random_binding(name, rng) for _ in range(3)]
        secs = []
        for b in bindings:
            ans, s = timed(lambda: prep.execute(b))
            secs.append(s)
            if ans.tier != 2 or bool(ans.overflow):
                raise AssertionError(f"{name}: tier {ans.tier}, overflow "
                                     f"{ans.overflow}")
            check_param(name, ans.value, oracle(name, b))
        lanes = [tq.random_binding(name, rng) for _ in range(BATCH_LANES)]
        ans, cold = timed(lambda: prep.execute_batch(lanes))
        if np.asarray(ans.overflow).any():
            raise AssertionError(f"{name} batch overflow {ans.overflow}")
        values = np.asarray(ans.value)
        for i, b in enumerate(lanes):
            check_param(name, values[i], oracle(name, b))
        _, warm = timed(lambda: prep.execute_batch(lanes))
        say(f"prepared {name}: {len(bindings)} bindings + {BATCH_LANES}-lane "
            f"batch match the oracle; execute first {secs[0]:.2f}s, warm "
            f"{min(secs[1:]):.4f}s; batch first {cold:.2f}s, warm "
            f"{warm:.4f}s")


def phase_registry(d, names) -> None:
    from repro.core import plans

    for name, how in names:
        run = d.run_ir if how == "run_ir" else d.run
        out, cold = timed(lambda: run(name))
        check_registry(name, out, d.oracle(name))
        _, warm = timed(lambda: jax_ready(run(name)))
        say(f"{how}({name}) [{d.backend}] matches oracle "
            f"'{plans.get(name).oracle}'; first {cold:.2f}s, warm "
            f"{warm:.4f}s")


def jax_ready(x):
    import jax

    return jax.block_until_ready(x)


def phase_param_on_backend(d, name: str, seed: int,
                           oracle: ParamOracle) -> None:
    from repro.tpch import queries as tq

    prep = d.prepare(tq.PARAM_QUERIES[name]())
    b = tq.random_binding(name, np.random.default_rng(seed))
    ans, cold = timed(lambda: prep.execute(b))
    if bool(ans.overflow):
        raise AssertionError(f"{name} overflowed")
    check_param(name, ans.value, oracle(name, b))
    say(f"prepared {name} [{d.backend}] matches the oracle; first "
        f"{cold:.2f}s")


def phase_serving(d, seed: int) -> None:
    from repro.serve import workload as wl
    from repro.serve.olap_engine import OLAPEngine

    _, build = timed(d.build_cubes)
    say(f"serving: {len(d.cubes)} cubes built in {build:.2f}s")
    items = wl.mixed_workload(d, 24, seed=seed)
    kinds = {k: sum(1 for i in items if i.kind == k)
             for k in ("tier1", "param", "tier2")}
    if min(kinds.values()) == 0:
        raise AssertionError(f"workload lacks a request kind: {kinds}")
    _, warm = timed(lambda: wl.warm_workload(d, items, batch_sizes=(1, 2, 4)))
    want = wl.sequential_baseline(d, items)

    async def go():
        async with OLAPEngine(d, max_batch=4) as engine:
            return await wl.run_closed_loop(engine, items, clients=4)

    got, secs = timed(lambda: asyncio.run(go()))
    worst = 0.0
    for g, w in zip(got, want):
        if not g.ok:
            raise AssertionError(f"{g.item.name} failed: {g.answer!r}")
        if g.answer.tier != w.answer.tier:
            raise AssertionError(f"{g.item.name}: tier {g.answer.tier} vs "
                                 f"prepared {w.answer.tier}")
        a, b = np.asarray(g.answer.value), np.asarray(w.answer.value)
        if g.answer.tier == 1:  # the same host-side rollup slice
            np.testing.assert_array_equal(a, b, err_msg=g.item.name)
        else:
            # a coalesced lane runs the batched executable, a separate XLA
            # program: its f32 sums may round differently in the last bit
            np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=g.item.name)
            worst = max(worst, float(np.max(np.abs(a - b) / np.maximum(
                np.abs(b), np.finfo(np.float32).tiny))))
    say(f"serving: {len(items)} OLAPEngine.submit requests {kinds} equal "
        f"the prepared path (tier 1 bit-identical, tier 2 max relative "
        f"difference {worst:.3g}); warm-up {warm:.2f}s, served in "
        f"{secs:.2f}s")


def phase_native_kernels(d) -> None:
    """The lowered q6 holds the scan kernel; the q3_lazy hand plan, whose
    request exchange is packed on every node count (the lowered plans pick
    a local or bitset semi-join here), holds the wire codec.  The check
    reads the lowered StableHLO: an executable loaded from the persistent
    compilation cache need not carry its HLO text."""
    from repro.kernels import ops

    impl = ops._codec_impl()
    if impl != "pallas":
        raise SmokeError(f"kernel path is {impl!r}, not 'pallas'")
    columns = {n: t.columns for n, t in d.placed.items()}
    for name, what, text in (
            ("q6", "scan filter", lambda: d.lowered_text("q6")),
            ("q3_lazy", "wire codec",
             lambda: d.compile("q3_lazy").lower(columns).as_text())):
        hlo, secs = timed(text)
        n = hlo.count("tpu_custom_call")
        if not n:
            raise SmokeError(f"no tpu_custom_call in the lowered {name}")
        say(f"native kernels: lowered {name} holds {n} tpu_custom_call "
            f"({what}); lowering {secs:.2f}s")


# ---------------------------------------------------------------------------
# drivers
# ---------------------------------------------------------------------------


def one_chip(devices, sf: float, seed: int) -> None:
    d = phase_load(sf, seed, devices)
    phase_scan_plan(d)
    oracle = ParamOracle(d)
    phase_prepared(d, seed, oracle)
    phase_registry(d, [("q1", "run_ir"), ("q6", "run_ir"), ("q4", "run_ir"),
                       ("q18", "run_ir"), ("q3_lazy", "run"),
                       ("q15_approx", "run")])
    phase_serving(d, seed)
    phase_native_kernels(d)


def four_chips(devices, sf: float, seed: int) -> None:
    d = phase_load(sf, seed, devices)
    oracle = ParamOracle(d)
    for backend in ("xla", "one_factor"):
        d.use_backend(backend)
        phase_registry(d, [("q4", "run_ir"), ("q18", "run_ir"),
                           ("q3_lazy", "run"), ("q15", "run")])
        phase_param_on_backend(d, "q14_promo", seed, oracle)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sf", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro import compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 2
    try:
        devices = phase_device(args.chips)
    except SmokeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 1
    say(f"compile cache: {compile_cache.enable()}")
    t0 = time.perf_counter()
    if args.chips == 1:
        one_chip(devices, args.sf, args.seed)
    else:
        four_chips(devices, args.sf, args.seed)
    say(f"all phases passed in {time.perf_counter() - t0:.1f}s")
    import jax

    d0 = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
