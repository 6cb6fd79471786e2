#!/usr/bin/env python3
"""One benchmark run of one cell on the chips of this machine.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in ``BENCHMARK.json``, which names its configuration
(``bench/configs/<config>.json``) and its traffic mix
(``bench/traffic/<traffic>.json``, read by the generator
``bench/traffic/<driver>.py`` that the mix names), makes the data from
``--seed``, warms up
every program the traffic uses, measures for ``--seconds`` seconds, checks
every answer of the window against the plain reference of
``bench/reference/``, and prints one JSON object as the last line of
standard output.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics; with ``--trace 1`` the window runs under the profiler and the
metrics are the cell's per-layer metrics, each computed by its reader
``bench/metrics/<metric>.py``.  Which metrics a cell reports is read from
``BENCHMARK.json``.

Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoChip(RuntimeError):
    """No accelerator, or fewer chips than the cell asks for."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """The cell's entry of ``BENCHMARK.json``, resolved (``resolve``)."""
    cells = {w["name"]: w for w in load_json(ROOT, "BENCHMARK.json")
             ["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    return resolve(cells[name])


def resolve(cell: dict):
    """A cell (``config`` and ``traffic`` names) with its traffic mix
    (``bench/traffic/<traffic>.json``) under ``mix``, and its configuration
    (``bench/configs/<config>.json``)."""
    cell = dict(cell)
    cell["mix"] = load_json(BENCH, "traffic", f"{cell['traffic']}.json")
    return cell, load_json(BENCH, "configs", f"{cell['config']}.json")


def cell_metrics(name: str, per_layer: bool) -> list:
    """The cell's metric entries from ``BENCHMARK.json``: the end-to-end
    ones, or the per-layer ones.  A metric with a ``workloads`` list
    belongs to the cells it names.  One without belongs, as the file's
    format defines it, to every cell if it is end-to-end, and if it is
    per-layer to every cell that reports the end-to-end metric it
    ``moves``."""
    bench = load_json(ROOT, "BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    if not per_layer:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if (name in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]


def load_reader(metric: str):
    path = os.path.join(BENCH, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def check_device(chips: int):
    import jax

    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise NoChip(f"no TPU: JAX's platform is {d0.platform!r} "
                     f"({d0.device_kind}); this benchmark runs on the chip "
                     f"only")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devices)}")
    return devices[:chips]


class CompileCounter:
    """Counts XLA compilations (backend compiles) while installed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1


def enable_compile_cache() -> None:
    """The program's persistent compilation cache (in the checkout), with
    every program, however quick to compile, written to it, so that only
    the first run of a cell in a checkout compiles."""
    import jax

    from repro import compile_cache

    say(f"compile cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def setup_cell(cell: dict, config: dict, seed: int, devices):
    """Load the deployment (generate, pack, place; cubes where the
    configuration has them), build the traffic and warm up every program
    it will run.  Returns ``(driver, traffic)``."""
    import jax

    from repro.core import Cluster
    from repro.tpch.driver import TPCHDriver

    traffic_mod = importlib.import_module(
        f"bench.traffic.{cell['mix']['driver']}")
    with jax.profiler.TraceAnnotation("bench.load"):
        driver = TPCHDriver(config["scale_factor"],
                            cluster=Cluster(devices=devices), seed=seed,
                            storage=config["storage"],
                            backend=config.get("exchange", "xla"))
    if config.get("cubes"):
        with jax.profiler.TraceAnnotation("bench.cubes"):
            driver.build_cubes()
    traffic = traffic_mod.Traffic(driver, cell["mix"], config, seed)
    with jax.profiler.TraceAnnotation("bench.warmup"):
        traffic.warm()
    return driver, traffic


def run_cell(name: str, cell: dict, config: dict, *, seed: int,
             seconds: float, trace: bool, devices, t_start: float) -> dict:
    """Set up, measure, check: the result object of one run."""
    import jax

    from bench import trace_reduce
    from bench.reference import compare, tpch_data
    from bench.reference.tpch import Reference
    from bench.traffic.common import percentile

    compiles = CompileCounter()
    driver, traffic = setup_cell(cell, config, seed, devices)
    load_seconds = dict(driver.load_seconds)
    setup_s = time.perf_counter() - t_start
    say(f"setup: {setup_s:.3f}s (load {json.dumps(load_seconds)}), "
        f"resident {driver.resident_bytes} bytes")

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    ev0, cc0 = len(driver.compile_events), compiles.count
    if trace:
        jax.profiler.start_trace(trace_dir)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
        records = traffic.run(seconds)
    window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    window_compiles = (len(driver.compile_events) - ev0, compiles.count - cc0)
    serve = traffic.stats()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    say(f"window: {len(records)} requests in {window_s:.3f}s; compile "
        f"events in the window: {window_compiles[0]} plan traces, "
        f"{window_compiles[1]} XLA compiles; serving counters {serve}")

    # the program's state goes before the reference runs
    traffic.close()
    del traffic, driver
    gc.collect()

    t_ref = time.perf_counter()
    tables = tpch_data.generate(config["scale_factor"], config["nodes"], seed)
    ref = Reference(tables)
    limits = config["limits"]
    per_answer, n_ok = [], 0
    for r in records:
        if r.error is not None:
            continue
        nums = compare.check_answer(r.value, r.overflow,
                                    ref.answer(r.name, r.binding))
        r.ok = compare.passes(nums, limits)
        n_ok += r.ok
        per_answer.append(nums)
    reading = compare.worst(per_answer)
    failed = len(records) - n_ok
    correct = bool(records) and failed == 0 and window_compiles == (0, 0)
    say(f"reference: {len(ref.memo)} distinct answers checked in "
        f"{time.perf_counter() - t_ref:.3f}s")
    del tables, ref
    gc.collect()

    by_query = {}
    for r in records:
        by_query.setdefault(r.name, []).append(r.latency_s * 1e3)
    for q, lat in sorted(by_query.items()):
        say(f"latency {q}: n {len(lat)}, p50 {percentile(lat, 0.5):.3f}ms, "
            f"p95 {percentile(lat, 0.95):.3f}ms, max {max(lat):.3f}ms")
    late = [r.sent - r.due for r in records]
    say(f"generator lateness: p50 {percentile(late, 0.5) * 1e3:.3f}ms, "
        f"max {max(late, default=0) * 1e3:.3f}ms")
    errors = [f"{r.name}: {r.error}" for r in records if r.error]
    if errors:
        say(f"{len(errors)} requests failed, first: {errors[0]}")

    view = types.SimpleNamespace(
        config=config, records=records, window_s=window_s,
        seconds=seconds, t0=t0, setup_s=setup_s, peak_bytes=peak, load_seconds=load_seconds,
        serve=serve, trace=None, device_kind=devices[0].device_kind)
    result = {"correct": correct, "attempted": len(records), "failed": failed,
              "metrics": {}}
    if trace:
        t_red = time.perf_counter()
        view.trace = trace_reduce.reduce(trace_reduce.find_trace(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        say(f"trace: busy {view.trace['busy_s']:.6f}s of "
            f"{view.trace['window_s']:.6f}s on {view.trace['devices']}, "
            f"reduced in {time.perf_counter() - t_red:.3f}s")
    for m in cell_metrics(name, per_layer=trace):
        value = load_reader(m["name"])(view)
        if value is None or not math.isfinite(value):
            say(f"metric {m['name']}: nothing to read in this run ({value})")
            continue
        result["metrics"][m["name"]] = {"value": value, "unit": m["unit"]}
    result["device"] = {"platform": devices[0].platform,
                        "kind": devices[0].device_kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        result["device"].update(busy_s=view.trace["busy_s"],
                                window_s=view.trace["window_s"])
        result["breakdown"] = trace_reduce.breakdown(view.trace)
    result["checks"] = {  # an answer of the wrong shape reads as 1e300
        **{k: {"value": min(reading[k], 1e300), "limit": limits[k]}
           for k in compare.NUMBERS},
        "unanswered": {"value": len(errors), "limit": 0},
        "window_compiles": {"value": sum(window_compiles), "limit": 0},
    }
    for k, v in result["checks"].items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell, config = load_cell(args.workload)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        print(f"bench: the program is not in this checkout ({e})",
              file=sys.stderr)
        return 2
    try:
        devices = check_device(cell["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    enable_compile_cache()
    result = run_cell(args.workload, cell, config, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      devices=devices, t_start=T_START)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
