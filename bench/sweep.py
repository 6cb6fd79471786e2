#!/usr/bin/env python3
"""Find the knee of an open-loop traffic mix on a configuration: the
highest offered rate the system sustains without a growing backlog.

    python bench/sweep.py --config tpch_sf10_cubes_1chip \
        --traffic dashboard_overload --seconds 51 \
        --rates 4 4.5 5 5.5 6 --seeds <n> <n> <n>

One process and one set-up (with the first seed's data); then each rate
in turn, lowest first, for ``--seconds`` of arrivals once per seed (the
seed draws the arrival order and the parameters, as in a run).  For each
window it prints one JSON line: the requests completed, the latency
median and 95th percentile from due time, how long the last answer came
after the last arrival (``drain_s``), and the median latency of the last
quarter of the requests over that of the first (``growth``; a growing
backlog makes it large).  After the seeds of a rate it prints the medians
of these over the seeds.  The knee is read from the medians and written
into a traffic mix as a number; the benchmark never searches for a
rate.  The mix's own rate is not used.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import run  # noqa: E402


def window_line(recs, rate: float, seed: int, wall_s: float, serve) -> dict:
    from bench.traffic.common import percentile

    lat = [r.latency_s for r in recs]
    q = max(len(recs) // 4, 1)
    first = percentile(lat[:q], 0.5)
    last = percentile(lat[-q:], 0.5)
    return {
        "rate_qps": rate, "seed": seed, "requests": len(recs),
        "failed": sum(r.error is not None for r in recs),
        "p50_ms": percentile(lat, 0.5) * 1e3,
        "p95_ms": percentile(lat, 0.95) * 1e3,
        "first_quarter_p50_ms": first * 1e3,
        "last_quarter_p50_ms": last * 1e3,
        "growth": last / first,
        "drain_s": max(r.done for r in recs) - max(r.due for r in recs),
        "wall_s": wall_s,
        "by_query_p95_ms": {
            n: percentile([r.latency_s for r in recs if r.name == n],
                          0.95) * 1e3
            for n in sorted({r.name for r in recs})},
        "serve": serve}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)

    cell, config = run.resolve({"config": args.config,
                                "traffic": args.traffic})
    sys.path.insert(1, os.path.join(run.ROOT, "src"))
    try:
        devices = run.check_device(config["chips"])
    except run.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 1
    run.enable_compile_cache()

    driver, traffic = run.setup_cell(cell, config, args.seeds[0], devices)
    run.say(f"setup {time.perf_counter() - T_START:.1f}s")
    for rate in sorted(args.rates):
        traffic.mix = dict(traffic.mix, rate_qps=rate)
        lines = []
        for seed in args.seeds:
            traffic.seed, traffic.windows = seed, 0
            t0 = time.perf_counter()
            recs = traffic.run(args.seconds)
            lines.append(window_line(recs, rate, seed,
                                     time.perf_counter() - t0,
                                     traffic.stats()))
            run.say(json.dumps(lines[-1]))
        med = {k: statistics.median(x[k] for x in lines)
               for k in ("p50_ms", "p95_ms", "growth", "drain_s")}
        lanes = sum(x["serve"]["lanes"] for x in lines) / max(
            sum(x["serve"]["lane_batches"] for x in lines), 1)
        run.say("median " + json.dumps({"rate_qps": rate, **med,
                                        "mean_lanes": lanes}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
