#!/usr/bin/env python3
"""The control of the output check: the plain reference put in the
program's place, computed in the next precision below the configuration's.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]

The configurations state float32 values and aggregates.  The control
rounds every float input column (prices, discounts, taxes, quantities,
order totals) through bfloat16, as a contraction at the TPU's default
precision would see them, and answers the requests a run of the cell
sends for that seed.  Those answers go through the same comparison as the
program's; the check must find them not correct.  It prints one JSON line
per seed with the readings and whether each passed the limits.  It needs
no chip: the benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

import numpy as np  # noqa: E402

from bench import run  # noqa: E402
from bench.reference import compare, tpch_data  # noqa: E402
from bench.reference.tpch import Reference  # noqa: E402

CLOSED_LOOP_REQUESTS = 150  # about what one window of the power cell sends


def window_requests(cell: dict, seed: int, seconds: float) -> list:
    """``(name, binding)`` of the requests a run of the cell sends."""
    mix = cell["mix"]
    if mix["driver"] == "closed_loop":
        from bench.traffic.closed_loop import requests

        return list(itertools.islice(requests(mix, seed),
                                     CLOSED_LOOP_REQUESTS))
    from bench.traffic.open_loop import requests

    return [(name, b) for _, name, b in requests(mix, seed, seconds)]


def as_answer(want):
    """A reference answer in the shape the program returns it."""
    if isinstance(want, tuple):
        values, keys = want
        return {"values": values, "keys": keys, "valid": np.isfinite(values)}
    return want


def readings(tables, reqs, limits, round_inputs) -> dict:
    ref, ctl = Reference(tables), Reference(tables, round_inputs)
    nums = [compare.check_answer(as_answer(ctl.answer(n, b)), False,
                                 ref.answer(n, b)) for n, b in reqs]
    out = compare.worst(nums)
    out["passed"] = all(compare.passes(x, limits) for x in nums)
    return out


def main(argv=None) -> int:
    import ml_dtypes

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float,
                    default=run.load_json(run.ROOT, "BENCHMARK.json")
                    ["run_seconds"])
    args = ap.parse_args(argv)
    cell, config = run.load_cell(args.workload)
    for seed in args.seeds:
        tables = tpch_data.generate(config["scale_factor"], config["nodes"],
                                    seed)
        reqs = window_requests(cell, seed, args.seconds)
        r = readings(tables, reqs, config["limits"], ml_dtypes.bfloat16)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "requests": len(reqs), **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
