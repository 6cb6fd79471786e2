"""Open loop: many independent dashboard users, through the serving engine
(``OLAPEngine.submit``), at a rate fixed in the traffic mix.

Parameters (the traffic mix):

- ``rate_qps``: requests per second offered;
- ``mix``: share of each request kind: ``param`` (the ``param_queries``
  in equal numbers, each with a fresh §2.4 draw), ``tier1`` (the
  cube-covered ``tier1_queries`` on their defaults, in equal numbers) and
  ``tier2`` (the ``tier2_queries``, which miss every cube);
- ``lane_buckets``: the padded batch sizes the engine dispatches, each
  warmed before the window.

Every seed sends the same work: ``rate_qps * seconds`` requests with the
same count of each kind, spread evenly through the window, and the same
inter-arrival gaps.  The seed only orders them within blocks of
``BLOCK`` requests: each block holds about the mix's shares of kinds and
the quantiles of the exponential distribution as its gaps, in an order
drawn from the seed.  So arrivals are Poisson-like within a block, but
no seed can pile the slow kinds into one stretch of the window, and what
a window's latencies and completions read does not hang on the seed.  A
request is timed from when it was due, so a late generator or a stall
shows in the latency of every request behind it.
"""
from __future__ import annotations

import asyncio
import math
import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.traffic.common import Record, draw

ANSWER_WAIT_S = 60.0  # how long past the window an answer is waited for
BLOCK = 10  # requests whose order and gaps a seed draws together


def _counts(n: int, shares: dict) -> dict:
    """Largest-remainder split of ``n`` requests over ``shares``."""
    total = sum(shares.values())
    exact = {k: n * v / total for k, v in shares.items()}
    out = {k: int(math.floor(x)) for k, x in exact.items()}
    short = n - sum(out.values())
    for k in sorted(exact, key=lambda k: out[k] - exact[k])[:short]:
        out[k] += 1
    return out


def requests(mix: dict, seed: int, seconds: float) -> list:
    """The window's requests as ``(due offset s, name, binding)``."""
    rng = np.random.default_rng([seed, 2])
    n = max(1, round(mix["rate_qps"] * seconds))
    kinds = _counts(n, mix["mix"])
    spaced = []  # (evenly spread position, name) of every request
    for kind, pool in (("param", mix["param_queries"]),
                       ("tier1", mix["tier1_queries"]),
                       ("tier2", mix["tier2_queries"])):
        count = kinds.get(kind, 0)
        spaced += [((i + 0.5) / count, pool[i % len(pool)])
                   for i in range(count)]
    names = [nm for _, nm in sorted(spaced)]
    order, gaps = [], []
    for b0 in range(0, n, BLOCK):
        size = min(BLOCK, n - b0)
        order += [b0 + int(i) for i in rng.permutation(size)]
        q = (np.arange(size) + 0.5) / size
        gaps.append(rng.permutation(-np.log1p(-q)))
    names = [names[i] for i in order]
    gaps = np.concatenate(gaps)
    due = seconds * (np.cumsum(gaps) - gaps) / gaps.sum()
    param = set(mix["param_queries"])
    return [(float(t), nm, draw(nm, rng) if nm in param else None)
            for t, nm in zip(due, names)]


def _query(tq, name: str):
    """The program's query object behind a request name."""
    if name in tq.PARAM_QUERIES:
        return tq.PARAM_QUERIES[name]()
    if name in tq.SERVING_QUERIES:
        return tq.SERVING_QUERIES[name]()
    if name == "q1_offedge":
        return tq.uncovered_query()
    raise KeyError(name)


class Traffic:
    def __init__(self, driver, mix: dict, config: dict, seed: int):
        from repro.tpch import queries as tq

        self.driver = driver
        self.mix = mix
        self.seed = seed
        self.engine_args = dict(config["engine"])
        names = (mix["param_queries"] + mix["tier1_queries"]
                 + mix["tier2_queries"])
        self.prepared = {n: driver.prepare(_query(tq, n)) for n in names}
        for n in mix["tier1_queries"]:
            prep = self.prepared[n]
            if prep.answer_tier1(prep.binding()) is None:
                raise RuntimeError(f"{n} is not cube-covered on its "
                                   f"defaults: were the cubes built?")
        self.stats0 = self._serve_counts()  # taken again as a window opens
        self.windows = 0

    def warm(self) -> None:
        """Every program the window can dispatch: each tier-2 shape once as
        a scalar plan and once per lane bucket."""
        rng = np.random.default_rng(0)
        for name, prep in self.prepared.items():
            b = prep.binding(draw(name, rng)
                             if name in self.mix["param_queries"] else None)
            if prep.answer_tier1(b) is not None:
                continue
            with TraceAnnotation("bench.warmup", query=name):
                prep.execute(b)
                if prep.params:
                    for lanes in self.mix["lane_buckets"]:
                        prep.execute_batch([b] * lanes)

    def run(self, seconds: float) -> list:
        # a second window of one process (the knee sweep) draws anew
        reqs = requests(self.mix, self.seed + self.windows, seconds)
        self.windows += 1
        self.stats0 = self._serve_counts()
        return asyncio.run(self._run(reqs))

    async def _run(self, reqs) -> list:
        from repro.serve.olap_engine import OLAPEngine

        records = []
        async with OLAPEngine(self.driver, **self.engine_args) as engine:
            t0 = time.perf_counter()
            tasks = []
            for offset, name, b in reqs:
                r = Record(name, b, due=t0 + offset)
                delay = r.due - time.perf_counter()
                if delay > 0:
                    await asyncio.sleep(delay)
                tasks.append(asyncio.ensure_future(
                    self._submit(engine, self.prepared[name], r)))
                records.append(r)
            deadline = t0 + reqs[-1][0] + ANSWER_WAIT_S
            _, pending = await asyncio.wait(
                tasks, timeout=max(deadline - time.perf_counter(), 0.0))
            for t in pending:
                t.cancel()
            await asyncio.gather(*pending, return_exceptions=True)
            for r in records:
                if r.value is None and r.error is None:
                    r.error = "no answer within the wait after the window"
        return records

    @staticmethod
    async def _submit(engine, prep, r: Record) -> None:
        r.sent = time.perf_counter()
        try:
            with TraceAnnotation("bench.submit", query=r.name):
                ans = await engine.submit(prep, r.binding)
        except Exception as e:  # a refused or failed request is counted
            r.error = f"{type(e).__name__}: {e}"
        else:
            r.value, r.overflow, r.tier = ans.value, ans.overflow, ans.tier
        r.done = time.perf_counter()

    def _serve_counts(self) -> dict:
        m = self.driver.obs.metrics
        h = m.get("serve.batch_size")
        return {"batches": m.value("serve.batches"),
                "coalesced_lanes": m.value("serve.coalesced_lanes"),
                "lanes": h.total if h is not None else 0.0,
                "lane_batches": h.count if h is not None else 0}

    def stats(self) -> dict:
        """Serving counters over the last window (their change in it)."""
        now = self._serve_counts()
        return {k: now[k] - self.stats0[k] for k in now}

    def close(self) -> None:
        self.prepared.clear()
        self.driver = None
