"""Closed loop: one analyst running reports back to back (the TPC-H power
test's single stream), through ``prepare(q).execute(binding)``.

Parameters (the traffic mix):

- ``queries``: the names of one round; each round runs them in an order
  drawn from the seed;
- ``param``: the names among them that get a fresh §2.4 draw per request
  (the program's ``PARAM_QUERIES`` forms); the others run their registry
  IR as it stands.

A request is due when the previous one finished, so its latency is its
service time.  The window stops sending at ``seconds``; the request in
flight then is waited for and counted among the attempted.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from bench.traffic.common import Record, draw


def requests(mix: dict, seed: int):
    """The endless request stream of a seed: ``(name, binding)`` pairs."""
    rng = np.random.default_rng([seed, 1])
    queries, param = list(mix["queries"]), set(mix.get("param", ()))
    while True:
        for i in rng.permutation(len(queries)):
            q = queries[int(i)]
            yield q, (draw(q, rng) if q in param else None)


class Traffic:
    def __init__(self, driver, mix: dict, config: dict, seed: int):
        from repro.tpch import queries as tq

        param = set(mix.get("param", ()))
        self.stream = requests(mix, seed)
        self.prepared = {
            q: driver.prepare(tq.PARAM_QUERIES[q]() if q in param else q)
            for q in mix["queries"]}
        self.param = param

    def warm(self) -> None:
        """Execute each shape once: every program the window runs."""
        rng = np.random.default_rng(0)
        for q, prep in self.prepared.items():
            with TraceAnnotation("bench.warmup", query=q):
                prep.execute(draw(q, rng) if q in self.param else None)

    def run(self, seconds: float) -> list:
        out = []
        t_end = time.perf_counter() + seconds
        while time.perf_counter() < t_end:
            q, b = next(self.stream)
            r = Record(q, b, due=time.perf_counter())
            r.sent = r.due
            try:
                with TraceAnnotation("bench.execute", query=q):
                    ans = self.prepared[q].execute(b)
            except Exception as e:  # a failed request is counted, not raised
                r.error = f"{type(e).__name__}: {e}"
            else:
                r.value, r.overflow, r.tier = ans.value, ans.overflow, ans.tier
            r.done = time.perf_counter()
            out.append(r)
        return out

    def stats(self) -> dict:
        return {}

    def close(self) -> None:
        self.prepared.clear()
