"""What every traffic driver shares: TPC-H §2.4 parameter draws, the
request record, and the exact percentile.

``draw`` is a copy of ``repro.tpch.queries.random_binding`` and
``percentile`` of ``repro.serve.workload.percentile``, kept here so that a
change to the program cannot change the traffic or the statistic.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

from bench.reference.tpch_data import day

Q1_CUT = day(1998, 12, 1)


def draw(name: str, rng) -> dict:
    """One random §2.4 substitution draw for a parameterized query.
    Discount bounds fall on midpoints of the 0.01 grid, so float32 plans
    and the float64 reference never disagree on a boundary row."""
    if name == "q1":
        return {"q1_shipdate_max": Q1_CUT - int(rng.integers(60, 121))}
    if name == "q6":
        y = int(rng.integers(1993, 1998))
        disc = int(rng.integers(2, 10)) / 100.0
        return {"q6_date_min": day(y, 1, 1), "q6_date_max": day(y + 1, 1, 1),
                "q6_disc_min": disc - 0.015, "q6_disc_max": disc + 0.015,
                "q6_quantity": float(rng.integers(24, 26))}
    if name == "q14_promo":
        y, m = int(rng.integers(1993, 1998)), int(rng.integers(1, 13))
        nxt = (y + 1, 1) if m == 12 else (y, m + 1)
        return {"q14_date_min": day(y, m, 1),
                "q14_date_max": day(nxt[0], nxt[1], 1)}
    raise KeyError(name)


@dataclasses.dataclass
class Record:
    """One request of the window: what was asked, when it was due, when it
    was sent and answered (host ``perf_counter`` seconds), and the answer
    (``None`` with ``error`` set when none came)."""

    name: str
    binding: Optional[dict]
    due: float
    sent: float = math.nan
    done: float = math.nan
    value: object = None
    overflow: object = False
    tier: int = 0
    error: Optional[str] = None
    ok: bool = False           # answered, and the answer passed the check

    @property
    def latency_s(self) -> float:
        """From due time to answer; a request never answered has none."""
        return self.done - self.due if self.error is None else math.inf


def percentile(xs, q: float) -> float:
    """Exact order-statistic percentile."""
    if not xs:
        return math.nan
    xs = sorted(xs)
    return xs[min(len(xs) - 1, int(q * len(xs)))]


def latency_ms(records, q: float) -> float:
    """Exact ``q``-percentile of the records' latencies, in ms."""
    return percentile([r.latency_s for r in records], q) * 1e3
