"""The comparison that decides ``correct``: served answers against the plain
reference.

Each checked answer gives three numbers, and a run reports the worst of
each over every answer it checked:

- ``agg_gap``: the widest relative gap of an aggregate,
  ``|got - ref| / max(|ref|, 1)``, over every value of the answer (group
  sums and counts, the top-k values);
- ``topk_miss``: rows of a top-k answer whose key differs from the
  reference's at the same rank, plus the difference in valid rows.  The
  engine ranks the same float32 values by (value desc, key asc), so this
  is exact;
- ``overflow``: answers whose exchange-overflow flag is set.

An answer passes when each of its numbers is within the configuration's
limit; the run is correct when every request was answered and passed.
"""
from __future__ import annotations

import numpy as np

NUMBERS = ("agg_gap", "topk_miss", "overflow")


def _rel_gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.size != want.size:
        return float("inf")
    got = got.reshape(want.shape)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1.0),
                        initial=0.0))


def _topk(value, want) -> tuple:
    ref_v, ref_k = want
    valid = np.asarray(value["valid"], bool)
    keys = np.asarray(value["keys"], np.int64)
    vals = np.asarray(value["values"], np.float64)
    n, n_ref = int(valid.sum()), int(np.isfinite(ref_v).sum())
    m = min(n, n_ref, len(keys))
    miss = abs(n - n_ref) + int(np.sum(keys[:m] != ref_k[:m]))
    return _rel_gap(vals[:m], ref_v[:m]), miss


def check_answer(value, overflow, want) -> dict:
    """The three numbers of one answer against its reference ``want``
    (an array, or a ``(values, keys)`` top-k pair)."""
    if isinstance(want, tuple):
        if not isinstance(value, dict) or not {"values", "keys",
                                               "valid"} <= set(value):
            return {"agg_gap": float("inf"), "topk_miss": len(want[1]),
                    "overflow": int(bool(np.any(overflow)))}
        gap, miss = _topk(value, want)
    else:
        gap, miss = _rel_gap(value, want), 0
    return {"agg_gap": gap, "topk_miss": miss,
            "overflow": int(bool(np.any(overflow)))}


def passes(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in NUMBERS)


def worst(per_answer) -> dict:
    """The run's reading of each number: the worst over its answers."""
    out = {"agg_gap": 0.0, "topk_miss": 0, "overflow": 0}
    for nums in per_answer:
        out["agg_gap"] = max(out["agg_gap"], nums["agg_gap"])
        out["topk_miss"] += nums["topk_miss"]
        out["overflow"] += nums["overflow"]
    return out
