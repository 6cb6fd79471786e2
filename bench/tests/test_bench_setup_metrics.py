"""The set-up readers: the program's step timers as the harness hands
them over (``load_seconds``, copied after warm-up, and ``setup_s``), and
nothing where the program does not time the step."""
from __future__ import annotations

import types

import pytest

from bench.run import load_reader

STEPS = {"generate": 27.5, "pack": 73.25, "place": 2.0, "catalog": 1.5,
         "compile": 9.0}


def _run(load_seconds, setup_s=125.0):
    return types.SimpleNamespace(load_seconds=load_seconds, setup_s=setup_s)


@pytest.mark.parametrize("metric,load_seconds,want", [
    ("load_place_s", STEPS, 2.0),
    ("load_place_s", {}, None),
    ("setup_compile_s", STEPS, 9.0),
    # a program without the compile timer (as before it was added)
    ("setup_compile_s", {k: v for k, v in STEPS.items() if k != "compile"},
     None),
    ("setup_unattributed_s", STEPS, 125.0 - 113.25),
    # every timed step is subtracted, cube builds too
    ("setup_unattributed_s", {**STEPS, "cubes": 5.0}, 125.0 - 118.25),
    ("setup_unattributed_s", {k: v for k, v in STEPS.items()
                              if k != "catalog"}, None),
    ("setup_unattributed_s", {}, None),
])
def test_setup_readers(metric, load_seconds, want):
    got = load_reader(metric)(_run(dict(load_seconds)))
    assert got == (None if want is None else pytest.approx(want))
