"""Shared test fixtures.

The distribution tests need a multi-device mesh to exercise the collective
schedules, so we ask XLA for 8 host platform devices BEFORE jax initializes.
This is deliberately 8 (a small cluster, fast compiles) and NOT the 512-way
production mesh — the 512-device placeholder config is reserved for
``launch/dryrun.py`` per the project brief.  Arch smoke tests ignore the
extra devices (their arrays live on device 0).
"""
from __future__ import annotations

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import pytest  # noqa: E402

from repro.tpch.reference import assert_topk_matches  # noqa: E402,F401


@pytest.fixture(scope="session")
def cluster():
    from repro.core import Cluster

    return Cluster()


@pytest.fixture(scope="session")
def tpch_driver(cluster):
    """Small deterministic TPC-H instance shared by correctness tests."""
    from repro.tpch.driver import TPCHDriver

    return TPCHDriver(sf=0.01, cluster=cluster, seed=0)


@pytest.fixture(scope="session")
def tpch_driver_seed1(cluster):
    from repro.tpch.driver import TPCHDriver

    return TPCHDriver(sf=0.02, cluster=cluster, seed=1)
