"""Shared set-up of the benchmark's own tests: the harness modules on the
path, and a tiny cell that the CPU runs in seconds."""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def load(kind, name):
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.fixture
def tiny_deploy():
    """vpic_bdcats at 8 clients x 8 OSTs (64 interfaces), 3 s jobs: six
    intervals, DIAL deciding in the last three."""
    cfg = dict(load("configs", "vpic_bdcats_256x16"), n_clients=8, n_osts=8)
    traffic = dict(load("traffic", "dial"), sim_seconds=3.0, check_jobs=1)
    return cfg, traffic


@pytest.fixture
def tiny_sweep():
    """The sweep over three of the catalog's scenarios, 3 s jobs."""
    cfg = dict(load("configs", "catalog"),
               scenarios=["vpic_checkpoint", "failover_ost", "hetero_links"])
    traffic = dict(load("traffic", "sweep"), sim_seconds=3.0)
    return cfg, traffic


@pytest.fixture
def paths():
    """``(bench_dir, repo_root)``."""
    return BENCH, ROOT
