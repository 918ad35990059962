"""Tests of the benchmark harness, on the CPU at small sizes:

    python -m pytest bench/tests
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

# every cell of BENCHMARK.json: a cell is data, and its tests follow it
CELLS = tuple(w["name"] for w in harness.load_benchmark()["workloads"])
# Cells whose entry, mix and readers stay under bench/ while they are out of
# BENCHMARK.json: tested here, so that each comes back as entries in
# BENCHMARK.json alone.  The replay compiles inside its own call; the
# machine-mode run's rate swings with a 1-2 s wait for the device that about
# one run in ten holds.
KEPT = {"sdsc_sp2_128.replay73k": {
    "name": "sdsc_sp2_128.replay73k", "config": "sdsc_sp2_128",
    "traffic": "replay73k", "chips": 1},
    "dragonfly_1024.run4k": {
    "name": "dragonfly_1024.run4k", "config": "dragonfly_1024",
    "traffic": "run4k", "chips": 1}}
KEPT_METRICS = ("engine_us_per_event.replay", "replay_rounds",
                "device_idle_share.replay")


def small(cell_name: str, bench=None):
    """``(bench, cell, config, traffic)`` of a cell of ``bench`` (or of
    ``KEPT``), config and traffic cut by the cell's entry to a size a test
    holds."""
    bench = bench or harness.load_benchmark()
    cell = KEPT.get(cell_name) or harness.find(bench["workloads"], cell_name,
                                               "workload")
    cfg = harness.load_json("configs", cell["config"])
    trf = harness.load_json("traffic", cell["traffic"])
    cfg, trf = harness.entry_class(trf["entry"]).small(cfg, trf)
    return bench, cell, cfg, trf


@pytest.fixture
def workdir(tmp_path):
    return str(tmp_path / "work")
