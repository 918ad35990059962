"""Tests of the benchmark harness, on the CPU at small sizes:

    python -m pytest bench/tests
"""

import copy
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import pytest  # noqa: E402

from lib import harness  # noqa: E402

CELLS = ("dragonfly_1024.sweep48", "sdsc_sp2_128.run8k",
         "sdsc_sp2_128.whatif")
# A cell whose entry, mix and readers stay under bench/ while it is out of
# BENCHMARK.json (the replay compiles inside its own call): tested here, so
# that it comes back as entries in BENCHMARK.json alone.
KEPT = {"sdsc_sp2_128.replay73k": {
    "name": "sdsc_sp2_128.replay73k", "config": "sdsc_sp2_128",
    "traffic": "replay73k", "chips": 1}}
KEPT_METRICS = ("engine_us_per_event.replay", "replay_rounds",
                "device_idle_share.replay")


def small(cell_name: str):
    """``(cell, config, traffic)`` of a cell, cut to a size a test holds."""
    bench = harness.load_benchmark()
    cell = KEPT.get(cell_name) or harness.find(bench["workloads"], cell_name,
                                               "workload")
    cfg = copy.deepcopy(harness.load_json("configs", cell["config"]))
    trf = copy.deepcopy(harness.load_json("traffic", cell["traffic"]))
    if cfg["machine"].get("topology"):
        cfg["machine"] = {"nodes": 64,
                          "topology": {"kind": "dragonfly", "shape": [4, 16]}}
        cfg["workload"].update(total_nodes=64, node_pow2_max=6)
        cfg["failures"].update(mtbf=2e5, horizon=1 << 20)
        trf.update(jobs=48, backlogs=2)
        trf["axes"]["failures.mtbf"] = [2e5, 4e5]
    elif trf["entry"] == "run":
        trf["jobs"] = 300
    elif trf["entry"] == "replay":
        trf.update(jobs=1200, window=256)
    elif trf["entry"] == "whatif":
        trf["queue_jobs"] = 200
    return bench, cell, cfg, trf


@pytest.fixture
def workdir(tmp_path):
    return str(tmp_path / "work")
