import os

import pytest

from conftest import CELLS, KEPT, KEPT_METRICS
from lib import harness


def test_every_cell_finds_its_files():
    bench = harness.load_benchmark()
    assert [w["name"] for w in bench["workloads"]] == list(CELLS)
    for w in bench["workloads"]:
        cfg = harness.load_json("configs", w["config"])
        assert cfg["name"] == w["config"]
        trf = harness.load_json("traffic", w["traffic"])
        assert callable(harness.entry_class(trf["entry"]))
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))


def test_every_per_layer_metric_has_a_reader():
    bench = harness.load_benchmark()
    for m in bench["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for name in KEPT_METRICS:
        assert callable(harness.metric_reader(name))


def test_kept_cells_find_their_files_outside_the_benchmark():
    bench = harness.load_benchmark()
    for name, cell in KEPT.items():
        assert name not in {w["name"] for w in bench["workloads"]}
        trf = harness.load_json("traffic", cell["traffic"])
        assert callable(harness.entry_class(trf["entry"]))


class _View:
    trace = {"module_s": {"jit__simulate_jit": 0.5, "jit_step": 0.2},
             "busy_s": 3.0, "window_s": 4.0}
    traced = {"engine_events": 1e4}


@pytest.mark.parametrize("name,value", [
    ("engine_us_per_event.run", 50.0), ("engine_us_per_event.replay", 20.0),
    ("device_idle_share.sweep", 0.25), ("device_idle_share.query", 0.25)])
def test_a_variant_without_a_file_is_read_by_its_stems_reader(name, value):
    assert not os.path.isfile(os.path.join(harness.BENCH_DIR, "metrics",
                                           f"{name}.py"))
    assert harness.metric_reader(name)(_View()) == pytest.approx(value)


def test_a_missing_stem_is_refused():
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric.variant")


def test_unknown_names_are_refused():
    bench = harness.load_benchmark()
    with pytest.raises(harness.BenchError):
        harness.find(bench["workloads"], "no_such.cell", "workload")
    with pytest.raises(harness.BenchError):
        harness.load_json("traffic", "no_such_mix")
    with pytest.raises(harness.BenchError):
        harness.metric_reader("no_such_metric")


def test_cell_metrics_follow_the_workloads_lists():
    bench = harness.load_benchmark()
    e2e = {c: {m["name"] for m in harness.cell_metrics(bench, c, "end_to_end")}
           for c in CELLS}
    assert e2e["dragonfly_1024.sweep48"] == {"sweep_jobs_per_s", "setup_s"}
    assert e2e["sdsc_sp2_128.run8k"] == {"jobs_per_s", "setup_s"}
    assert e2e["sdsc_sp2_128.whatif"] == {"query_s_p90", "setup_s"}
    for c in CELLS:
        layer = harness.cell_metrics(bench, c, "per_layer")
        assert layer, c
        for m in layer:
            assert m["moves"] in e2e[c], (c, m["name"])


def test_a_metric_without_a_list_goes_to_every_cell_of_its_metric():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "b"}],
             "per_layer": [{"name": "m", "moves": "a"},
                           {"name": "n", "moves": "b"}]}
    assert [m["name"] for m in harness.cell_metrics(bench, "x", "per_layer")
            ] == ["m", "n"]
    assert [m["name"] for m in harness.cell_metrics(bench, "y", "per_layer")
            ] == ["n"]
