import json
import os

import pytest

from conftest import CELLS, KEPT, KEPT_METRICS, small
from lib import harness


def _cells_find_their_files(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    for w in bench["workloads"]:
        cfg = harness.load_json("configs", w["config"])
        assert cfg["name"] == w["config"]
        trf = harness.load_json("traffic", w["traffic"])
        entry = harness.entry_class(trf["entry"])
        assert w["chips"] == 1 or entry.SHARDS, w["name"]
    for c in bench["configs"]:
        assert os.path.isfile(os.path.join(harness.ROOT, c["file"]))


def test_every_cell_finds_its_files():
    bench = harness.load_benchmark()
    assert [w["name"] for w in bench["workloads"]] == list(CELLS)
    _cells_find_their_files(bench)


@pytest.mark.parametrize("cell_name", CELLS + tuple(KEPT))
def test_every_entry_cuts_its_cell_for_the_cpu(cell_name):
    """Each cell's entry has its own ``small``: copies, never the files'
    own dicts, and a smaller cell."""
    bench = harness.load_benchmark()
    cell = KEPT.get(cell_name) or harness.find(bench["workloads"], cell_name,
                                               "workload")
    cfg = harness.load_json("configs", cell["config"])
    trf = harness.load_json("traffic", cell["traffic"])
    entry = harness.entry_class(trf["entry"])
    assert "small" in vars(entry), f"{entry.__name__} has no small()"
    _, _, small_cfg, small_trf = small(cell_name)
    assert small_cfg is not cfg and small_trf is not trf
    assert small_cfg["machine"]["nodes"] <= cfg["machine"]["nodes"]
    assert small_trf != trf
    assert (harness.load_json("configs", cell["config"]), harness.load_json(
        "traffic", cell["traffic"])) == (cfg, trf)


def test_a_mix_kept_under_a_second_name_stays_the_same():
    """A cell that reruns another cell's mix on other chips keeps a traffic
    file of its own (a configuration and traffic pair names one cell),
    marked ``same_mix_as``; it may not drift from the file it copies."""
    folder = os.path.join(harness.BENCH_DIR, "traffic")
    for fname in sorted(os.listdir(folder)):
        trf = harness.load_json("traffic", fname.rpartition(".")[0])
        if "same_mix_as" in trf:
            other = harness.load_json("traffic", trf.pop("same_mix_as"))
            assert trf == other, fname


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
    for m in bench["end_to_end"]:
        assert set(m.get("workloads", CELLS)) <= set(CELLS), m["name"]
    for c in CELLS:
        want = {m["name"] for m in bench["end_to_end"]
                if c in m.get("workloads", [c])}
        assert e2e[c] == want
        assert "setup_s" in e2e[c] and len(e2e[c]) >= 2, c
        layer = harness.cell_metrics(bench, c, "per_layer")
        assert layer, c
        for m in layer:
            assert m["moves"] in e2e[c], (c, m["name"])


def _bench_with_a_new_cell(tmp_path, name):
    """A copy of ``BENCHMARK.json`` with one more cell on ``run8k``'s files,
    listed where ``run8k`` is."""
    bench = harness.load_benchmark()
    bench["workloads"].append({"name": name, "config": "sdsc_sp2_128",
                               "traffic": "run8k", "chips": 1,
                               "why": "a cell added as data"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "sdsc_sp2_128.run8k" in m.get("workloads", ()):
            m["workloads"].append(name)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return harness.load_benchmark(str(tmp_path))


def test_a_new_cell_is_entries_alone(tmp_path, workdir):
    """A copy of ``BENCHMARK.json`` with one more cell, on files that are
    there, and the cell listed under metrics that are there: the registry,
    the cut and a whole run take it with no other edit."""
    name = "sdsc_sp2_128.run8k.again"
    bench = _bench_with_a_new_cell(tmp_path, name)
    _cells_find_their_files(bench)
    assert {m["name"] for m in harness.cell_metrics(
        bench, name, "per_layer")} == {"engine_us_per_event.run",
                                       "device_idle_share.jobs"}
    _, cell, cfg, trf = small(name, bench)
    assert cell["name"] == name and trf["jobs"] == 300
    res = harness.run_cell(bench, cell, seed=2**32 + 5, seconds=1.0,
                           trace=False, t_start=0.0,
                           device=harness.device_info(1), workdir=workdir,
                           config=cfg, traffic=trf)
    assert res["correct"] is True, res["limits"]
    assert set(res["metrics"]) == {"jobs_per_s", "setup_s"}


def test_a_cell_of_several_chips_needs_an_entry_that_shards(tmp_path,
                                                            workdir):
    """A cell of two chips on an entry that runs on one chip is refused by
    name before anything runs, not run on its first chip alone."""
    name = "sdsc_sp2_128.run8k.x2"
    bench = _bench_with_a_new_cell(tmp_path, name)
    cell = harness.find(bench["workloads"], name, "workload")
    cell["chips"] = 2
    with pytest.raises(AssertionError, match=name):
        _cells_find_their_files(bench)
    _, _, cfg, trf = small(name, bench)
    with pytest.raises(harness.BenchError, match=name):
        harness.run_cell(bench, cell, seed=3, seconds=1.0, trace=False,
                         t_start=0.0, device=harness.device_info(2),
                         workdir=workdir, config=cfg, traffic=trf)
    assert not os.path.exists(workdir)


def test_a_metric_without_a_list_goes_to_every_cell_of_its_metric():
    bench = {"end_to_end": [{"name": "a", "workloads": ["x"]},
                            {"name": "b"}],
             "per_layer": [{"name": "m", "moves": "a"},
                           {"name": "n", "moves": "b"}]}
    assert [m["name"] for m in harness.cell_metrics(bench, "x", "per_layer")
            ] == ["m", "n"]
    assert [m["name"] for m in harness.cell_metrics(bench, "y", "per_layer")
            ] == ["n"]
