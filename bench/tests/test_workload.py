"""The benchmark's own input generators against the program's, and the
properties the configurations state of them."""

import json
import os

import numpy as np
import pytest

from lib import harness, workload


def _config(name):
    return harness.load_json("configs", name)


def test_synthetic_trace_is_the_programs_generator():
    from repro.traces import synthetic_trace

    kw = dict(mean_interarrival=1323.0, runtime_lognorm=(6.2, 1.9),
              max_runtime=64800, node_pow2_max=7, large_frac=0.06,
              total_nodes=128, estimate_factor=(1.2, 5.0), burstiness=0.4)
    ours = workload.synthetic_trace(3000, seed=5, **kw)
    theirs = synthetic_trace(3000, seed=5, **kw)
    for k in ("submit", "runtime", "nodes", "estimate"):
        np.testing.assert_array_equal(ours[k], theirs[k])


def test_failure_stream_is_the_programs_materialization():
    from repro.reliability import FailureModel
    from repro.reliability.model import merge_stream

    model = FailureModel(mtbf=2e5, seed=3, horizon=1 << 20,
                         max_failures=256, mean_repair=60)
    t, n, k = merge_stream(model.materialize(64))
    ours = workload.failure_stream(mtbf=2e5, seed=3, n_nodes=64,
                                   horizon=1 << 20, max_failures=256,
                                   mean_repair=60)
    np.testing.assert_array_equal(ours["time"], t)
    np.testing.assert_array_equal(ours["node"], n)
    np.testing.assert_array_equal(ours["kind"], k)


@pytest.mark.parametrize("name,n_jobs", [("sdsc_sp2_128", 1024),
                                         ("dragonfly_1024", 512)])
def test_every_seed_gets_the_same_gaps_and_jobs(name, n_jobs):
    cfg = _config(name)
    a = workload.config_trace(cfg, n_jobs, 1)
    b = workload.config_trace(cfg, n_jobs, 2**40 + 3)
    assert not np.array_equal(a["submit"], b["submit"])
    np.testing.assert_array_equal(np.sort(np.diff(a["submit"], prepend=0)),
                                  np.sort(np.diff(b["submit"], prepend=0)))
    rows = lambda t: sorted(zip(t["runtime"], t["nodes"], t["estimate"]))
    assert rows(a) == rows(b)
    assert (a["estimate"] >= a["runtime"]).all()


@pytest.mark.parametrize("name,n_jobs,nodes,load", [
    ("sdsc_sp2_128", 1024, 128, 0.810), ("sdsc_sp2_128", 8192, 128, 0.811),
    ("sdsc_sp2_128", 73496, 128, 0.811), ("dragonfly_1024", 512, 1024, 0.810)])
def test_offered_load_is_what_the_configuration_states(name, n_jobs, nodes,
                                                       load):
    t = workload.config_trace(_config(name), n_jobs, 7)
    assert workload.offered_load(t, nodes) == pytest.approx(load, rel=0.01)


def test_dragonfly_groups_are_the_machines():
    cfg = _config("dragonfly_1024")
    kind, shape = cfg["machine"]["topology"].values()
    assert kind == "dragonfly" and shape[0] == cfg["groups"]
    assert shape[0] * shape[1] == cfg["machine"]["nodes"]


def test_question_seeds_are_distinct_and_take_wide_seeds():
    seeds = {workload.question_seed(2**33 + 1, q) for q in range(1000)}
    assert len(seeds) == 1000
    assert all(0 <= s < 2**63 for s in seeds)


def test_swf_text_round_trips_through_the_programs_loader(tmp_path):
    from repro.traces import load_swf

    t = workload.config_trace(_config("sdsc_sp2_128"), 200, 9)
    t["submit"] = t["submit"] - t["submit"].min()
    path = os.path.join(tmp_path, "q.swf")
    with open(path, "w") as f:
        f.write(workload.swf_lines(t))
    back, report = load_swf(path)
    assert report.n_jobs == 200
    for k in ("submit", "runtime", "nodes", "estimate"):
        np.testing.assert_array_equal(back[k], t[k])
    json.dumps({k: v.tolist() for k, v in back.items()})


def test_sweep_questions_repeat_their_backlogs_with_their_failures():
    """The seed picks where a run starts among the fixed backlogs; a
    backlog and its failure stream are the same in every run."""
    from lib.entries.sweep import SweepEntry

    cfg = _config("dragonfly_1024")
    trf = harness.load_json("traffic", "sweep48")
    k = trf["backlogs"]
    a = SweepEntry(None, cfg, trf, 5, "")
    b = SweepEntry(None, cfg, trf, 2**40 + 6, "")
    for q in range(k):
        (ta, fa), (tb, fb) = a.question(q + 1), b.question(q)
        assert fa == fb
        np.testing.assert_array_equal(ta["submit"], tb["submit"])
    seeds = {a.question(q)[1] for q in range(k)}
    assert len(seeds) == k
