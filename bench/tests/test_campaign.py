"""The Montage campaign cell on the CPU at ``small()``'s size: the program
is bit-exact against the benchmark's own DAG reference (``lib/refsim_dag``),
the reference in the program's place passes the check, the control and an
altered finish fail it, ``deps_lower_ms`` reads the entry's counter, and a
program without the ``montage_campaign`` kind fails at once."""

import dataclasses

import numpy as np
import pytest

from conftest import small
from lib import control, harness, refsim_dag
from lib.entries import campaign

CELL = "montage_campaign_1024.campaign10"


def _run(workdir, *, trace=False, seed=2**35 + 3):
    bench, cell, cfg, trf = small(CELL)
    return harness.run_cell(bench, cell, seed=seed, seconds=1.0, trace=trace,
                            t_start=0.0, device=harness.device_info(1),
                            workdir=workdir, config=cfg, traffic=trf)


@pytest.mark.parametrize("seed", [3, 2**40 + 11])
def test_program_equals_the_dag_reference(seed):
    from repro.api import Scenario, WorkflowTrace, run

    _, _, cfg, _ = small(CELL)
    params = tuple(sorted(cfg["workload"]["params"].items()))
    spec = WorkflowTrace(kind="montage_campaign", seed=seed, params=params)
    got = run(Scenario(trace=spec, total_nodes=32, policy="fcfs")).to_np()
    trace = spec.materialize()
    ref = refsim_dag.simulate(trace, 32)
    for k in ("start", "finish", "ready"):
        np.testing.assert_array_equal(got[k], ref[k], err_msg=k)
    assert got["n_events"] == ref["n_events"]
    want = campaign.expected_campaign(cfg["workload"]["params"])
    assert campaign.off_profile(trace, want, cfg["workload"]) == (0, 0)


def test_the_full_campaigns_shape():
    cfg = harness.load_json("configs", "montage_campaign_1024")
    want = campaign.expected_campaign(cfg["workload"]["params"])
    assert (len(want["submit"]), len(want["deps"])) == (104_290, 248_730)
    assert np.bincount(want["stage"][:10_429]).tolist() == \
        [2102, 6172, 1, 1, 2102, 17, 17, 16, 1]


def test_reference_in_the_programs_place_passes(monkeypatch):
    orig = refsim_dag.simulate
    monkeypatch.setattr(refsim_dag, "simulate",
                        lambda *a, release="finish", **k: orig(*a, **k))
    _, cell, cfg, trf = small(CELL)
    readings = control.control_readings(cell, seed=11, questions=2,
                                        config=cfg, traffic=trf)
    assert all(lim is None or v <= lim for _, v, lim in readings), readings


def test_an_altered_finish_fails_the_check(monkeypatch, workdir):
    from repro.core import engine

    orig = engine.simulate
    monkeypatch.setattr(engine, "simulate", lambda *a, **k: dataclasses.replace(
        r := orig(*a, **k), finish=r.finish.at[5].add(1)))
    assert _run(workdir)["correct"] is False


def test_traced_run_reads_the_lowering(workdir):
    res = _run(workdir, trace=True)
    assert res["correct"] is True, res["limits"]
    ms = res["metrics"]["deps_lower_ms"]["value"]
    assert 0 < ms < 1e3
    bench = harness.load_benchmark()
    assert set(res["metrics"]) <= {m["name"] for m in harness.cell_metrics(
        bench, CELL, "per_layer")}


def test_no_counter_reads_nothing():
    from types import SimpleNamespace

    read = harness.metric_reader("deps_lower_ms")
    assert read(SimpleNamespace(traced={"questions": 2.0})) is None
    assert read(SimpleNamespace(traced={"deps_lower_us": 3000.0,
                                        "questions": 2.0})) == 1.5


def test_a_program_without_the_kind_fails_at_once(monkeypatch, workdir):
    from repro.api import WorkflowTrace
    from repro.api.scenario import _materialize_workflow

    _materialize_workflow.cache_clear()
    gens = dict(WorkflowTrace._GENERATORS)
    gens.pop("montage_campaign")
    monkeypatch.setattr(WorkflowTrace, "_GENERATORS", gens)
    with pytest.raises(ValueError, match="unknown workflow kind"):
        _run(workdir)


def _miswired(drop_stage, from_stage):
    """The generator less every edge from a ``from_stage`` task to a
    ``drop_stage`` task (stages as in ``campaign.STAGES``)."""
    def gen(**kw):
        from repro.traces import workflows

        stage = campaign.expected_campaign(kw)["stage"]
        wf = workflows.montage_campaign(**kw)
        d = wf["dep_pairs"]
        cut = ((stage[d[:, 0]] == campaign.STAGES.index(drop_stage))
               & (stage[d[:, 1]] == campaign.STAGES.index(from_stage)))
        return dict(wf, dep_pairs=d[~cut])
    return gen


def _slow_projections(**kw):
    from repro.traces import workflows

    wf = workflows.montage_campaign(**kw)
    n = 2 * kw["images"] + kw["pairs"] + 3 * kw["tiles"] + 5
    first = (np.arange(len(wf["exec_time"])) % n) < kw["images"]
    return dict(wf, exec_time=np.where(first, 3 * wf["exec_time"],
                                       wf["exec_time"]))


@pytest.mark.parametrize("gen,reading", [
    (_miswired("background", "bgmodel"), "campaigns_off_shape"),
    (_miswired("imgtbl", "background"), "campaigns_off_shape"),
    (_slow_projections, "stages_off_mean"),
])
def test_a_generator_off_the_profile_fails_the_check(monkeypatch, gen,
                                                     reading):
    """The reference's layout is built in the benchmark: a campaign wired
    otherwise, or a stage off its mean runtime, reads as off the profile,
    even with the reference's answers in the program's place."""
    from repro.api import WorkflowTrace
    from repro.api.scenario import _materialize_workflow

    _materialize_workflow.cache_clear()
    monkeypatch.setattr(WorkflowTrace, "_GENERATORS",
                        dict(WorkflowTrace._GENERATORS,
                             montage_campaign=gen))
    orig = refsim_dag.simulate
    monkeypatch.setattr(refsim_dag, "simulate",
                        lambda *a, release="finish", **k: orig(*a, **k))
    _, cell, cfg, trf = small(CELL)
    try:
        readings = dict((n, v) for n, v, _ in control.control_readings(
            cell, seed=11, questions=2, config=cfg, traffic=trf))
    finally:
        _materialize_workflow.cache_clear()
    assert readings[reading] > 0, readings
