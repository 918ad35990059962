"""A run with the timed path broken underneath must read ``correct``
false: once for each fault the cell can have (one chip, so no exchange
between chips to leave out)."""

import dataclasses
import importlib

import numpy as np
import pytest

from conftest import small
from lib import harness


def _run(cell_name, workdir):
    bench, cell, cfg, trf = small(cell_name)
    return harness.run_cell(
        bench, cell, seed=29, seconds=1.0, trace=False, t_start=0.0,
        device=harness.device_info(1), workdir=workdir, config=cfg,
        traffic=trf)


def _alter_one_finish(res):
    return dataclasses.replace(res, finish=res.finish.at[0].add(1))


def test_run_answer_altered(monkeypatch, workdir):
    from repro.core import engine

    orig = engine.simulate
    monkeypatch.setattr(engine, "simulate",
                        lambda *a, **k: _alter_one_finish(orig(*a, **k)))
    assert _run("sdsc_sp2_128.run8k", workdir)["correct"] is False


def test_sweep_answer_altered(monkeypatch, workdir):
    sweep_mod = importlib.import_module("repro.api.sweep")

    orig = sweep_mod._run_bucket

    def broken(bucket, mesh):
        out = orig(bucket, mesh)
        for r in out:
            r.raw = _alter_one_finish(r.raw)
        return out

    monkeypatch.setattr(sweep_mod, "_run_bucket", broken)
    assert _run("dragonfly_1024.sweep48", workdir)["correct"] is False


def test_sweep_half_the_batch_left_out(monkeypatch, workdir):
    sweep_mod = importlib.import_module("repro.api.sweep")

    orig = sweep_mod._run_bucket

    def broken(bucket, mesh):
        half = len(bucket) // 2
        out = orig(bucket[:half], mesh)
        filled = [dataclasses.replace(out[i % half], scenario=scn)
                  for i, scn in enumerate(bucket[half:])]
        return out + filled

    monkeypatch.setattr(sweep_mod, "_run_bucket", broken)
    assert _run("dragonfly_1024.sweep48", workdir)["correct"] is False


def test_replay_answer_altered(monkeypatch, workdir):
    from repro.replay import runner

    orig = runner.StreamingReplay._result

    def broken(self):
        res = orig(self)
        res.start = res.start.copy()
        res.start[len(res.start) // 2] += 1
        return res

    monkeypatch.setattr(runner.StreamingReplay, "_result", broken)
    assert _run("sdsc_sp2_128.replay73k", workdir)["correct"] is False


def test_replay_step_returns_its_state_unchanged(monkeypatch, workdir):
    """Broken after set-up: every round of the window leaves the state as
    it found it."""
    from repro.replay import runner

    orig_entry = harness.entry_class

    def entry_class(name):
        base = orig_entry(name)

        class Broken(base):
            def warm_up(self):
                super().warm_up()
                monkeypatch.setattr(runner, "simulate_window",
                                    lambda pol, jobs, state, *a, **k:
                                    (state, np.bool_(False)))
        return Broken

    monkeypatch.setattr(harness, "entry_class", entry_class)
    res = _run("sdsc_sp2_128.replay73k", workdir)
    assert res["correct"] is False


def test_whatif_answer_altered(monkeypatch, workdir):
    from repro.service import planner

    orig = planner.enriched_summary

    def broken(result):
        s = orig(result)
        s["p99_wait"] += 1.0
        return s

    monkeypatch.setattr(planner, "enriched_summary", broken)
    assert _run("sdsc_sp2_128.whatif", workdir)["correct"] is False


@pytest.mark.parametrize("cell_name", ["sdsc_sp2_128.run8k"])
def test_unbroken_run_is_correct(cell_name, workdir):
    assert _run(cell_name, workdir)["correct"] is True
