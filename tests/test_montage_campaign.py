"""The Montage campaign deployment (``WorkflowTrace(kind="montage_campaign")``):
the generator holds the published Montage 8-degree shape (Juve et al.,
FGCS 2013) whatever the seed, ``run()`` on a small campaign is bit-exact
against the host reference, and the lowering's span, the generator's span
and the engine's ``release`` scope show in a CPU trace and in the compiled
program."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import Scenario, WorkflowTrace, build_jobset, run, run_ref
from repro.core import engine
from repro.traces.workflows import montage_campaign

from test_obs import op_name_scopes, program_spans

# the published profile: tasks a stage of one mosaic
PROFILE = {"mProjectPP": 2102, "mDiffFit": 6172, "mConcatFit": 1,
           "mBgModel": 1, "mBackground": 2102, "mImgtbl": 17, "mAdd": 17,
           "mShrink": 16, "mJPEG": 1}
SMALL = (("images", 40), ("mosaics", 2), ("pairs", 110), ("tiles", 4))


def stages(wf, n):
    """Tasks of the first mosaic by stage, read off the DAG alone: roots
    are mProjectPP, tasks on two roots mDiffFit, the task on every
    mDiffFit mConcatFit, then each stage is what waits on the one before."""
    pairs = np.asarray(wf["dep_pairs"])
    pairs = pairs[pairs[:, 0] < n]
    deps = [set() for _ in range(n)]
    for t, d in pairs:
        deps[t].add(int(d))
    roots = {i for i in range(n) if not deps[i]}
    diff = {i for i in range(n) if len(deps[i]) == 2 and deps[i] <= roots}
    concat = {i for i in range(n) if deps[i] and deps[i] == diff}
    bgm = {i for i in range(n) if deps[i] and deps[i] <= concat}
    back = {i for i in range(n) if bgm & deps[i]}
    tile_tbl = {i for i in range(n) if deps[i] and deps[i] <= back}
    tile_add = {i for i in range(n) if deps[i] and deps[i] <= tile_tbl}
    shrink = {i for i in range(n) if deps[i] and deps[i] <= tile_add}
    last_tbl = {i for i in range(n) if deps[i] and deps[i] == shrink}
    last_add = {i for i in range(n) if deps[i] and deps[i] <= last_tbl}
    jpeg = {i for i in range(n) if deps[i] and deps[i] <= last_add}
    return {"mProjectPP": len(roots), "mDiffFit": len(diff),
            "mConcatFit": len(concat), "mBgModel": len(bgm),
            "mBackground": len(back),
            "mImgtbl": len(tile_tbl) + len(last_tbl),
            "mAdd": len(tile_add) + len(last_add), "mShrink": len(shrink),
            "mJPEG": len(jpeg)}


def test_one_mosaic_is_the_published_profile():
    wf = montage_campaign(1, seed=3)
    assert len(wf["exec_time"]) == 10_429
    assert np.asarray(wf["dep_pairs"]).shape == (24_873, 2)
    assert stages(wf, 10_429) == PROFILE
    assert (np.asarray(wf["resources"])[:, 0] == 1).all()


@pytest.mark.parametrize("seed", [0, 1, 2**40 + 7])
def test_the_seed_draws_runtimes_and_never_the_shape(seed):
    base = montage_campaign(seed=0)
    wf = montage_campaign(seed=seed)
    assert len(wf["exec_time"]) == 104_290
    assert wf["dep_pairs"].dtype == np.int32
    np.testing.assert_array_equal(wf["dep_pairs"], base["dep_pairs"])
    np.testing.assert_array_equal(wf["submit"], base["submit"])
    assert (wf["exec_time"] >= 1).all()
    assert sorted(set(wf["submit"].tolist())) == [220 * k for k in range(10)]


def test_stage_runtimes_follow_the_profiles_means():
    wf = montage_campaign(20, seed=5)
    et = wf["exec_time"].reshape(20, -1)
    # mDiffFit (0.66 s) and mProjectPP (1.73 s) in ticks of 0.1 s
    assert et[:, 2102:2102 + 6172].mean() == pytest.approx(6.6, rel=0.02)
    assert et[:, :2102].mean() == pytest.approx(17.3, rel=0.02)


def test_too_many_pairs_or_a_non_square_tiling_is_refused():
    with pytest.raises(ValueError, match="overlap"):
        montage_campaign(1, images=9, pairs=40, tiles=4)
    with pytest.raises(ValueError, match="square"):
        montage_campaign(1, images=40, pairs=110, tiles=5)


def small_scenario(seed=11):
    return Scenario(trace=WorkflowTrace(kind="montage_campaign", seed=seed,
                                        params=SMALL),
                    total_nodes=32, policy="fcfs")


@pytest.mark.parametrize("seed", [11, 2**33 + 5])
def test_run_is_bit_exact_against_the_reference(seed):
    scn = small_scenario(seed)
    a, b = run(scn).to_np(), run_ref(scn).to_np()
    n = len(scn.trace.materialize()["submit"])
    assert n == 2 * (2 * 40 + 110 + 3 * 4 + 5)
    for k in ("start", "finish", "ready"):
        np.testing.assert_array_equal(a[k][:n], b[k][:n], err_msg=k)
    assert a["n_events"] == b["n_events"]
    # a dependent never starts before its dependencies finish, and the
    # second mosaic's roots wait for the gap
    deps = scn.trace.materialize()["deps"]
    assert (a["start"][deps[:, 0]] >= a["finish"][deps[:, 1]]).all()
    assert a["start"][n // 2:].min() >= 220


def test_campaign_spans_and_counters_in_a_cpu_trace(tmp_path):
    scn = small_scenario(seed=2**20 + 3)
    run(small_scenario(seed=1)).summary()     # compile outside the trace
    before = obs.counters()
    with jax.profiler.trace(str(tmp_path)):
        run(scn).summary()
    after = obs.counters()
    spans = program_spans(tmp_path)
    by = {s[0]: s for s in spans}
    lower, mat = by["repro.deps.lower"], by["repro.workflow.materialize"]
    prep = by["repro.run.prepare"]
    assert prep[1] <= mat[1] and mat[2] <= lower[1] and lower[2] <= prep[2]
    n, e = 2 * 207, 2 * 3 * (40 + 110 + 4 + 1)
    assert lower[4]["tasks"] == n and lower[4]["edges"] == e
    assert mat[4]["tasks"] == n
    assert after["deps.edges"] - before.get("deps.edges", 0) == e
    assert after["workflow.tasks"] - before.get("workflow.tasks", 0) == n
    lowered_us = after["deps.lower_us"] - before.get("deps.lower_us", 0)
    assert 0 < lowered_us <= (lower[2] - lower[1]) / 1e3 + 1


def test_release_scope_is_in_the_dag_program_only():
    def scopes(scn):
        jobs = build_jobset(scn)
        pol = engine.policies_id("fcfs")
        return op_name_scopes(engine._simulate_jit.lower(
            jobs, jnp.asarray(pol, jnp.int32), jnp.asarray(32, jnp.int32),
            None, fctx=None, static_policy=engine._static_policy_hint(pol),
            static_strategy=None).compile().as_text())

    dag = scopes(small_scenario())
    assert {"advance", "release", "schedule"} <= dag
    plain = Scenario(trace=small_scenario().trace.materialize() | {"deps": None},
                     total_nodes=32, policy="fcfs")
    assert "release" not in scopes(plain)
