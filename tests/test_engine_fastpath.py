"""Engine fast paths (ISSUE 4, DESIGN.md §14): sparse dependency counters +
batched scheduling pass.

- representation: the padded ``dep_dst``/``dep_src`` edge list reconstructs
  exactly the dense matrix the engine used to carry, and the unmet counters
  initialize to the dense in-degrees;
- bit-exactness: the statically-specialized fast executable (batched prefix
  pass, direct selector dispatch) equals the fully-dynamic seed-loop
  executable — same schedule, same ``ready``/``wait`` columns — across
  policies, DAGs, and count-capped allocation strategies;
- elision: ``deps=None`` / zero-edge job tables still produce bit-identical
  results to the seed engine across all six policies;
- stacking: ``stack_jobsets`` pads members mixing edge lists of different
  lengths and edge-free tables, without changing any member's schedule.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro import obs
from repro.api import Scenario, Topology, WorkflowTrace, run, run_ref
from repro.core import engine
from repro.core.engine import _simulate_jit, make_alloc_ctx, simulate
from repro.core.jobs import POLICY_IDS, make_jobset
from repro.core.parallel import simulate_ensemble, stack_jobsets
from repro.traces.workflows import (
    galactic_like, montage_like, random_layered, workflow_to_trace,
)

ALL_POLICIES = ("fcfs", "sjf", "ljf", "bestfit", "backfill", "preempt")
BLOCKING = ("fcfs", "sjf", "ljf")


def _loop_simulate(jobs, policy, total_nodes, ctx=None):
    """The fully-dynamic executable: no static policy/strategy hints, so the
    scheduling pass is the seed per-start selector loop."""
    return _simulate_jit(
        jobs, jnp.asarray(POLICY_IDS[policy], jnp.int32),
        jnp.asarray(total_nodes, jnp.int32), ctx, max_events=None,
        static_policy=None, static_strategy=None)


def _assert_same(a, b, fields=("start", "finish", "ready", "wait"), msg=""):
    for f in fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{msg}:{f}")


# ---------------------------------------------------------------------------
# representation: edge list == dense matrix
# ---------------------------------------------------------------------------


def test_edge_list_round_trips_the_dense_matrix():
    wf = montage_like(8, seed=3)
    trace = workflow_to_trace(wf)
    n = len(trace["submit"])
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace["deps"], total_nodes=8)
    # reference: the dense matrix of the pairs permuted by the (submit, id)
    # sort — exactly what the removed dense field used to hold
    order = np.lexsort((np.arange(n), np.asarray(trace["submit"])))
    dense = np.zeros((n, n), dtype=bool)
    for t, d in trace["deps"]:
        dense[t, d] = True
    want = dense[order][:, order]
    got = np.asarray(jobs.deps)  # property reconstructs from the edge list
    np.testing.assert_array_equal(got[:n, :n], want)
    assert not got[n:].any() and not got[:, n:].any()
    # padding: edge list is 64-aligned, pad slots hold the OOB row index
    E = jobs.edge_capacity
    assert E % 64 == 0 and E >= want.sum()
    dst = np.asarray(jobs.dep_dst)
    assert (dst[int(want.sum()):] == jobs.capacity).all()


def test_n_unmet_initializes_to_dense_indegree():
    from repro.core.jobs import SimState

    trace = workflow_to_trace(galactic_like(tiles=2, width=5, seed=1))
    n = len(trace["submit"])
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       deps=trace["deps"], total_nodes=8)
    state = SimState.init(jobs, 8)
    indeg = np.asarray(jobs.deps).sum(axis=1)
    np.testing.assert_array_equal(np.asarray(state.n_unmet), indeg)
    # no-deps tables carry the zero-size placeholder (static elision)
    plain = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                        total_nodes=8)
    assert SimState.init(plain, 8).n_unmet.shape == (0,)


def test_make_jobset_edge_capacity_validates():
    trace = dict(submit=[0, 0, 0], runtime=[5, 5, 5], nodes=[1, 1, 1])
    jobs = make_jobset(**trace, deps=[(1, 0), (2, 1)], total_nodes=4,
                       edge_capacity=8)
    assert jobs.edge_capacity == 8
    with pytest.raises(ValueError, match="edge_capacity"):
        make_jobset(**trace, deps=[(1, 0), (2, 1)], total_nodes=4,
                    edge_capacity=1)


# ---------------------------------------------------------------------------
# bit-exactness: fast executable == seed-loop executable
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_fast_equals_loop_on_workflow(policy):
    trace = workflow_to_trace(galactic_like(tiles=2, width=5, seed=0))
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace["deps"], total_nodes=8)
    fast = simulate(jobs, POLICY_IDS[policy], 8)       # static specialization
    slow = _loop_simulate(jobs, policy, 8)             # seed loop
    _assert_same(fast, slow, msg=policy)
    assert int(fast.n_events) == int(slow.n_events)


@pytest.mark.parametrize("policy", BLOCKING)
def test_fast_equals_loop_on_plain_trace(policy):
    rng = np.random.default_rng(7)
    n = 120
    jobs = make_jobset(rng.integers(0, 400, n), rng.integers(1, 90, n),
                       rng.integers(1, 9, n), rng.integers(1, 120, n),
                       total_nodes=16)
    _assert_same(simulate(jobs, POLICY_IDS[policy], 16),
                 _loop_simulate(jobs, policy, 16), msg=policy)


@pytest.mark.parametrize("alloc", ("simple", "spread"))
@pytest.mark.parametrize("policy", BLOCKING)
def test_fast_equals_loop_count_capped_machine(policy, alloc):
    """With a machine and a count-capped strategy the batched pass picks the
    same start set and places it in the same order — node maps included."""
    machine = Topology.mesh2d(4, 4).build()
    trace = workflow_to_trace(montage_like(6, seed=2))
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace["deps"], total_nodes=16)
    fast = simulate(jobs, POLICY_IDS[policy], 16, machine=machine, alloc=alloc)
    ctx = make_alloc_ctx(machine, alloc, None)
    slow = _simulate_jit(
        jobs, jnp.asarray(POLICY_IDS[policy], jnp.int32), jnp.asarray(16, jnp.int32),
        ctx, max_events=None, static_policy=None, static_strategy=None)
    _assert_same(fast, slow,
                 fields=("start", "finish", "alloc_first", "alloc_span",
                         "alloc_sum"), msg=f"{policy}/{alloc}")


@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), policy=st.sampled_from(BLOCKING),
       total_nodes=st.sampled_from([8, 16]))
def test_fast_equals_loop_random_dags(seed, policy, total_nodes):
    trace = workflow_to_trace(random_layered(30, 4, p_edge=0.2, seed=seed))
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       deps=trace["deps"], total_nodes=total_nodes)
    _assert_same(simulate(jobs, POLICY_IDS[policy], total_nodes),
                 _loop_simulate(jobs, policy, total_nodes),
                 msg=f"{policy}@{seed}")


@pytest.mark.parametrize("policy", ALL_POLICIES)
def test_no_deps_still_bit_identical_to_seed_engine(policy):
    """deps=None and zero-edge inputs compile to the seed event graph: the
    schedule matches the reference simulator row for row."""
    rng = np.random.default_rng(11)
    n = 80
    trace = dict(submit=rng.integers(0, 300, n), runtime=rng.integers(1, 70, n),
                 nodes=rng.integers(1, 9, n), estimate=rng.integers(1, 90, n),
                 priority=rng.integers(0, 3, n))
    plain = make_jobset(**trace, total_nodes=16)
    elided = make_jobset(**trace, deps=[], total_nodes=16)
    assert elided.dep_dst is None and elided.dep_src is None
    a = simulate(plain, POLICY_IDS[policy], 16)
    b = simulate(elided, POLICY_IDS[policy], 16)
    _assert_same(a, b, msg=policy)
    from repro.refsim import simulate_reference
    ref = simulate_reference(trace, policy, total_nodes=16)
    np.testing.assert_array_equal(np.asarray(a.start)[:n], ref["start"])
    np.testing.assert_array_equal(np.asarray(a.finish)[:n], ref["finish"])


# ---------------------------------------------------------------------------
# stacking: ragged edge lists + edge-free members
# ---------------------------------------------------------------------------


def test_stack_jobsets_pads_ragged_edge_lists():
    cap = 64
    dag_a = workflow_to_trace(montage_like(8, seed=0))       # pads to 64
    dag_b = workflow_to_trace(galactic_like(tiles=2, width=8, seed=0))  # 128
    rng = np.random.default_rng(0)
    plain = dict(submit=rng.integers(0, 100, 20), runtime=rng.integers(1, 50, 20),
                 nodes=rng.integers(1, 5, 20))
    js = [
        make_jobset(dag_a["submit"], dag_a["runtime"], dag_a["nodes"],
                    deps=dag_a["deps"], capacity=cap, total_nodes=8),
        make_jobset(dag_b["submit"], dag_b["runtime"], dag_b["nodes"],
                    deps=dag_b["deps"], capacity=cap, total_nodes=8),
        make_jobset(**plain, capacity=cap, total_nodes=8),   # edge-free
    ]
    assert js[0].edge_capacity != js[1].edge_capacity        # genuinely ragged
    stacked = stack_jobsets(js)
    E = max(j.edge_capacity for j in js)
    assert stacked.dep_dst.shape == (3, E) and stacked.dep_src.shape == (3, E)
    # edge-free member got only inert OOB padding
    assert (np.asarray(stacked.dep_dst[2]) == cap).all()
    # stacked members reproduce their standalone schedules bit-for-bit
    pol = np.full((3,), POLICY_IDS["fcfs"], np.int32)
    batched = simulate_ensemble(stacked, pol, np.full((3,), 8, np.int32))
    for i, j in enumerate(js):
        single = simulate(j, POLICY_IDS["fcfs"], 8)
        np.testing.assert_array_equal(np.asarray(batched.start)[i],
                                      np.asarray(single.start), f"member {i}")
        np.testing.assert_array_equal(np.asarray(batched.ready)[i],
                                      np.asarray(single.ready), f"member {i}")


def test_sweep_mixed_edge_counts_single_bucket():
    """Random-DAG seeds generate different edge counts; the sweep stacks them
    into one executable and every point still matches the reference."""
    from repro.api import sweep

    scn = Scenario(trace=WorkflowTrace(kind="random",
                                       params=(("n_tasks", 24), ("n_layers", 4))),
                   total_nodes=8, policy="fcfs")
    grid = sweep(scn, axes={"trace.seed": (0, 1, 2), "policy": ("fcfs", "sjf")})
    assert grid.n_compiles == 1
    for point, res in grid:
        assert res.matches(run_ref(res.scenario)), point


# ---------------------------------------------------------------------------
# scheduling-pass equivalence at the event level
# ---------------------------------------------------------------------------


def test_batched_pass_starts_exact_feasible_prefix():
    """Six 2-node jobs plus one dependent, 7 free nodes: FCFS starts exactly
    three (the longest prefix whose cumulative demand fits) in one event.

    The dependency edge matters twice: it makes the table eligible for the
    batched prefix pass (dep-free tables keep the selector loop), and it
    pins the prefix boundary — an off-by-one in ``take``/``n_take`` would
    start a fourth job at t=0."""
    n = 7
    trace = dict(submit=np.zeros(n), runtime=np.full(n, 50),
                 nodes=np.full(n, 2), deps=[(6, 0)])   # last job needs job 0
    jobs = make_jobset(**trace, total_nodes=7)
    assert engine._fast_order(jobs, None, POLICY_IDS["fcfs"], None) is not None
    res = simulate(jobs, POLICY_IDS["fcfs"], 7)
    start = np.asarray(res.start)
    assert (start[:3] == 0).all()            # rows 0-2 start at t=0
    assert (start[3:6] == 50).all()          # the rest wait for completions
    assert start[6] >= 50                    # dependent releases at t=50
    ref = run_ref(Scenario(trace=trace, total_nodes=7, policy="fcfs"))
    np.testing.assert_array_equal(start, ref["start"])
    np.testing.assert_array_equal(np.asarray(res.finish), ref["finish"])


# ---------------------------------------------------------------------------
# backfill batched pass (ISSUE 8, DESIGN.md §18)
# ---------------------------------------------------------------------------


BF = "backfill"
BF_FAIL = dict(mtbf=600.0, requeue="requeue", seed=7, mean_repair=50,
               horizon=4000, max_failures=32, checkpoint_interval=20,
               restart_overhead=5)


def _bf_trace(dag: bool) -> dict:
    if dag:
        t = workflow_to_trace(galactic_like(tiles=2, width=5, seed=4))
        return dict(submit=t["submit"], runtime=t["runtime"],
                    nodes=t["nodes"], estimate=t["estimate"],
                    deps=t["deps"])
    rng = np.random.default_rng(9)
    n = 60
    return dict(submit=rng.integers(0, 400, n),
                runtime=rng.integers(5, 80, n),
                nodes=rng.integers(1, 6, n),
                estimate=rng.integers(5, 100, n))


def _bf_run_three_ways(trace, *, machine=None, alloc=None, ftrace=None,
                       plan=None, total_nodes=16, msg=""):
    """simulate (batched where eligible) == seed loop == refsim, bit-exact."""
    from repro.malleable import make_mal_ctx
    from repro.refsim import simulate_reference
    from repro.reliability import make_fail_ctx

    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace.get("deps"),
                       total_nodes=total_nodes)
    if plan is not None:
        from repro.malleable import materialize_plan
        plan = materialize_plan(plan, trace, total_nodes=total_nodes,
                                capacity=jobs.capacity)
    fast = simulate(jobs, POLICY_IDS[BF], total_nodes, machine=machine,
                    alloc=alloc, failures=ftrace, malleable=plan)
    ctx = make_alloc_ctx(machine, alloc, None) if machine is not None else None
    slow = _simulate_jit(
        jobs, jnp.asarray(POLICY_IDS[BF], jnp.int32),
        jnp.asarray(total_nodes, jnp.int32), ctx,
        fctx=make_fail_ctx(ftrace, n_nodes=total_nodes),
        mctx=make_mal_ctx(plan), max_events=None,
        static_policy=None, static_strategy=None)
    _assert_same(fast, slow, msg=msg)
    assert int(fast.n_events) == int(slow.n_events), msg
    ref = simulate_reference(trace, BF, total_nodes=total_nodes,
                             machine=machine,
                             alloc=alloc if alloc is not None else "simple",
                             failures=ftrace, malleable=plan)
    n = len(trace["submit"])
    for f in ("start", "finish"):
        np.testing.assert_array_equal(np.asarray(getattr(fast, f))[:n],
                                      ref[f], err_msg=f"{msg}:ref:{f}")


@pytest.mark.slow
@pytest.mark.parametrize("mold", (False, True), ids=("rigid", "moldable"))
@pytest.mark.parametrize("fail", (False, True), ids=("nofail", "failures"))
@pytest.mark.parametrize("mode", ("scalar", "mesh"))
@pytest.mark.parametrize("dag", (False, True), ids=("nodeps", "galactic"))
def test_backfill_differential_grid(dag, mode, fail, mold):
    """The full ISSUE-8 grid: batched pass (where eligible — scalar/spread
    rigid) vs seed selector loop vs refsim, bit-exact.  The mesh+contiguous
    and moldable corners run the per-start loop by eligibility (DESIGN.md
    §18's table) and must *still* match refsim — the gate itself is part of
    the contract."""
    from repro.api import FailureModel
    from repro.malleable import MalleableModel

    trace = _bf_trace(dag)
    kw = {"msg": f"{dag}/{mode}/{fail}/{mold}"}
    if mode == "mesh":
        kw.update(machine=Topology.mesh2d(4, 4).build(), alloc="contiguous")
    if fail:
        kw.update(ftrace=FailureModel(**BF_FAIL).materialize(16))
    if mold:
        kw.update(plan=MalleableModel(curve="amdahl", param=0.2, min_width=1,
                                      max_width=8, mode="moldable"))
    _bf_run_three_ways(trace, **kw)


@pytest.mark.parametrize("dag", (False, True), ids=("nodeps", "galactic"))
def test_backfill_batched_pass_fast_lane(dag):
    """Fast-lane corner of the grid above: the two cases that actually take
    the batched pass (scalar cap, rigid jobs), both trace shapes."""
    _bf_run_three_ways(_bf_trace(dag), msg=f"fastlane/{dag}")


def test_backfill_fast_order_eligibility():
    """DESIGN.md §18 eligibility: backfill batches on count-capped caps for
    BOTH dep-free and DAG tables (unlike FCFS/SJF/LJF, which batch only
    with deps); contiguous caps and malleable jobs keep the seed loop."""
    import repro.alloc as _alloc

    trace = _bf_trace(False)
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], total_nodes=16)
    bf = POLICY_IDS[BF]
    assert engine._fast_order(jobs, None, bf, None) is not None
    # dep-free FCFS stays on the selector loop (prefix pass needs deps to
    # pay for itself) — backfill is the documented exception
    assert engine._fast_order(jobs, None, POLICY_IDS["fcfs"], None) is None
    machine = Topology.mesh2d(4, 4).build()
    for strat, want in (("simple", True), ("spread", True),
                        ("contiguous", False), ("topo", False)):
        ctx = make_alloc_ctx(machine, strat, None)
        got = engine._fast_order(jobs, ctx, bf, _alloc.canonical_id(strat))
        assert (got is not None) == want, strat
    # a traced strategy id (static_strategy=None) must also fall back
    ctx = make_alloc_ctx(machine, "simple", None)
    assert engine._fast_order(jobs, ctx, bf, None) is None


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), dag=st.booleans())
def test_backfill_random_traces_engine_equals_refsim(seed, dag):
    """Property: random traces (and random DAGs) keep the batched backfill
    pass bit-identical to both the ``static_policy=None`` seed loop and the
    refsim oracle."""
    from repro.refsim import simulate_reference

    if dag:
        trace = workflow_to_trace(random_layered(24, 4, p_edge=0.2, seed=seed))
    else:
        rng = np.random.default_rng(seed)
        n = 40
        trace = dict(submit=rng.integers(0, 300, n),
                     runtime=rng.integers(1, 70, n),
                     nodes=rng.integers(1, 8, n),
                     estimate=rng.integers(1, 90, n))
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace.get("deps"),
                       total_nodes=16)
    # the property is about the batched path: assert it is actually taken
    assert engine._fast_order(jobs, None, POLICY_IDS[BF], None) is not None
    fast = simulate(jobs, POLICY_IDS[BF], 16)
    slow = _loop_simulate(jobs, BF, 16)
    _assert_same(fast, slow, msg=f"bf@{seed}")
    ref = simulate_reference(trace, BF, total_nodes=16)
    n = len(trace["submit"])
    np.testing.assert_array_equal(np.asarray(fast.start)[:n], ref["start"])
    np.testing.assert_array_equal(np.asarray(fast.finish)[:n], ref["finish"])


# ---------------------------------------------------------------------------
# reliability elision (ISSUE 5): failures=None is the pre-reliability engine
# ---------------------------------------------------------------------------


def test_failures_none_hlo_identical_to_pre_reliability_head():
    """The strongest seed-identity property: lowering the engine with
    ``failures=None`` across the policy x alloc x DAG differential grid
    produces byte-identical StableHLO modules to the pinned ones in
    ``tests/data/hlo_nofail.json``.  Identical programs imply
    bit-identical results, so this subsumes output comparison.  Lowered
    with the same jax, the commit before the reliability subsystem gives
    the pinned hash for every configuration except the four backfill ones
    and the dynamic-policy one, which the batched backfill pass (DESIGN.md
    §18) changed on purpose; the row-order pass (DESIGN.md §14) changed
    ``dag_scalar_fcfs``, ``dag_scalar_backfill`` and
    ``plain_scalar_backfill`` on purpose since.

    Regenerate the fixture ONLY for intentional engine-graph changes:
    ``PYTHONPATH=src:tests python tests/_hlo_fixture.py --write``.
    """
    import jax

    from _hlo_fixture import fingerprints, load_fixture

    fixture = load_fixture()
    assert fixture["jax_version"] == jax.__version__, (
        f"fixture lowered with jax {fixture['jax_version']}, running "
        f"{jax.__version__}; after checking that the engine graph did not "
        "change, regenerate it with "
        "`PYTHONPATH=src:tests python tests/_hlo_fixture.py --write`")
    got = fingerprints()
    want = fixture["hashes"]
    assert set(got) == set(want)
    bad = sorted(k for k in want if want[k] != got[k])
    assert not bad, (
        f"failures=None no longer lowers to the pre-reliability HLO for "
        f"{bad}; the reliability subsystem must stay statically elided")


def test_failures_none_result_carries_no_reliability_state():
    jobs = make_jobset([0, 0], [5, 5], [1, 1], total_nodes=4)
    res = simulate(jobs, 0, 4)
    assert res.rel is None
    from repro.core.jobs import SimState
    assert SimState.init(jobs, 4).n_unmet.shape == (0,)
    assert SimState.init(jobs, 4).rel is None


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       policy=st.sampled_from(ALL_POLICIES))
def test_empty_failure_stream_is_semantically_elided(seed, policy):
    """Property over random traces: an attached-but-eventless failure model
    never perturbs the schedule (the executables differ, the event graphs
    agree — HLO identity for failures=None itself is the test above)."""
    from repro.reliability import FailureModel

    rng = np.random.default_rng(seed)
    n = 50
    trace = dict(submit=rng.integers(0, 300, n), runtime=rng.integers(1, 60, n),
                 nodes=rng.integers(1, 8, n), estimate=rng.integers(1, 80, n),
                 priority=rng.integers(0, 3, n))
    jobs = make_jobset(**trace, total_nodes=16)
    quiet = FailureModel(mtbf=1e12, max_failures=8).materialize(16)
    assert quiet.n_failures == 0
    a = simulate(jobs, POLICY_IDS[policy], 16)
    b = simulate(jobs, POLICY_IDS[policy], 16, failures=quiet)
    for f in ("start", "finish", "ready", "wait"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), f)
    assert int(a.n_events) == int(b.n_events)


def test_traced_policy_keeps_seed_semantics_under_vmap():
    """A vmapped policy axis cannot specialize statically; the ensemble path
    must still match per-policy single runs (i.e. the dynamic loop is intact
    and bit-exact)."""
    trace = workflow_to_trace(montage_like(6, seed=5))
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       deps=trace["deps"], total_nodes=8)
    pols = np.asarray([POLICY_IDS[p] for p in ("fcfs", "sjf", "ljf")], np.int32)
    batched = simulate_ensemble(stack_jobsets([jobs] * 3), pols,
                                np.full((3,), 8, np.int32))
    for i, p in enumerate(("fcfs", "sjf", "ljf")):
        single = simulate(jobs, POLICY_IDS[p], 8)
        np.testing.assert_array_equal(np.asarray(batched.start)[i],
                                      np.asarray(single.start), p)


# ---------------------------------------------------------------------------
# row-order batched pass (DESIGN.md §14): a make_jobset table's row order is
# its FCFS order, so the pass reads the queue in place and applies the starts
# as masks — bit-identical to the gather-and-loop pass over blocking_order
# ---------------------------------------------------------------------------


def _pass_counts():
    c = obs.counters()
    return (c.get("engine.batched.row_order", 0),
            c.get("engine.batched.permuted", 0))


def _row_order_case(case):
    """(trace, policy, total_nodes, failure trace or None) for one case."""
    from repro.api import FailureModel

    if case.startswith("fcfs_dag"):
        seed = int(case.rsplit("_", 1)[1])
        t = workflow_to_trace(random_layered(40, 5, p_edge=0.25, seed=seed))
        return dict(t), "fcfs", 8, None
    if case == "montage_campaign":
        wf = WorkflowTrace(kind="montage_campaign", seed=3,
                           params=(("images", 40), ("mosaics", 2),
                                   ("pairs", 110), ("tiles", 4)))
        return dict(wf.materialize()), "fcfs", 32, None
    if case == "galactic_failures":
        t = workflow_to_trace(galactic_like(tiles=2, width=5, seed=4))
        return dict(t), "fcfs", 16, FailureModel(**BF_FAIL).materialize(16)
    if case.startswith("easy"):
        fail = case.endswith("failures")
        ftrace = FailureModel(**BF_FAIL).materialize(16) if fail else None
        return _bf_trace(False), BF, 16, ftrace
    assert case == "sjf"
    t = workflow_to_trace(galactic_like(tiles=2, width=5, seed=1))
    return dict(t), "sjf", 8, None


@pytest.mark.parametrize("case", (
    "fcfs_dag_0", "fcfs_dag_1", "fcfs_dag_2", "montage_campaign",
    "galactic_failures", "easy_scalar", "easy_scalar_failures", "sjf"))
def test_row_order_pass_equals_gather_pass_and_refsim(case):
    """The row-order pass, the gather-and-loop pass over ``blocking_order``
    (the same table without ``submit_sorted``) and the host reference agree
    bit for bit; the counters name the pass each call took (SJF keys on
    the estimate, so it keeps the gathers)."""
    from repro.refsim import simulate_reference

    trace, policy, nodes, ftrace = _row_order_case(case)
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace.get("estimate"), deps=trace.get("deps"),
                       total_nodes=nodes)
    assert jobs.submit_sorted
    gathered = dataclasses.replace(jobs, submit_sorted=False)
    row0, perm0 = _pass_counts()
    a = simulate(jobs, POLICY_IDS[policy], nodes, failures=ftrace)
    row1, perm1 = _pass_counts()
    b = simulate(gathered, POLICY_IDS[policy], nodes, failures=ftrace)
    row2, perm2 = _pass_counts()
    row_order = policy != "sjf"
    assert (row1 - row0, perm1 - perm0) == ((1, 0) if row_order else (0, 1))
    assert (row2 - row1, perm2 - perm1) == (0, 1)
    _assert_same(a, b, msg=case)
    assert int(a.n_events) == int(b.n_events)
    ref = simulate_reference(trace, policy, total_nodes=nodes,
                             failures=ftrace)
    n = len(trace["submit"])
    for f in ("start", "finish", "ready"):
        np.testing.assert_array_equal(np.asarray(getattr(a, f))[:n], ref[f],
                                      err_msg=f"{case}:ref:{f}")
    assert int(a.n_events) == int(ref["n_events"])


@settings(max_examples=40, deadline=None)
@given(seed=st.integers(0, 2**31 - 1), n=st.integers(1, 40),
       pad=st.integers(0, 9), span=st.sampled_from([1, 3, 50]))
def test_make_jobset_rows_are_in_blocking_order(seed, n, pad, span):
    """Property: every ``make_jobset`` table — tied submits and padding
    rows included — has FCFS's and EASY's blocking order equal to its row
    order, which is the premise ``submit_sorted`` states."""
    rng = np.random.default_rng(seed)
    jobs = make_jobset(rng.integers(0, span, n), rng.integers(1, 30, n),
                       rng.integers(1, 5, n), capacity=n + pad,
                       total_nodes=8)
    assert jobs.submit_sorted
    rows = np.arange(n + pad)
    for policy in ("fcfs", BF):
        np.testing.assert_array_equal(
            np.asarray(engine.blocking_order(jobs, POLICY_IDS[policy])), rows)


def test_stack_jobsets_keeps_row_order_only_when_every_member_has_it():
    jobs = make_jobset([0, 1, 1], [5, 5, 5], [1, 2, 1], total_nodes=4)
    other = dataclasses.replace(jobs, submit_sorted=False)
    assert stack_jobsets([jobs, jobs]).submit_sorted
    assert not stack_jobsets([jobs, other]).submit_sorted


def test_replay_and_multicluster_tables_never_take_the_row_order_pass():
    """Replay windows and multicluster tables are built outside
    ``make_jobset`` (or lose its order to migration), so their backfill
    pass keeps the gathers: the counters see ``permuted`` and never
    ``row_order``."""
    from repro.core.parallel import simulate_multicluster
    from repro.replay import replay_trace

    trace = _bf_trace(False)
    row0, perm0 = _pass_counts()
    replay_trace(dict(trace), BF, total_nodes=16, window=24)
    row1, perm1 = _pass_counts()
    assert row1 == row0 and perm1 > perm0
    js = [make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                      trace["estimate"], capacity=80, total_nodes=16)
          for _ in range(2)]
    res = simulate_multicluster(stack_jobsets(js), POLICY_IDS[BF],
                                np.full((2,), 16, np.int32), window=100,
                                horizon=600)
    row2, perm2 = _pass_counts()
    assert row2 == row1 and perm2 > perm1
    assert not res.jobs.submit_sorted
