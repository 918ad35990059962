"""The host lowering of task dependencies to the engine's edge list
(``repro.core.jobs.dep_edge_arrays``, DESIGN.md §13-14): O(J + E) on pairs,
the same ``(dst, src)`` arrays a dense matrix gives, duplicates once,
cycles and self-edges refused, and a 10^5-task campaign lowered in
seconds and megabytes."""

import time
import tracemalloc

import numpy as np
import pytest

from _hypothesis_compat import given, settings, st
from repro import obs
from repro.core.jobs import dep_edge_arrays, make_jobset
from repro.traces.workflows import montage_campaign, workflow_to_trace


def dense_oracle(pairs, n, order):
    """The lowering as a dense matrix permuted by the sort: ``(dst, src)``
    of ``np.nonzero``, duplicates collapsed by the matrix itself."""
    dense = np.zeros((n, n), dtype=bool)
    for t, d in pairs:
        dense[t, d] = True
    return np.nonzero(dense[order][:, order])


@st.composite
def dags(draw):
    """A DAG of up to 200 tasks as (job, dependency) pairs in input order,
    some repeated, and a shuffled sort permutation."""
    n = draw(st.integers(2, 200))
    rank = np.random.default_rng(draw(st.integers(0, 2**32 - 1)))
    topo = rank.permutation(n)          # edges point down this order
    k = draw(st.integers(0, 4 * n))
    a = rank.integers(0, n, k)
    b = rank.integers(0, n, k)
    keep = a != b
    hi, lo = np.maximum(a, b)[keep], np.minimum(a, b)[keep]
    pairs = np.stack([topo[hi], topo[lo]], axis=1)
    dup = rank.integers(0, max(len(pairs), 1), draw(st.integers(0, 20)))
    if len(pairs):
        pairs = np.concatenate([pairs, pairs[dup]])
    rank.shuffle(pairs)
    return n, pairs, rank.permutation(n)


@settings(max_examples=60, deadline=None)
@given(dags(), st.sampled_from(["array", "list", "dense"]))
def test_edge_list_lowering_equals_the_dense_oracle(dag, form):
    n, pairs, order = dag
    want = dense_oracle(pairs, n, order)
    if form == "array":
        deps = pairs
    elif form == "list":
        deps = [tuple(p) for p in pairs.tolist()]
    else:
        deps = np.zeros((n, n), dtype=bool)
        deps[pairs[:, 0], pairs[:, 1]] = True
    dst, src = dep_edge_arrays(deps, n, order)
    np.testing.assert_array_equal(dst, want[0])
    np.testing.assert_array_equal(src, want[1])


@pytest.mark.parametrize("deps,match", [
    ([(0, 1), (1, 2), (2, 0)], "cycle"),
    ([(0, 1), (1, 0), (2, 1)], "cycle"),
    (np.array([[0, 1], [1, 2], [2, 1]]), "cycle"),
    (np.array([[False, True, False], [False, False, True],
               [True, False, False]]), "cycle"),
    ([(1, 1)], "self-dependency"),
    (np.eye(3, dtype=bool), "self-dependency"),
    ([(0, 7)], "out of range"),
    ([(-1, 0)], "out of range"),
    (np.zeros((2, 2), dtype=bool), r"expected \(3, 3\)"),
])
def test_bad_dependencies_are_refused(deps, match):
    with pytest.raises(ValueError, match=match):
        dep_edge_arrays(deps, 3, np.arange(3))


def test_an_empty_or_all_false_dependency_set_is_elided():
    for deps in ([], np.zeros((0, 2), np.int32), np.zeros((3, 3), bool)):
        jobs = make_jobset([0, 0, 0], [1, 1, 1], [1, 1, 1], deps=deps)
        assert jobs.dep_dst is None


def test_a_campaign_lowers_in_linear_time_and_memory():
    """104,290 tasks and 248,730 edges: the dense path took minutes and
    tens of GB here; the edge list takes well under 10 s and 200 MB."""
    trace = workflow_to_trace(montage_campaign(seed=7))
    assert len(trace["submit"]) == 104_290 and len(trace["deps"]) == 248_730
    tracemalloc.start()
    try:
        t0 = time.perf_counter()
        jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                           trace["estimate"], deps=trace["deps"],
                           total_nodes=1024)
        took = time.perf_counter() - t0
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert took < 10.0, took
    assert peak < 200e6, peak
    dst = np.asarray(jobs.dep_dst)
    assert (dst[:248_730] < 104_290).all() and (dst[248_730:] == 104_290).all()
    assert (np.diff(dst[:248_730]) >= 0).all()


def test_lowering_counts_its_edges_and_time():
    pairs = [(1, 0), (2, 0), (2, 1), (2, 1)]        # one pair repeated
    before = obs.counters()
    t0 = time.perf_counter()
    make_jobset([0, 0, 0], [1, 1, 1], [1, 1, 1], deps=pairs)
    took_us = (time.perf_counter() - t0) * 1e6
    after = obs.counters()
    assert after["deps.edges"] - before.get("deps.edges", 0) == 3
    spent = after["deps.lower_us"] - before.get("deps.lower_us", 0)
    assert 0 <= spent <= took_us
    make_jobset([0, 0, 0], [1, 1, 1], [1, 1, 1])    # no deps: no move
    assert obs.counters()["deps.edges"] == after["deps.edges"]
