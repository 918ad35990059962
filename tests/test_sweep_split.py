"""How a sweep bucket's batched result reaches its lanes (DESIGN.md §12.2).

- for every lane of a bucket, the one-unstack-per-leaf split gives the
  same ``to_np()`` as slicing each leaf lane by lane, on every kind of
  bucket (failure, serving and malleable columns included);
- the unstack gives every row of a leaf, at most 100 rows a program and
  one program per leaf shape, whatever the number of lanes;
- the split counter counts the bucket's leaves;
- ``to_np()`` still derives from ``Result.raw``: a raw replaced after the
  bucket returns is what the host reads.
"""

import dataclasses
import itertools

import jax
import numpy as np
import pytest

from repro import obs
from repro.api import (
    AutoscalePolicy, MalleableModel, Scenario, ServiceClass, ServiceTrace,
    SyntheticTrace,
)
from repro.api.result import Result
from repro.api.sweep import (
    _UNSTACK_ROWS, _bucket_fn, _bucket_program, _rows, _run_bucket,
    _static_key, _unstack,
)

from test_obs import MIXED_AXES, machine_scenario, scalar_scenario


def serving_scenario():
    classes = (ServiceClass("small", nodes=1, mean_runtime=30, slo_wait=40),
               ServiceClass("big", nodes=4, mean_runtime=120,
                            dist="exponential", slo_wait=200, weight=0.3))
    scaler = AutoscalePolicy(up_threshold=6, down_threshold=1, min_nodes=4,
                             max_nodes=16, step=2, interval=50, max_ticks=32)
    return Scenario(trace=ServiceTrace(rate=0.06, classes=classes,
                                       autoscale=scaler, horizon=1000,
                                       seed=7, max_jobs=96),
                    total_nodes=16, policy="fcfs")


def malleable_scenario():
    mal = MalleableModel(curve="power", param=0.7, min_width=1, max_width=8,
                         mode="elastic", interval=30, max_ticks=32,
                         shrink_threshold=8, grow_threshold=2, step=2)
    return Scenario(trace=SyntheticTrace(n_jobs=40, seed=0, congest=4),
                    total_nodes=32, policy="backfill", malleable=mal)


# (scenario, axes, a column only that kind of bucket carries)
BUCKETS = {
    "machine_failures": (machine_scenario, MIXED_AXES, "n_restarts"),
    "scalar": (scalar_scenario,
               {"policy": ("fcfs", "sjf", "backfill")}, "wait"),
    "serving": (serving_scenario,
                {"trace.rate": (0.03, 0.07), "trace.seed": (0, 1)},
                "slo_met"),
    "malleable": (malleable_scenario,
                  {"malleable.curve": ("amdahl", "power"),
                   "malleable.param": (0.2, 0.5)}, "mal_width"),
}


def one_bucket(kind):
    make, axes, _ = BUCKETS[kind]
    base = make()
    bucket = [base.with_(**dict(zip(axes, combo)))
              for combo in itertools.product(*axes.values())]
    assert len({_static_key(s) for s in bucket}) == 1
    return bucket


def sliced_lanes(bucket):
    """Each lane of the bucket, its leaves sliced one by one."""
    fn_key, args, machine, jobsets = _bucket_program(bucket, None)
    fn = _bucket_fn(*fn_key)
    batched = fn(*args) if machine is None else fn(*args, machine)
    return [Result(scenario=scn, backend="jax",
                   raw=jax.tree.map(lambda a, i=i: a[i], batched),
                   jobs=jobsets[i])
            for i, scn in enumerate(bucket)]


@pytest.mark.parametrize("kind", list(BUCKETS))
def test_split_matches_lane_slices(kind):
    bucket = one_bucket(kind)
    new, old = _run_bucket(bucket, None), sliced_lanes(bucket)
    assert len(new) == len(old) == len(bucket)
    for a, b in zip(new, old):
        x, y = a.to_np(), b.to_np()
        assert BUCKETS[kind][2] in x
        assert set(x) == set(y)
        for key in x:
            assert np.asarray(x[key]).dtype == np.asarray(y[key]).dtype, key
            assert np.array_equal(x[key], y[key]), key
        assert a.summary() == b.summary()


@pytest.mark.parametrize("n", [1, 7, _UNSTACK_ROWS, _UNSTACK_ROWS + 1,
                               2 * _UNSTACK_ROWS + 50])
def test_unstack_rows_are_the_rows_in_bounded_programs(n):
    x = jax.numpy.arange(n * 6, dtype=jax.numpy.int32).reshape(n, 3, 2) - 5
    before = _unstack._cache_size()
    rows = _rows(x)
    assert len(rows) == n
    for i, r in enumerate(rows):
        assert r.shape == (3, 2) and r.dtype == x.dtype
        assert np.array_equal(np.asarray(r), np.asarray(x)[i])
    # one program of at most _UNSTACK_ROWS outputs per leaf shape
    assert _unstack._cache_size() - before <= 1
    _rows(x + 1)
    assert _unstack._cache_size() - before <= 1


def test_split_counter_counts_the_bucket_leaves():
    bucket = one_bucket("scalar")
    before = obs.counters().get("sweep.split.unstacked", 0)
    out = _run_bucket(bucket, None)
    after = obs.counters().get("sweep.split.unstacked", 0)
    leaves = len(jax.tree.leaves(out[0].raw))
    assert leaves > 0
    assert after - before == leaves


def test_raw_replaced_after_the_bucket_is_what_to_np_reads():
    bucket = one_bucket("machine_failures")
    out = _run_bucket(bucket, None)
    r = out[0]
    finish0 = int(np.asarray(r.raw.finish)[0])
    r.raw = dataclasses.replace(r.raw, finish=r.raw.finish.at[0].add(1))
    assert int(r.to_np()["finish"][0]) == finish0 + 1
    assert int(out[1].to_np()["finish"][0]) == \
        int(np.asarray(out[1].raw.finish)[0])
