"""Generic multi-axis scenario sweeps (DESIGN.md §12.2).

``sweep(scenario, axes={...})`` expands a cartesian grid of dotted-path
axes over a base :class:`Scenario` and runs every point with as few
compiled executables as possible:

1. every grid point becomes a scenario via ``Scenario.with_``;
2. points are partitioned into *static buckets* — everything that changes
   compiled shapes (topology, trace shape, capacity, ``max_events``,
   multicluster settings, and ``total_nodes`` when a topology pins the
   machine) keys the bucket;
3. within a bucket the remaining axes (``policy``, ``alloc``,
   ``contention``, ``total_nodes``, ``trace.seed``) are *data*: job tables
   are stacked (workflow dependency edge lists included — a DAG's *shape*
   is static but its edges are ordinary vmap leaves; ``stack_jobsets`` pads
   ragged edge counts to one shape), scalar knobs become i32[B] arrays,
   contention pytrees are leaf-stacked, and ONE ``vmap``-ped executable
   runs the whole bucket — optionally sharded over a 1-D device mesh.
   When every point in a bucket shares one ``policy`` (and, with a
   machine, one ``alloc``) the shared value is passed *statically* so the
   batched executable gets the engine's trace-time specialization —
   including the §14/§18 batched scheduling passes; a mixed policy axis
   keeps the fully-dynamic path, whose backfill cost under vmap is pinned
   by the lazy full-sort guard in ``policies.backfill_shadow``
   (DESIGN.md §18);
4. the batched outputs are re-sliced into per-point :class:`Result`\\ s in
   grid order: one unstack program per output leaf (per 100 lanes), each
   lane's device-to-host copy started at once.

This replaces ``simulate_alloc_sweep`` (an alloc-only special case,
regression-tested bit-exact in ``tests/test_api.py``) and every
hand-rolled benchmark loop, and it expresses grids no legacy entry point
could — e.g. policy × alloc × contention in one call.
"""

from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Any, Dict, Iterator, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import alloc as _alloc
from repro import obs
from repro.core import engine
from repro.core.jobs import JobSet
from repro.core.parallel import stack_jobsets

from repro.api.result import Result
from repro.api.run import build_jobset, run
from repro.api.scenario import Scenario


def _static_key(scenario: Scenario) -> tuple:
    """Hashable compile-bucket key: everything that forces a recompile.

    A failure model contributes only its padded capacity: the failure
    *arrays* are ordinary vmap leaves (materialization is host-side per
    scenario and no compiled shape depends on ``total_nodes`` without a
    topology), so MTBF / checkpoint / requeue — and ``total_nodes`` in
    scalar-counter mode — batch into one executable (DESIGN.md §15).
    """
    tn: Any = None
    if scenario.topology is not None or scenario.multicluster is not None:
        tn = scenario.total_nodes  # pins machine / cluster shapes
    return (
        tuple(t.static_key() for t in scenario.trace_specs()),
        scenario.topology,
        tn,
        scenario.multicluster,
        scenario.capacity,
        scenario.max_events,
        None if scenario.failures is None else scenario.failures.static_key(),
        # the width-range / mode / tick-capacity shapes; curve kind and
        # parameters are plan data (vmap leaves), so a speedup-curve grid
        # stays in one bucket (DESIGN.md §17)
        None if scenario.malleable is None
        else scenario.malleable.static_key(),
    )


@dataclasses.dataclass
class SweepResult:
    """Grid-ordered sweep outcome.

    ``points[i]`` is the axis-value dict of grid point *i* and
    ``results[i]`` its :class:`Result`; iteration yields ``(point,
    result)`` pairs.  ``summaries()`` flattens to a list of plain dicts
    (axis values + scalar metrics) ready for CSV emission, and
    ``stack(field)`` restacks one per-job array across the whole grid.
    ``n_compiles`` reports how many static buckets (≈ executables) the
    sweep needed.
    """

    axes: Dict[str, List[Any]]
    points: List[Dict[str, Any]]
    results: List[Result]
    n_compiles: int

    def __len__(self) -> int:
        return len(self.points)

    def __iter__(self) -> Iterator[Tuple[Dict[str, Any], Result]]:
        return iter(zip(self.points, self.results))

    def __getitem__(self, i: int) -> Result:
        return self.results[i]

    def get(self, **coords) -> Result:
        """The unique result whose point matches every given axis value."""
        hits = [r for p, r in self if all(p[k] == v for k, v in coords.items())]
        if len(hits) != 1:
            raise KeyError(f"{coords} matches {len(hits)} grid points")
        return hits[0]

    def summaries(self) -> List[Dict[str, Any]]:
        return [{**p, **r.summary()} for p, r in self]

    def stack(self, field: str) -> np.ndarray:
        return np.stack([r.to_np()[field] for r in self.results])


def sweep(scenario: Scenario, axes: Dict[str, Sequence[Any]], *,
          mesh: Optional[Mesh] = None) -> SweepResult:
    """Run the cartesian grid of ``axes`` over ``scenario`` (module doc).

    ``axes`` maps dotted scenario paths to value sequences, e.g.::

        sweep(s, axes={"policy": ("fcfs", "backfill"),
                       "alloc": ("simple", "topo"),
                       "contention": (None, (1, 5))})

    With ``mesh`` (1-D device mesh) each batched bucket is padded to the
    device count and sharded, devices advancing their grid shards fully
    independently.
    """
    axes = {k: list(v) for k, v in axes.items()}
    if not axes:
        return SweepResult(axes={}, points=[{}], results=[run(scenario)],
                           n_compiles=1)
    names = list(axes)
    points = [dict(zip(names, combo))
              for combo in itertools.product(*axes.values())]

    buckets: Dict[tuple, List[int]] = {}
    scenarios: List[Scenario] = []
    for i, point in enumerate(points):
        scn = scenario.with_(**point)
        scenarios.append(scn)
        buckets.setdefault(_static_key(scn), []).append(i)

    results: List[Optional[Result]] = [None] * len(points)
    for indices in buckets.values():
        bucket = [scenarios[i] for i in indices]
        if bucket[0].multicluster is not None:
            # every multicluster knob is static: one executable per point
            for i, scn in zip(indices, bucket):
                results[i] = run(scn)
        else:
            for i, res in zip(indices, _run_bucket(bucket, mesh)):
                results[i] = res
    return SweepResult(axes=axes, points=points, results=results,
                       n_compiles=len(buckets))


# ---------------------------------------------------------------------------
# one compiled executable per static bucket
# ---------------------------------------------------------------------------

# The batched runners are cached at module level so jit's executable cache
# (keyed on function identity + argument shapes) survives across sweep()
# calls: re-running the same grid costs milliseconds, not a recompile.  The
# machine is a runtime pytree argument, so one cached function serves every
# topology of a given shape; distinct shapes retrace automatically.


# ---------------------------------------------------------------------------
# public cache statistics (DESIGN.md §20)
# ---------------------------------------------------------------------------

# The what-if query service (repro.service) promises that repeated queries
# against one scenario bucket pay the XLA compile exactly once.  That
# contract needs to be *assertable*, so every `_run_bucket` execution is
# logged against its compile signature — the `_bucket_fn` cache key plus
# the batched argument treedef and leaf shapes/dtypes, i.e. exactly what
# determines whether jit reuses an executable or compiles a new one.  A
# signature seen before counts as a `hit` (warm), a new one as a `compile`
# (cold); both are counters of `repro.obs` (`sweep.compiles`,
# `sweep.hits`).  Stats cover the batched bucket runners only:
# `sweep(s, axes={})` degenerates to `run()` and multicluster buckets run
# point-wise, neither of which goes through the shared executable cache.

_SEEN_SIGNATURES: set = set()


@dataclasses.dataclass(frozen=True)
class SweepCacheStats:
    """Warm-vs-cold executable counters for the shared sweep bucket cache.

    ``compiles`` counts bucket executions whose compile signature had not
    been seen since the last ``reset_cache_stats(clear=True)`` (cold path:
    trace + XLA compile); ``hits`` counts executions that reused a known
    signature (warm path: milliseconds).  ``entries`` is the number of
    distinct live signatures.
    """

    compiles: int
    hits: int
    entries: int


def cache_stats() -> SweepCacheStats:
    """Current warm-vs-cold counters for the sweep executable cache."""
    c = obs.counters()
    return SweepCacheStats(compiles=c.get("sweep.compiles", 0),
                           hits=c.get("sweep.hits", 0),
                           entries=len(_SEEN_SIGNATURES))


def reset_cache_stats(*, clear: bool = False) -> None:
    """Zero the warm/cold counters.

    With ``clear=False`` (default) the cached bucket runners — and the
    signature set that marks them warm — survive, so subsequent reuse still
    counts as hits; this is how a long-running service zeroes per-query
    deltas.  ``clear=True`` additionally drops the cached runner functions
    (``_bucket_fn.cache_clear()``) and the signature set, so the next query
    genuinely recompiles — the cold-path fixture for benchmarks and tests.
    """
    obs.reset_counters("sweep.compiles", "sweep.hits")
    if clear:
        _SEEN_SIGNATURES.clear()
        _bucket_fn.cache_clear()


def _log_bucket_execution(fn_key: tuple, args: tuple, machine) -> bool:
    """Count one bucket execution; True when its signature is new."""
    leaves, treedef = jax.tree.flatten((args, machine))
    sig = (fn_key, str(treedef),
           tuple((tuple(np.shape(leaf)),
                  np.dtype(getattr(leaf, "dtype",
                                   np.asarray(leaf).dtype)).str)
                 for leaf in leaves))
    cold = sig not in _SEEN_SIGNATURES
    _SEEN_SIGNATURES.add(sig)
    obs.count("sweep.compiles" if cold else "sweep.hits")
    return cold


@functools.lru_cache(maxsize=None)
def _bucket_fn(with_alloc: bool, with_fail: bool, with_svc: bool,
               with_mal: bool, max_events: Optional[int],
               mesh: Optional[Mesh], axis: Optional[str],
               static_policy: Optional[int] = None,
               static_alloc: Optional[int] = None):
    # one generic batched runner: the optional subsystem args ride behind
    # (jobs, policy, total_nodes) in a fixed order — alloc pair, fail ctx,
    # svc ctx, mal ctx — and the machine (a non-batched pytree) comes last.
    # A bucket whose points all share one policy (or alloc) passes it here
    # as a Python int instead of a batched leaf: the engine then resolves
    # its static hints at trace time and the whole bucket runs the
    # specialized executable, batched scheduling passes included.
    def fn(*args):
        if with_alloc:
            *batched, machine = args
        else:
            batched, machine = args, None

        def one(*leaves):
            it = iter(leaves)
            j = next(it)
            p = static_policy if static_policy is not None else next(it)
            t = next(it)
            kw = {}
            if with_alloc:
                kw["alloc"] = (static_alloc if static_alloc is not None
                               else next(it))
                kw["contention"] = next(it)
            if with_fail:
                kw["failures"] = next(it)
            if with_svc:
                kw["service"] = next(it)
            if with_mal:
                kw["malleable"] = next(it)
            return engine.simulate(j, p, t, machine=machine,
                                   max_events=max_events, **kw)

        return jax.vmap(one)(*batched)

    if mesh is None:
        return jax.jit(fn)
    # a single prefix sharding applies the batch-axis partition to every
    # output leaf (all leaves carry the leading B dim after vmap)
    return jax.jit(fn, out_shardings=NamedSharding(mesh, P(axis)))


def _bucket_program(bucket: List[Scenario], mesh: Optional[Mesh]):
    """The executable key and host arguments of one static bucket.

    Returns ``(fn_key, args, machine, jobsets)``: ``_bucket_fn(*fn_key)``
    is the batched runner, called as ``fn(*args)`` or ``fn(*args,
    machine)``; ``jobsets[i]`` is point *i*'s job table.  Split from
    ``_run_bucket`` so the same program can be lowered from shapes alone.
    """
    base = bucket[0]
    machine = base.topology.build() if base.topology is not None else None
    max_events = base.max_events

    jobs_cache: Dict[tuple, JobSet] = {}
    jobsets = []
    for scn in bucket:
        spec = scn.trace_specs()[0]
        # key on the full spec (all specs are hashable; ArrayTrace by
        # identity): two points sharing a static bucket may still differ
        # in trace *data* — seed, arrival rate, class mix — and must not
        # collide onto one job table
        key = (spec, int(scn.total_nodes))
        if key not in jobs_cache:
            jobs_cache[key] = build_jobset(scn)
        jobsets.append(jobs_cache[key])

    B = len(bucket)
    # a policy (or alloc) uniform across the bucket is hoisted out of the
    # batched leaves and baked into the executable as a static hint — this
    # is what routes a backfill sweep axis onto the §18 batched pass
    pol_ids = [engine.policies_id(s.policy) for s in bucket]
    static_pol: Optional[int] = pol_ids[0] if len(set(pol_ids)) == 1 else None
    pol_b = jnp.asarray(pol_ids, dtype=jnp.int32)
    tn_b = jnp.asarray([int(s.total_nodes) for s in bucket], dtype=jnp.int32)

    pad = 0
    if mesh is not None:
        D = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
        pad = (-B) % D
        jobsets += [jobsets[-1]] * pad
        pol_b = jnp.concatenate([pol_b, jnp.repeat(pol_b[-1:], pad)])
        tn_b = jnp.concatenate([tn_b, jnp.repeat(tn_b[-1:], pad)])
    jobs_b = stack_jobsets(jobsets)

    pol_args = () if static_pol is not None else (pol_b,)
    static_alloc: Optional[int] = None
    if machine is None:
        args = (jobs_b, *pol_args, tn_b)
    else:
        alloc_ids = [
            _alloc.canonical_id(s.alloc if s.alloc is not None else "simple")
            for s in bucket]
        if len(set(alloc_ids)) == 1:
            static_alloc = alloc_ids[0]
        alloc_b = jnp.asarray(alloc_ids + [0] * pad, dtype=jnp.int32)
        alloc_args = () if static_alloc is not None else (alloc_b,)
        con_b = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *([_alloc.Contention.canonical(s.contention) for s in bucket]
              + [_alloc.Contention.off()] * pad))
        args = (jobs_b, *pol_args, tn_b, *alloc_args, con_b)

    with_fail = base.failures is not None
    if with_fail:
        # per-point materialized streams stack into ordinary vmap leaves
        # (uniform shapes: max_failures is part of the static bucket key)
        from repro.reliability import make_fail_ctx

        fctxs = [make_fail_ctx(s.failures, n_nodes=int(s.total_nodes))
                 for s in bucket]
        fctxs += [fctxs[-1]] * pad
        args = args + (jax.tree.map(lambda *xs: jnp.stack(xs), *fctxs),)

    with_svc = hasattr(base.trace_specs()[0], "plan")
    if with_svc:
        # materialized serving plans stack into ordinary vmap leaves
        # (uniform shapes: max_jobs / max_ticks key the static bucket), so
        # a rate × mix × threshold grid is ONE executable (DESIGN.md §16)
        from repro.serving import make_svc_ctx

        sctxs = [make_svc_ctx(s.trace_specs()[0].plan(),
                              n_nodes=int(s.total_nodes)) for s in bucket]
        sctxs += [sctxs[-1]] * pad
        args = args + (jax.tree.map(lambda *xs: jnp.stack(xs), *sctxs),)

    with_mal = base.malleable is not None
    if with_mal:
        # materialized width/dilation tables stack into ordinary vmap
        # leaves (uniform shapes: the width range and tick capacity key
        # the static bucket), so a speedup-curve / threshold grid is ONE
        # executable (DESIGN.md §17)
        from repro.api.run import _mal_plan
        from repro.malleable import make_mal_ctx

        mctxs = [make_mal_ctx(_mal_plan(s)) for s in bucket]
        mctxs += [mctxs[-1]] * pad
        args = args + (jax.tree.map(lambda *xs: jnp.stack(xs), *mctxs),)

    axis = mesh.axis_names[0] if mesh is not None else None
    fn_key = (machine is not None, with_fail, with_svc, with_mal,
              max_events, mesh, axis, static_pol, static_alloc)
    return fn_key, args, machine, jobsets


def _run_bucket(bucket: List[Scenario], mesh: Optional[Mesh]) -> List[Result]:
    """vmap-batch all scenarios of one static bucket (single-cluster only).

    Host spans: ``sweep.prepare`` (job tables, contexts, stacking),
    ``sweep.dispatch`` (signature log and the jitted call; a cold
    signature compiles here) and ``sweep.split`` (per-lane results,
    :func:`_split_lanes`)."""
    lanes = len(bucket)
    obs.count("sweep.buckets")
    obs.count("sweep.lanes", lanes)
    with obs.span("sweep.prepare", lanes=lanes):
        fn_key, args, machine, jobsets = _bucket_program(bucket, mesh)
    with obs.span("sweep.dispatch", lanes=lanes) as sp:
        cold = _log_bucket_execution(fn_key, args, machine)
        sp.set_metadata(cold=int(cold))
        fn = _bucket_fn(*fn_key)
        if mesh is not None:
            axis = mesh.axis_names[0]
            shard = NamedSharding(mesh, P(axis))
            args = tuple(jax.device_put(a, shard) for a in args)
        batched = fn(*args) if machine is None else fn(*args, machine)

    with obs.span("sweep.split", lanes=lanes):
        raws = _split_lanes(batched, lanes)
        return [Result(scenario=scn, backend="jax", raw=raws[i],
                       jobs=jobsets[i])
                for i, scn in enumerate(bucket)]


_UNSTACK_ROWS = 100     # outputs of one unstack program at most


@functools.partial(jax.jit, static_argnums=2)
def _unstack(x: jax.Array, start: int, rows: int) -> List[jax.Array]:
    # ``rows`` rows of ``x`` from ``start``: one program per leaf shape,
    # whatever the bucket's size, so the bucket's warm-up compiles it
    block = lax.dynamic_slice_in_dim(x, start, rows)
    return [lax.index_in_dim(block, i, keepdims=False) for i in range(rows)]


def _rows(x: jax.Array) -> List[jax.Array]:
    """Every row of ``x``, at most ``_UNSTACK_ROWS`` a program; the last
    block ends at the last row, so every block has one shape."""
    n = x.shape[0]
    rows = min(n, _UNSTACK_ROWS)
    out: List[jax.Array] = []
    for lo in range(0, n, rows):
        start = min(lo, n - rows)
        out += _unstack(x, start, rows)[lo - start:]
    return out


def _split_lanes(batched: Any, lanes: int) -> List[Any]:
    """The first ``lanes`` lanes of a batched result, one pytree each, with
    every leaf's device-to-host copy already started.  Each leaf is cut by
    unstack programs (counter ``sweep.split.unstacked``, in leaves), on one
    device or sharded over a mesh alike."""
    leaves, treedef = jax.tree.flatten(batched)
    obs.count("sweep.split.unstacked", len(leaves))
    cols = [_rows(a)[:lanes] for a in leaves]
    for col in cols:
        for a in col:
            a.copy_to_host_async()
    return [treedef.unflatten([col[i] for col in cols])
            for i in range(lanes)]
