"""Declarative experiment specs (DESIGN.md §12.1).

A :class:`Scenario` is a frozen, host-side description of ONE simulation:
where the jobs come from (`trace` — synthetic generators, SWF logs,
explicit arrays, or a :class:`WorkflowTrace` DAG scheduled onto the
cluster), what machine runs them (`total_nodes` plus an optional
:class:`Topology`), how they are scheduled (`policy`, `alloc`,
`contention`), and whether the run is partitioned into
conservatively-synchronized clusters (`multicluster`).  Specs carry no
device arrays — they are cheap to construct, compare, copy and sweep, and
the same spec drives both the JAX engine (``repro.api.run``) and the
host reference simulator (``repro.api.run_ref``) for bit-exact validation.

Sweepable fields split into two classes (DESIGN.md §12.2):

- *traced* — ``policy``, ``alloc``, ``contention``, ``total_nodes`` (when no
  topology pins the machine size) and ``trace.seed``: batched with ``vmap``,
  one executable serves every value;
- *static* — the topology, trace shape (``n_jobs``/source), ``capacity``,
  ``max_events`` and every multicluster setting: each distinct combination
  compiles its own executable.

``Scenario.with_(...)`` applies dotted-path overrides (``"trace.seed"``),
which is how ``repro.api.sweep`` expands an axis grid into scenario points.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple, Union

import numpy as np

from repro import alloc as _alloc
from repro import obs
from repro.core.jobs import INF_TIME
from repro.malleable import MalleableModel
from repro.reliability import FailureModel
from repro.serving import ServiceTrace
from repro.traces import das2_like, load_swf, sdsc_sp2_like, synthetic_trace
from repro.traces import workflows as _workflows
from repro.traces.workflows import workflow_to_trace

# ---------------------------------------------------------------------------
# trace sources
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SyntheticTrace:
    """Deterministic synthetic workload (``repro.traces.synthetic``).

    ``kind`` selects the generator: ``"generic"`` (``synthetic_trace``),
    ``"das2"`` or ``"sdsc_sp2"``.  ``params`` are extra keyword arguments
    for the generator as a tuple of (name, value) pairs — a tuple so the
    spec stays hashable (specs key compile-bucket caches).  ``congest``
    divides submit times by an integer factor to densify arrivals (the
    benchmarks' standard trick to make policies diverge).
    """

    n_jobs: int = 1000
    seed: int = 0
    kind: str = "generic"
    params: Tuple[Tuple[str, Any], ...] = ()
    congest: int = 1

    _GENERATORS = {"generic": synthetic_trace, "das2": das2_like,
                   "sdsc_sp2": sdsc_sp2_like}

    def materialize(self) -> Dict[str, np.ndarray]:
        try:
            gen = self._GENERATORS[self.kind]
        except KeyError:
            raise ValueError(
                f"unknown synthetic trace kind {self.kind!r}; "
                f"known: {sorted(self._GENERATORS)}") from None
        trace = gen(self.n_jobs, seed=self.seed, **dict(self.params))
        if self.congest != 1:
            trace["submit"] = trace["submit"] // int(self.congest)
        return trace

    def static_key(self):
        """Everything except ``seed`` — seed is trace *data*, not shape."""
        return ("synthetic", self.n_jobs, self.kind, self.params, self.congest)

    @property
    def n_rows(self) -> int:
        return self.n_jobs


@dataclasses.dataclass(frozen=True)
class SwfTrace:
    """A Standard Workload Format log on disk (optionally gzipped).

    ``strict=True`` makes ingestion raise on the first malformed line
    instead of quarantining it (the lenient default counts bad lines in
    the loader report and keeps going — see ``repro.traces.SwfReport``).
    This spec feeds the one-shot engine, so it keeps the int32 horizon
    guard; full-archive logs that overflow it go through ``repro.replay``
    instead (int64 host clocks, windowed rounds).
    """

    path: str
    max_jobs: Optional[int] = None
    strict: bool = False

    def materialize(self) -> Dict[str, np.ndarray]:
        trace, _report = load_swf(self.path, max_jobs=self.max_jobs,
                                  strict=self.strict)
        # int32 clock-overflow guard (mirrors ServiceTrace.materialize):
        # the engine runs the clock in int32, so the span of the log plus
        # the largest completion must stay below INF_TIME — a silent
        # wraparound would corrupt every downstream metric
        sub = np.asarray(trace["submit"], dtype=np.int64)
        if len(sub):
            run = np.asarray(trace["runtime"], dtype=np.int64)
            est = np.asarray(trace.get("estimate", run), dtype=np.int64)
            top = int(sub.max() - sub.min()) + 2 * int(
                max(run.max(initial=1), est.max(initial=1)))
            if top >= int(INF_TIME):
                raise ValueError(
                    f"SWF trace {self.path!r} overflows int32 clock range: "
                    f"submit span + 2*max runtime = {top} >= {int(INF_TIME)} "
                    "(INF_TIME); trim the log with max_jobs= or rescale "
                    "its time unit")
        return trace

    def static_key(self):
        return ("swf", self.path, self.max_jobs)

    @property
    def n_rows(self) -> Optional[int]:
        return None  # unknown until loaded


@dataclasses.dataclass(frozen=True)
class WorkflowTrace:
    """A workflow DAG scheduled *onto the cluster* (paper §3, DESIGN.md §13).

    ``kind`` selects the ``repro.traces.workflows`` generator: ``"montage"``,
    ``"galactic"`` (Galactic Plane: K montage tiles + merge), ``"sipht"``,
    ``"chain"``, ``"fork_join"``, ``"random"`` (random layered DAG) or
    ``"montage_campaign"`` (mosaics of the published Montage profile at a
    fixed submit gap; its shape, and so J and E, never depend on ``seed``).
    ``params`` are generator keyword arguments as (name, value) pairs —
    e.g. ``(("width", 16),)`` or ``(("tiles", 4), ("width", 8))``.  The DAG
    lowers through ``workflow_to_trace``: tasks become jobs (cpu requirement
    -> node count), edges become the ``JobSet.dep_dst``/``dep_src`` edge
    list (O(E) per vmap leaf, DESIGN.md §14), and every task of one
    workflow shares one ``submit`` time (``submit``, plus the mosaic's
    offset in a campaign) so release order is purely dependency-driven.
    Generation runs in the span ``repro.workflow.materialize`` and counts
    ``workflow.tasks``; the last few DAGs are cached.

    The DAG *shape* (kind/params/submit/priority) is a static recompile
    axis; ``seed`` only perturbs task durations and random edges, so it is
    traced sweep data exactly like ``SyntheticTrace.seed`` (a seed that
    changes the edge *count* is fine — ``stack_jobsets`` pads ragged edge
    lists to one shape inside the sweep bucket).
    ``priority="cpath"`` attaches critical-path priorities for ``preempt``.
    """

    kind: str = "montage"
    seed: int = 0
    params: Tuple[Tuple[str, Any], ...] = ()
    submit: int = 0
    priority: Optional[str] = None

    _GENERATORS = {
        "montage": _workflows.montage_like,
        "galactic": _workflows.galactic_like,
        "sipht": _workflows.sipht_like,
        "chain": _workflows.chain,
        "fork_join": _workflows.fork_join,
        "random": _workflows.random_layered,
        "montage_campaign": _workflows.montage_campaign,
    }
    _SEEDLESS = frozenset({"chain"})

    def materialize(self) -> Dict[str, np.ndarray]:
        # shallow copy of the cached dict: the spec is frozen/hashable, so
        # sweep grids and n_rows don't regenerate (and re-cycle-check) the
        # same DAG per grid point
        return dict(_materialize_workflow(self))

    def static_key(self):
        """Everything except ``seed`` — the DAG's task count and edge-matrix
        shape are fixed by (kind, params), so seed is trace *data*."""
        return ("workflow", self.kind, self.params, self.submit,
                self.priority)

    @property
    def n_rows(self) -> int:
        return len(self.materialize()["submit"])


# a handful of DAGs: a campaign holds 10^5 tasks and 2.5 * 10^5 edges
@functools.lru_cache(maxsize=8)
def _materialize_workflow(spec: WorkflowTrace) -> Dict[str, np.ndarray]:
    try:
        gen = spec._GENERATORS[spec.kind]
    except KeyError:
        raise ValueError(
            f"unknown workflow kind {spec.kind!r}; "
            f"known: {sorted(spec._GENERATORS)}") from None
    kwargs = dict(spec.params)
    if spec.kind not in spec._SEEDLESS:
        kwargs["seed"] = spec.seed
    with obs.span("workflow.materialize") as span:
        trace = workflow_to_trace(gen(**kwargs), submit=spec.submit,
                                  priority=spec.priority)
        span.set_metadata(tasks=len(trace["submit"]))
    obs.count("workflow.tasks", len(trace["submit"]))
    return trace


@dataclasses.dataclass(frozen=True, eq=False)
class ArrayTrace:
    """Explicit host arrays — the escape hatch for custom workloads.

    ``eq=False`` keeps the dataclass hashable by identity: two ArrayTraces
    are the "same trace" for compile-bucketing iff they are the same object.
    ``deps`` (optional (job, dep) pairs or dense bool matrix, input order)
    makes the jobs a workflow (DESIGN.md §13).
    """

    submit: Any
    runtime: Any
    nodes: Any
    estimate: Any = None
    priority: Any = None
    deps: Any = None

    @classmethod
    def from_dict(cls, trace: Dict[str, Any]) -> "ArrayTrace":
        return cls(submit=trace["submit"], runtime=trace["runtime"],
                   nodes=trace["nodes"], estimate=trace.get("estimate"),
                   priority=trace.get("priority"), deps=trace.get("deps"))

    def materialize(self) -> Dict[str, np.ndarray]:
        out = {"submit": np.asarray(self.submit),
               "runtime": np.asarray(self.runtime),
               "nodes": np.asarray(self.nodes)}
        if self.estimate is not None:
            out["estimate"] = np.asarray(self.estimate)
        if self.priority is not None:
            out["priority"] = np.asarray(self.priority)
        if self.deps is not None:
            out["deps"] = self.deps
        return out

    def static_key(self):
        return ("arrays", id(self))

    @property
    def n_rows(self) -> int:
        return len(np.asarray(self.submit))


@dataclasses.dataclass(frozen=True)
class InjectedTrace:
    """A base trace spec plus appended *candidate* jobs (DESIGN.md §20).

    The what-if service's "where should this job run" queries need to add
    a hypothetical job to an existing workload without tearing the sweep
    compile cache: the injected job *values* are trace data (vmap leaves),
    and only the injected job *count* changes compiled shapes.
    ``static_key`` is therefore ``(base static key, len(jobs))`` — every
    placement query against the same base workload with the same number of
    candidates reuses one cached executable.

    ``jobs`` is a tuple of ``(submit, runtime, nodes, estimate, priority)``
    tuples (``estimate``/``priority`` may be None); rows are appended after
    the base trace in input order, so with equal submit times the candidate
    sorts *behind* every incumbent — a what-if query never jumps the queue.
    Base dependency edges (pair lists or dense matrices) are preserved;
    injected jobs are always dependency-free.
    """

    base: Any                    # a TraceSpec (not ServiceTrace)
    jobs: Tuple[Tuple[Optional[int], ...], ...]

    def __post_init__(self):
        base = as_trace_spec(self.base)
        if isinstance(base, ServiceTrace):
            raise ValueError(
                "InjectedTrace cannot wrap a ServiceTrace: open-arrival "
                "plans carry their own padded job table (inject the "
                "candidate through ServiceTrace.arrivals instead)")
        object.__setattr__(self, "base", base)
        norm = []
        for j in self.jobs:
            j = tuple(j) + (None,) * (5 - len(j))
            if len(j) != 5:
                raise ValueError(
                    "injected jobs are (submit, runtime, nodes[, estimate"
                    f"[, priority]]) tuples; got {j!r}")
            submit, runtime, nodes = (int(j[0]), int(j[1]), int(j[2]))
            if runtime < 1 or nodes < 1:
                raise ValueError(
                    f"injected job needs runtime >= 1 and nodes >= 1; "
                    f"got runtime={runtime}, nodes={nodes}")
            if submit < 0:
                raise ValueError(
                    f"injected job submit must be >= 0, got {submit} "
                    "(make_jobset re-zeroes the trace on its minimum "
                    "submit; an earlier candidate would shift every "
                    "incumbent timestamp)")
            est = None if j[3] is None else int(j[3])
            pri = None if j[4] is None else int(j[4])
            norm.append((submit, runtime, nodes, est, pri))
        if not norm:
            raise ValueError("InjectedTrace needs at least one injected job")
        object.__setattr__(self, "jobs", tuple(norm))

    def materialize(self) -> Dict[str, np.ndarray]:
        t = dict(self.base.materialize())
        k = len(self.jobs)
        sub = np.asarray(t["submit"], dtype=np.int64)
        run = np.asarray(t["runtime"], dtype=np.int64)
        j_sub = np.asarray([j[0] for j in self.jobs], dtype=np.int64)
        j_run = np.asarray([j[1] for j in self.jobs], dtype=np.int64)
        j_nod = np.asarray([j[2] for j in self.jobs], dtype=np.int64)
        out = {
            "submit": np.concatenate([sub, j_sub]),
            "runtime": np.concatenate([run, j_run]),
            "nodes": np.concatenate(
                [np.asarray(t["nodes"], dtype=np.int64), j_nod]),
        }
        # optional columns exist iff the base carries them OR an injected
        # job sets them; the base default mirrors make_jobset (estimate ==
        # runtime, priority == 0)
        j_est = [j[3] for j in self.jobs]
        if "estimate" in t or any(e is not None for e in j_est):
            base_est = np.asarray(t.get("estimate", run), dtype=np.int64)
            inj = np.asarray(
                [e if e is not None else r
                 for e, r in zip(j_est, j_run)], dtype=np.int64)
            out["estimate"] = np.concatenate([base_est, inj])
        j_pri = [j[4] for j in self.jobs]
        if "priority" in t or any(p is not None for p in j_pri):
            base_pri = np.asarray(
                t.get("priority", np.zeros(len(sub))), dtype=np.int64)
            inj = np.asarray([p if p is not None else 0 for p in j_pri],
                             dtype=np.int64)
            out["priority"] = np.concatenate([base_pri, inj])
        deps = t.get("deps")
        if deps is not None:
            dm = np.asarray(deps)
            if dm.ndim == 2 and dm.dtype == bool:
                # dense matrix: pad k all-False rows/cols (injected jobs
                # neither depend on nor release anything)
                n = dm.shape[0]
                padded = np.zeros((n + k, n + k), dtype=bool)
                padded[:n, :n] = dm
                out["deps"] = padded
            else:
                # (job, dependency) pairs index the base's input order,
                # which appending at the tail leaves untouched
                out["deps"] = deps
        return out

    def static_key(self):
        """Base key + injected COUNT: the candidate jobs' values are vmap
        data, only how many rows they add is a compiled shape."""
        return ("inject", self.base.static_key(), len(self.jobs))

    @property
    def n_rows(self) -> Optional[int]:
        base = self.base.n_rows
        return None if base is None else base + len(self.jobs)


TraceSpec = Union[SyntheticTrace, SwfTrace, ArrayTrace, WorkflowTrace,
                  ServiceTrace, InjectedTrace]


def as_trace_spec(trace) -> TraceSpec:
    """Accept a spec, a plain dict-of-arrays, or an .swf path string."""
    if isinstance(trace, (SyntheticTrace, SwfTrace, ArrayTrace,
                          WorkflowTrace, ServiceTrace, InjectedTrace)):
        return trace
    if isinstance(trace, dict):
        return ArrayTrace.from_dict(trace)
    if isinstance(trace, str):
        return SwfTrace(trace)
    raise TypeError(
        f"trace must be a trace spec, dict of arrays, or .swf path; "
        f"got {type(trace).__name__}")


# ---------------------------------------------------------------------------
# machine topology
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Topology:
    """Declarative machine shape; builds a ``repro.alloc.Machine`` on demand.

    ``kind`` ∈ {"linear", "mesh2d", "dragonfly"}; ``shape`` is the builder's
    positional arguments: (n_nodes, group_size), (rows, cols), or
    (n_groups, nodes_per_group) respectively.
    """

    kind: str
    shape: Tuple[int, int]

    @classmethod
    def linear(cls, n_nodes: int, *, group_size: int = 8) -> "Topology":
        return cls("linear", (int(n_nodes), int(group_size)))

    @classmethod
    def mesh2d(cls, rows: int, cols: int) -> "Topology":
        return cls("mesh2d", (int(rows), int(cols)))

    @classmethod
    def dragonfly(cls, n_groups: int, nodes_per_group: int) -> "Topology":
        return cls("dragonfly", (int(n_groups), int(nodes_per_group)))

    @property
    def n_nodes(self) -> int:
        if self.kind == "linear":
            return self.shape[0]
        return self.shape[0] * self.shape[1]

    def build(self) -> _alloc.Machine:
        a, b = self.shape
        if self.kind == "linear":
            return _alloc.linear(a, group_size=b)
        if self.kind == "mesh2d":
            return _alloc.mesh2d(a, b)
        if self.kind == "dragonfly":
            return _alloc.dragonfly(a, b)
        raise ValueError(
            f"unknown topology kind {self.kind!r}; "
            "known: linear, mesh2d, dragonfly")


# ---------------------------------------------------------------------------
# multicluster settings
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class Multicluster:
    """Conservative-window multi-cluster settings (DESIGN.md §2).

    When set on a :class:`Scenario`, ``trace`` must be a tuple of trace
    specs (one per cluster) and ``total_nodes`` is per-cluster (one int
    broadcast to all clusters, or a tuple).
    """

    window: int
    horizon: Optional[int] = None   # None: derived from max submit time
    migrate: bool = True
    max_export: int = 8
    latency: Optional[int] = None   # None: == window (minimum conservative)
    load_imbalance_threshold: float = 1.5


# ---------------------------------------------------------------------------
# the scenario itself
# ---------------------------------------------------------------------------

# dotted axis paths vmap-batched by repro.api.sweep; everything else forces
# a recompile bucket ("total_nodes" moves to static when a topology pins the
# machine size — see sweep._static_key).  Every FailureModel field except
# max_failures (the padded capacity, a compiled shape) is trace data: the
# materialized failure arrays are ordinary vmap leaves, so an MTBF /
# checkpoint / requeue grid compiles to ONE executable (DESIGN.md §15).
TRACED_AXES = ("policy", "alloc", "contention", "total_nodes", "trace.seed",
               "failures.mtbf", "failures.seed", "failures.mean_repair",
               "failures.requeue", "failures.checkpoint_interval",
               "failures.restart_overhead",
               # ServiceTrace (DESIGN.md §16): everything except max_jobs
               # and autoscale.max_ticks is trace data, so arrival-rate /
               # horizon / class-mix / autoscale-threshold sweeps compile
               # once per static bucket
               "trace.rate", "trace.horizon", "trace.classes",
               "trace.autoscale",
               # MalleableModel (DESIGN.md §17): the width range and mode
               # fix the dur-table / tick-stream shapes; the curve family
               # and its parameters, the resize cadence and the thresholds
               # are all plan data, so speedup-curve grids compile once
               "malleable.curve", "malleable.param", "malleable.table",
               "malleable.interval", "malleable.step",
               "malleable.shrink_threshold", "malleable.grow_threshold")


@dataclasses.dataclass(frozen=True)
class Scenario:
    """One fully-specified experiment (see module docstring).

    ``total_nodes=None`` with a ``topology`` defaults to the topology's node
    count.  ``alloc``/``contention`` require a ``topology`` (without one the
    engine runs in scalar-counter mode and would silently ignore them —
    ``run`` rejects the combination, mirroring the engine's own check).

    ``failures`` (a frozen ``repro.reliability.FailureModel``) switches on
    reliability-aware simulation (DESIGN.md §15); both engines consume the
    one materialized trace, and ``failures=None`` statically elides the
    whole subsystem.

    ``malleable`` (a frozen ``repro.malleable.MalleableModel``) switches on
    two-level resource management (DESIGN.md §17): moldable width choice at
    dispatch, optionally elastic grow/shrink at capacity ticks, and
    shrink-instead-of-requeue under node failures.  ``malleable=None``
    statically elides the whole subsystem.
    """

    trace: Union[TraceSpec, Dict[str, Any], str, Tuple[TraceSpec, ...]]
    total_nodes: Optional[Union[int, Tuple[int, ...]]] = None
    policy: Union[str, int] = "fcfs"
    topology: Optional[Topology] = None
    alloc: Optional[Union[str, int]] = None
    contention: Optional[Any] = None    # Contention | (num, den) | None
    multicluster: Optional[Multicluster] = None
    capacity: Optional[int] = None
    max_events: Optional[int] = None
    failures: Optional[FailureModel] = None
    malleable: Optional[MalleableModel] = None

    def __post_init__(self):
        if self.malleable is not None:
            if not isinstance(self.malleable, MalleableModel):
                raise TypeError(
                    "Scenario.malleable must be a repro.malleable."
                    f"MalleableModel, got {type(self.malleable).__name__} "
                    "(specs stay frozen/hashable; materialized "
                    "MalleablePlans belong to the engine call, not the "
                    "scenario)")
            if self.multicluster is not None:
                raise ValueError(
                    "malleable jobs are not supported in multicluster "
                    "scenarios yet; simulate the clusters individually")
            if self.contention is not None:
                raise ValueError(
                    "malleable jobs cannot be combined with contention "
                    "dilation: the speedup curve already rescales runtime "
                    "per width, and composing the two dilations is "
                    "undefined (DESIGN.md §17)")
            if self.policy == "preempt":
                raise ValueError(
                    "malleable jobs cannot be combined with the preempt "
                    "policy (width-aware preemption is an open item, "
                    "DESIGN.md §17)")
        if self.failures is not None:
            if not isinstance(self.failures, FailureModel):
                raise TypeError(
                    "Scenario.failures must be a repro.reliability."
                    f"FailureModel, got {type(self.failures).__name__} "
                    "(specs stay frozen/hashable; materialized FailureTraces "
                    "belong to the engine call, not the scenario)")
            if self.multicluster is not None:
                raise ValueError(
                    "failures are not supported in multicluster scenarios "
                    "yet; simulate the clusters individually")
        if self.multicluster is None:
            object.__setattr__(self, "trace", as_trace_spec(self.trace))
        else:
            traces = self.trace
            if not isinstance(traces, (tuple, list)):
                raise ValueError(
                    "multicluster scenarios take one trace spec per cluster "
                    "(a tuple); got a single trace")
            object.__setattr__(
                self, "trace", tuple(as_trace_spec(t) for t in traces))
            if any(isinstance(t, ServiceTrace) for t in self.trace):
                raise ValueError(
                    "ServiceTrace is not supported in multicluster "
                    "scenarios yet; serve each cluster individually")
        if isinstance(self.trace, ServiceTrace):
            if (self.failures is not None and self.topology is not None
                    and self.trace.autoscale is not None):
                raise ValueError(
                    "machine-mode failures cannot be combined with an "
                    "autoscaling ServiceTrace; drop topology=, failures=, "
                    "or autoscale (engine restriction, DESIGN.md §16)")
            if (self.capacity is not None
                    and int(self.capacity) != self.trace.max_jobs):
                raise ValueError(
                    f"capacity={self.capacity} disagrees with "
                    f"ServiceTrace.max_jobs={self.trace.max_jobs}; the "
                    "deadline/class columns are padded to max_jobs, so the "
                    "job table must share that shape")
        if self.topology is None and (self.alloc is not None
                                      or self.contention is not None):
            raise ValueError(
                "alloc/contention require topology=; without a Topology the "
                "simulation runs in scalar-counter mode and would silently "
                "ignore them")
        if self.total_nodes is None:
            if self.topology is None:
                raise ValueError(
                    "total_nodes is required when no topology is given")
            object.__setattr__(self, "total_nodes", self.topology.n_nodes)
        if self.topology is not None and self.multicluster is None \
                and int(self.total_nodes) != self.topology.n_nodes:
            raise ValueError(
                f"topology has {self.topology.n_nodes} nodes but "
                f"total_nodes={self.total_nodes}")

    # -- sweep support ------------------------------------------------------

    def with_(self, **overrides) -> "Scenario":
        """Functional update; keys may be dotted paths into sub-specs,
        e.g. ``with_(policy="sjf", **{"trace.seed": 3})``."""
        flat: Dict[str, Any] = {}
        nested: Dict[str, Dict[str, Any]] = {}
        for key, value in overrides.items():
            if "." in key:
                head, rest = key.split(".", 1)
                nested.setdefault(head, {})[rest] = value
            else:
                flat[key] = value
        for head, sub in nested.items():
            target = flat.get(head, getattr(self, head))
            if target is None:
                raise ValueError(f"cannot set {head}.{next(iter(sub))}: "
                                 f"scenario has no {head}")
            if isinstance(target, tuple):  # per-cluster trace specs
                target = tuple(dataclasses.replace(t, **sub) for t in target)
            else:
                target = dataclasses.replace(target, **sub)
            flat[head] = target
        return dataclasses.replace(self, **flat)

    def trace_specs(self) -> Tuple[TraceSpec, ...]:
        """Per-cluster tuple view of ``trace`` (length 1 without
        multicluster)."""
        return self.trace if isinstance(self.trace, tuple) else (self.trace,)

    def nodes_per_cluster(self) -> Tuple[int, ...]:
        """Per-cluster ``total_nodes`` tuple (length 1 without
        multicluster)."""
        n_clusters = len(self.trace_specs())
        tn = self.total_nodes
        if isinstance(tn, tuple):
            if len(tn) != n_clusters:
                raise ValueError(
                    f"total_nodes tuple has {len(tn)} entries for "
                    f"{n_clusters} clusters")
            return tuple(int(x) for x in tn)
        return (int(tn),) * n_clusters
