"""Job-table data structures for the JAX discrete-event scheduler.

The paper encapsulates each job as a ``TaskEvent`` C++ object moved between
SST components.  On SPMD hardware we keep the whole job table as a
struct-of-arrays pytree (``JobSet``) plus a mutable simulation state
(``SimState``); "moving a job between queues" is a masked state transition.

All times are int32 *relative* seconds (trace loaders normalize so that
``min(submit) == 0`` and ``max(submit) + 2*max(runtime) < 2**30``, which
keeps every ``clock + estimate`` addition overflow-free).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

# Job lifecycle states (paper Fig. 1: submission -> waiting -> running -> done).
PENDING = 0   # submitted to the simulator but its submit time is in the future
WAITING = 1   # in the wait queue
RUNNING = 2   # allocated nodes, executing
DONE = 3      # completed; resources reclaimed

# Sentinel "infinite" time.  Kept well under int32 max so sentinel arithmetic
# (e.g. INF + estimate) cannot wrap.
INF_TIME = np.int32(2**30 - 1)

# Scheduling policies (paper §2.1) + priority preemption (paper §5 lists
# preemption as planned future work; implemented here in both engines).
FCFS = 0
SJF = 1
LJF = 2
BESTFIT = 3
BACKFILL = 4
PREEMPT = 5

POLICY_NAMES = {
    FCFS: "fcfs",
    SJF: "sjf",
    LJF: "ljf",
    BESTFIT: "bestfit",
    BACKFILL: "backfill",
    PREEMPT: "preempt",
}
POLICY_IDS = {v: k for k, v in POLICY_NAMES.items()}


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class JobSet:
    """Immutable struct-of-arrays job table, sorted by (submit, id).

    ``valid`` masks padding rows so fixed-capacity tables can be batched /
    sharded.  ``estimate`` is the user walltime request (drives SJF/LJF
    ordering and EASY reservations); ``runtime`` is the actual duration
    (drives completion events) — mirroring how CQsim treats walltime vs. run
    time.

    ``dep_dst``/``dep_src`` make task dependencies a first-class axis of the
    cluster engine (paper §3, DESIGN.md §13/§14): edge *e* means job
    ``dep_dst[e]`` cannot enter the wait queue until job ``dep_src[e]`` is
    DONE.  The edge list is a *static-shape* padded representation — real
    edges first, padding slots hold the out-of-range index ``capacity`` so
    every scatter (``.at[...]`` with ``mode="drop"``) ignores them — which
    keeps dependency memory at O(E) instead of the dense matrix's O(J²) and
    lets the engine maintain an incremental unmet-dependency counter
    (``SimState.n_unmet``) instead of re-reducing a matrix per event.  Both
    are ``None`` (statically elided — the engine compiles to the exact seed
    path) for plain job traces; being pytree leaves they batch through
    ``vmap`` ensembles and ``sweep()`` like any other job attribute
    (``stack_jobsets`` pads ragged edge counts to one shape).

    ``submit_sorted`` is a static (pytree aux) fact, not a leaf: True when
    ``submit`` is non-decreasing down the rows, so the stable submit-order
    permutation is the identity and row order IS FCFS order.  Only
    ``make_jobset`` sets it (it sorts by (submit, index) and pads with
    ``INF_TIME`` at the tail); every other constructor leaves it False, and
    an edit that moves submits or rows must clear it.  The engine's batched
    scheduling pass reads the queue in row order when it is set (DESIGN.md
    §14).
    """

    submit: jax.Array    # i32[J]
    runtime: jax.Array   # i32[J]  actual duration, >= 1
    estimate: jax.Array  # i32[J]  requested walltime, >= 1
    nodes: jax.Array     # i32[J]  requested nodes, >= 1
    priority: jax.Array  # i32[J]  lower = more important (preempt policy)
    valid: jax.Array     # bool[J]
    dep_dst: jax.Array | None = None  # i32[E] dependent row  (capacity = pad)
    dep_src: jax.Array | None = None  # i32[E] dependency row (capacity = pad)
    submit_sorted: bool = dataclasses.field(default=False,
                                            metadata=dict(static=True))

    @property
    def capacity(self) -> int:
        return self.submit.shape[-1]

    @property
    def edge_capacity(self) -> int:
        """Padded edge-list length (0 when the table carries no edges)."""
        return 0 if self.dep_dst is None else self.dep_dst.shape[-1]

    @property
    def deps(self) -> jax.Array | None:
        """Dense ``bool[J, J]`` reconstruction of the edge list (or ``None``).

        Host-side convenience for tests/metrics on a single (unbatched) job
        table; the engine itself never materializes this matrix.
        """
        if self.dep_dst is None:
            return None
        if self.dep_dst.ndim != 1:
            raise ValueError(
                "JobSet.deps reconstructs the dense matrix for unbatched "
                "tables only; index into the batch dimension first")
        J = self.capacity
        return jnp.zeros((J, J), dtype=bool).at[
            self.dep_dst, self.dep_src].set(True, mode="drop")

    def num_valid(self) -> jax.Array:
        return jnp.sum(self.valid.astype(jnp.int32), axis=-1)


def _check_acyclic(dst: np.ndarray, src: np.ndarray, n: int) -> None:
    """Kahn's algorithm over the edge list (``dst[e]`` depends on
    ``src[e]``), one whole front of sources at a time; raises on cycles.
    O(n + E) work, one NumPy pass per front."""
    indeg = np.bincount(dst, minlength=n)
    by_src = np.argsort(src, kind="stable")
    out_dst = dst[by_src]
    indptr = np.concatenate([[0], np.cumsum(np.bincount(src, minlength=n))])
    front = np.flatnonzero(indeg == 0)
    seen = 0
    while front.size:
        seen += front.size
        lo, hi = indptr[front], indptr[front + 1]
        lens = hi - lo
        # the out-edges of every task of the front, as one index array
        starts = np.repeat(lo - np.concatenate([[0], np.cumsum(lens)[:-1]]),
                           lens)
        heads = out_dst[starts + np.arange(lens.sum())]
        np.subtract.at(indeg, heads, 1)
        front = np.unique(heads[indeg[heads] == 0])
    if seen != n:
        raise ValueError("dependency graph contains a cycle")


def assert_acyclic(deps: np.ndarray) -> None:
    """Raise on a cycle of the dense bool dependency matrix ``deps``
    (``deps[i, j]`` = *i* depends on *j*); used by
    ``repro.core.workflow.make_taskset``."""
    dst, src = np.nonzero(deps)
    _check_acyclic(dst, src, deps.shape[0])


def _dep_pairs(deps, n: int) -> tuple:
    """Validated ``(job, dependency)`` index arrays of ``deps`` (a pair list,
    an ``[E, 2]`` array or a dense matrix, pre-sort indices).

    A bool 2-D array is always a dense matrix (a wrong shape is an error,
    never re-parsed as pairs); other 2-D arrays are a matrix only at the
    exact (n, n) shape, and pairs wherever they have two columns (so an
    ``[E, 2]`` edge array of a two-task table stays pairs).  Only a dense
    matrix costs O(n²); pairs cost O(E).
    """
    if not isinstance(deps, (list, tuple)) and not hasattr(deps, "shape"):
        deps = list(deps)       # any other iterable of pairs
    mat = np.asarray(deps) if not isinstance(deps, (list, tuple)) else None
    is_dense = (mat is not None and mat.ndim == 2 and mat.dtype != object
                and (mat.dtype == bool
                     or (mat.shape == (n, n) and mat.shape[1] != 2)))
    if is_dense:
        if mat.shape != (n, n):
            raise ValueError(
                f"dense deps matrix has shape {mat.shape}, expected ({n}, {n})")
        t, d = np.nonzero(mat)
    else:
        pairs = np.asarray(deps if mat is None else mat,
                           dtype=np.int64).reshape(-1, 2)
        t, d = pairs[:, 0], pairs[:, 1]
        bad = (t < 0) | (t >= n) | (d < 0) | (d >= n)
        if bad.any():
            i = int(np.argmax(bad))
            raise ValueError(
                f"dependency pair ({int(t[i])},{int(d[i])}) out of range")
    if (t == d).any():
        raise ValueError("self-dependency")
    return t, d


# Edge-list pads round up to this multiple so DAGs with nearby edge counts
# share one compiled shape (the differential-test matrix reuses executables).
_EDGE_ALIGN = 64


def dep_edge_arrays(deps, n: int, order: np.ndarray) -> tuple:
    """Normalize ``deps`` to (dst, src) index arrays in *sorted-row*
    coordinates, in (dst, src) lexicographic order, duplicate pairs once.

    O(n + E log E) on pairs: validation, the inverse of the (submit, id)
    sort permutation ``order``, one sort of the packed keys, and Kahn's
    cycle check over the result.  One shared path for ``make_jobset`` and
    ``repro.refsim.ReferenceSimulator.load``, so both engines derive
    bit-identical edge sets from the same input.
    """
    t, d = _dep_pairs(deps, n)
    rank = np.empty(n, dtype=np.int64)
    rank[order] = np.arange(n, dtype=np.int64)
    key = np.unique(rank[t] * n + rank[d])
    dst, src = key // n, key % n
    _check_acyclic(dst, src, n)
    return dst, src


def make_jobset(
    submit,
    runtime,
    nodes,
    estimate=None,
    priority=None,
    *,
    deps=None,
    capacity: int | None = None,
    edge_capacity: int | None = None,
    total_nodes: int | None = None,
) -> JobSet:
    """Build a normalized ``JobSet`` from host arrays.

    - sorts by (submit, original index) so row order == FCFS order, and
      says so in ``submit_sorted`` (padding rows hold ``INF_TIME``, so the
      order holds over the whole padded table),
    - clamps node requests to ``total_nodes`` (paper traces contain requests
      larger than the simulated machine; CQsim clamps the same way),
    - pads to ``capacity`` with invalid rows.

    ``deps`` is either an iterable of ``(job, dependency)`` index pairs (an
    ``[E, 2]`` array among them) or a dense bool matrix, both in *input*
    order (indices into ``submit``); it is cycle-checked, permuted into the
    sorted row order, and lowered to the padded ``dep_dst``/``dep_src`` edge
    list (length rounded up to a multiple of 64, or exactly
    ``edge_capacity`` when given; padding slots hold the out-of-range index
    ``capacity``) in O(J + E) on the host (span ``repro.deps.lower``,
    counters ``deps.edges`` and ``deps.lower_us``).  An empty or all-False
    ``deps`` is elided to ``None`` so the no-dependency case compiles to the
    exact seed path.
    """
    submit = np.asarray(submit, dtype=np.int64)
    runtime = np.asarray(runtime, dtype=np.int64)
    nodes = np.asarray(nodes, dtype=np.int64)
    estimate = (
        np.asarray(estimate, dtype=np.int64) if estimate is not None else runtime.copy()
    )
    n = submit.shape[0]
    priority = (np.asarray(priority, dtype=np.int64) if priority is not None
                else np.zeros(n, dtype=np.int64))
    if not (runtime.shape[0] == nodes.shape[0] == estimate.shape[0] == n):
        raise ValueError("job attribute arrays must have equal length")

    submit = submit - (submit.min() if n else 0)
    runtime = np.maximum(runtime, 1)
    estimate = np.maximum(estimate, 1)
    nodes = np.maximum(nodes, 1)
    if total_nodes is not None:
        nodes = np.minimum(nodes, total_nodes)

    horizon = submit.max(initial=0) + 2 * max(int(runtime.max(initial=1)), int(estimate.max(initial=1)))
    if horizon >= int(INF_TIME):
        raise ValueError(
            f"trace horizon {horizon} overflows int32 sentinel; rescale the trace"
        )

    order = np.lexsort((np.arange(n), submit))
    submit, runtime, estimate, nodes, priority = (
        submit[order], runtime[order], estimate[order], nodes[order],
        priority[order],
    )

    cap = capacity or n
    if cap < n:
        raise ValueError(f"capacity {cap} < number of jobs {n}")

    dep_dst = dep_src = None
    if deps is not None:
        with obs.span("deps.lower", tasks=n) as span:
            t0 = time.perf_counter()
            dst, src = dep_edge_arrays(deps, n, order)
            lower_us = int((time.perf_counter() - t0) * 1e6)
            span.set_metadata(edges=int(dst.size))
        obs.count("deps.edges", int(dst.size))
        obs.count("deps.lower_us", lower_us)
        n_edges = int(dst.size)
        if n_edges:
            if edge_capacity is None:
                ecap = -(-n_edges // _EDGE_ALIGN) * _EDGE_ALIGN
            else:
                ecap = int(edge_capacity)
                if ecap < n_edges:
                    raise ValueError(
                        f"edge_capacity {ecap} < number of edges {n_edges}")
            dep_dst = np.full((ecap,), cap, dtype=np.int32)
            dep_src = np.full((ecap,), cap, dtype=np.int32)
            dep_dst[:n_edges] = dst
            dep_src[:n_edges] = src

    def pad(a, fill):
        out = np.full((cap,), fill, dtype=np.int32)
        out[:n] = a.astype(np.int32)
        return out

    valid = np.zeros((cap,), dtype=bool)
    valid[:n] = True
    return JobSet(
        submit=jnp.asarray(pad(submit, INF_TIME)),
        runtime=jnp.asarray(pad(runtime, 1)),
        estimate=jnp.asarray(pad(estimate, 1)),
        nodes=jnp.asarray(pad(nodes, 1)),
        priority=jnp.asarray(pad(priority, 0)),
        valid=jnp.asarray(valid),
        dep_dst=None if dep_dst is None else jnp.asarray(dep_dst),
        dep_src=None if dep_src is None else jnp.asarray(dep_src),
        submit_sorted=True,
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class RelState:
    """Reliability bookkeeping (DESIGN.md §15), present only when the
    simulation carries a failure model.

    Like ``JobSet.dep_dst``, the whole subtree is ``None`` on
    ``SimState`` for failure-free runs — not zero-size placeholders —
    so the no-failure engine lowers to the *exact* pre-reliability HLO
    module (fingerprint-tested).  ``down`` is the per-node outage mask
    in machine mode ([0] in scalar-counter mode, where outages are pure
    capacity accounting on the ``free`` counter); ``last_start`` is the
    clock of each job's latest dispatch, the base of the checkpoint
    rework math.
    """

    ptr: jax.Array         # i32 scalar: next unconsumed failure-stream entry
    last_start: jax.Array  # i32[J] clock of the latest dispatch (0 = never)
    n_restarts: jax.Array  # i32[J] requeue kills survived so far
    lost_work: jax.Array   # i32[J] rework + overhead (+ aborted work) charged
    aborted: jax.Array     # bool[J] terminated by a failure under "abort"
    down: jax.Array        # bool[N] node outage mask; [0] w/o machine


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class FailureInfo:
    """Per-job reliability outcome columns (``SimResult.rel``)."""

    n_restarts: jax.Array  # i32[J]
    lost_work: jax.Array   # i32[J]
    aborted: jax.Array     # bool[J]


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SvcState:
    """Serving/autoscaler bookkeeping (DESIGN.md §16), present only when
    the simulation carries a service plan.

    Like ``SimState.rel``, the whole subtree is ``None`` for serving-free
    runs — not zero-size placeholders — so the serving-free engine lowers
    to the *exact* pre-serving HLO module (fingerprint-tested).
    ``offline`` is the autoscaler's per-node out-of-service mask in
    machine mode ([0] in scalar-counter mode, where capacity is pure
    accounting on the ``free`` counter); ``cap_online`` logs the online
    node count after each consumed tick (-1 = tick never consumed), the
    capacity series goodput-under-autoscaling integrates.
    """

    ptr: jax.Array         # i32 scalar: next unconsumed autoscale tick
    n_online: jax.Array    # i32 scalar: nodes currently in service
    offline: jax.Array     # bool[N] scaled-out mask; [0] w/o machine
    cap_online: jax.Array  # i32[T] online count after each consumed tick


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SvcInfo:
    """Per-request serving outcome columns (``SimResult.svc``)."""

    slo_met: jax.Array     # bool[J] started within the class SLO deadline
    deadline: jax.Array    # i32[J] submit + slo_wait (INF_TIME = padding)
    cap_online: jax.Array  # i32[T] online nodes after each consumed tick


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MalState:
    """Malleability bookkeeping (DESIGN.md §17), present only when the
    simulation carries a malleable plan.

    Like ``SimState.rel``/``SimState.svc``, the whole subtree is ``None``
    for rigid runs — not zero-size placeholders — so the rigid engine
    lowers to the *exact* pre-malleable HLO module (fingerprint-tested).
    ``width`` is each job's *current* effective width (``min_width``
    until first dispatch); ``prev_w`` the width at the latest dispatch
    (0 = never dispatched, the fresh-job sentinel of the re-dilation
    math); ``seg_start``/``node_s`` the open node-second segment and the
    accumulated node-second integral (``width * wall-time``, closed at
    every resize/kill/completion); ``disp_dur`` the dilated duration
    chosen at the latest dispatch (-1 = never)."""

    ptr: jax.Array        # i32 scalar: next unconsumed elastic tick
    width: jax.Array      # i32[J] current effective width
    prev_w: jax.Array     # i32[J] width at latest dispatch (0 = never)
    seg_start: jax.Array  # i32[J] clock opening the current node_s segment
    node_s: jax.Array     # i32[J] accumulated node-seconds
    n_resizes: jax.Array  # i32[J] grow/shrink actions applied so far
    disp_dur: jax.Array   # i32[J] dilated duration at latest dispatch (-1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MalInfo:
    """Per-job malleability outcome columns (``SimResult.mal``)."""

    width: jax.Array      # i32[J] final width
    nref: jax.Array       # i32[J] reference (requested) width
    n_resizes: jax.Array  # i32[J] grow/shrink actions applied
    node_s: jax.Array     # i32[J] node-seconds actually consumed
    disp_dur: jax.Array   # i32[J] dilated duration at latest dispatch


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimState:
    """Mutable (functionally) simulation state for one cluster.

    The allocation fields (DESIGN.md §11) are zero-size placeholders when the
    simulation runs in seed scalar-counter mode (no ``Machine``): the pytree
    structure is identical in both modes, only leaf shapes differ.
    ``node_owner`` is the per-node occupancy map (-1 = free, else owning job
    row); ``alloc_first``/``alloc_span``/``alloc_sum`` fingerprint each job's
    latest allocation (lowest node id, distinct topology groups spanned, sum
    of 1-based node ids) for cross-engine node-map validation; the ``ev_*``
    ring records (clock, free nodes, largest free contiguous run) per event
    for fragmentation metrics.

    ``n_unmet`` is the incremental unmet-dependency counter (DESIGN.md §14):
    ``n_unmet[i]`` counts dependencies of job *i* not yet DONE, decremented
    by an O(E) scatter-add at each completion event, so the release test is
    an O(J) compare instead of an O(J²) matrix reduction.  Zero-size
    placeholder when the job table carries no edges (same elision pattern as
    the allocation fields).
    """

    clock: jax.Array        # i32 scalar
    jstate: jax.Array       # i32[J] in {PENDING, WAITING, RUNNING, DONE}
    n_unmet: jax.Array      # i32[J] unmet-dependency count; [0] w/o deps
    start: jax.Array        # i32[J] FIRST start time (INF until started)
    finish: jax.Array       # i32[J] actual completion time (INF until started)
    rsv_finish: jax.Array   # i32[J] start + estimate; EASY shadow math input
    remaining: jax.Array    # i32[J] runtime left (preemption suspends work)
    free: jax.Array         # i32 scalar, nodes currently free
    n_events: jax.Array     # i32 scalar, events processed
    node_owner: jax.Array   # i32[N] owning job row per node (-1 free); [0] w/o machine
    alloc_first: jax.Array  # i32[J] lowest node id of latest allocation (-1 = never)
    alloc_span: jax.Array   # i32[J] group span of latest allocation (locality score)
    alloc_sum: jax.Array    # i32[J] sum of 1-based node ids of latest allocation
    ev_time: jax.Array      # i32[L] event clock log (-1 = unused slot); [0] w/o machine
    ev_free: jax.Array      # i32[L] free nodes after each event
    ev_lfb: jax.Array       # i32[L] largest free contiguous block after each event
    rel: RelState | None = None  # reliability state; None = statically elided
    svc: SvcState | None = None  # serving state; None = statically elided
    mal: MalState | None = None  # malleability state; None = statically elided

    @classmethod
    def init(cls, jobs: JobSet, total_nodes: int, machine=None,
             event_log: int = 0, failures: bool = False,
             service: int | None = None,
             malleable: tuple | None = None) -> "SimState":
        J = jobs.capacity
        N = machine.n_nodes if machine is not None else 0
        L = int(event_log) if machine is not None else 0
        inf = jnp.full((J,), INF_TIME, dtype=jnp.int32)
        jstate = jnp.where(jobs.valid, jnp.int32(PENDING), jnp.int32(DONE))
        if jobs.dep_dst is None:
            n_unmet = jnp.zeros((0,), dtype=jnp.int32)
        else:
            n_unmet = jnp.zeros((J,), dtype=jnp.int32).at[jobs.dep_dst].add(
                1, mode="drop")
        return cls(
            clock=jnp.int32(0),
            jstate=jstate,
            n_unmet=n_unmet,
            start=inf,
            finish=inf,
            rsv_finish=inf,
            remaining=jobs.runtime,
            free=jnp.int32(total_nodes),
            n_events=jnp.int32(0),
            node_owner=jnp.full((N,), -1, dtype=jnp.int32),
            alloc_first=jnp.full((J,), -1, dtype=jnp.int32),
            alloc_span=jnp.zeros((J,), dtype=jnp.int32),
            alloc_sum=jnp.zeros((J,), dtype=jnp.int32),
            ev_time=jnp.full((L,), -1, dtype=jnp.int32),
            ev_free=jnp.zeros((L,), dtype=jnp.int32),
            ev_lfb=jnp.zeros((L,), dtype=jnp.int32),
            rel=None if not failures else RelState(
                ptr=jnp.int32(0),
                last_start=jnp.zeros((J,), dtype=jnp.int32),
                n_restarts=jnp.zeros((J,), dtype=jnp.int32),
                lost_work=jnp.zeros((J,), dtype=jnp.int32),
                aborted=jnp.zeros((J,), dtype=bool),
                down=jnp.zeros((N,), dtype=bool),
            ),
            # ``service`` is the padded autoscale tick capacity T (an int);
            # every node starts online, so n_online == total_nodes
            svc=None if service is None else SvcState(
                ptr=jnp.int32(0),
                n_online=jnp.int32(total_nodes),
                offline=jnp.zeros((N,), dtype=bool),
                cap_online=jnp.full((int(service),), -1, dtype=jnp.int32),
            ),
            # ``malleable`` is ``(min_width, tick_capacity)``; min_width may
            # be a tracer (vmap data), the tick capacity is static
            mal=None if malleable is None else MalState(
                ptr=jnp.int32(0),
                width=jnp.full((J,), 1, dtype=jnp.int32)
                * jnp.asarray(malleable[0], dtype=jnp.int32),
                prev_w=jnp.zeros((J,), dtype=jnp.int32),
                seg_start=jnp.zeros((J,), dtype=jnp.int32),
                node_s=jnp.zeros((J,), dtype=jnp.int32),
                n_resizes=jnp.zeros((J,), dtype=jnp.int32),
                disp_dur=jnp.full((J,), -1, dtype=jnp.int32),
            ),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SimResult:
    """Per-job outcome; every paper metric derives from these arrays.

    The ``alloc_*`` / ``ev_*`` fields are zero-size or inert (-1 / 0) unless
    the simulation ran with a ``Machine`` (DESIGN.md §11).
    """

    start: jax.Array        # i32[J]
    finish: jax.Array       # i32[J]
    ready: jax.Array        # i32[J] max(submit, last dep finish); == submit w/o deps
    wait: jax.Array         # i32[J] start - ready (paper Fig. 7 metric)
    makespan: jax.Array     # i32 scalar
    n_events: jax.Array     # i32 scalar
    done: jax.Array         # bool[J] job reached DONE (False => engine hit event cap)
    alloc_first: jax.Array  # i32[J] lowest node id of final allocation (-1 = none)
    alloc_span: jax.Array   # i32[J] topology groups spanned by final allocation
    alloc_sum: jax.Array    # i32[J] sum of 1-based node ids (node-map witness)
    ev_time: jax.Array      # i32[L] per-event clock (-1 = unused slot)
    ev_free: jax.Array      # i32[L] per-event free-node count
    ev_lfb: jax.Array       # i32[L] per-event largest free contiguous block
    rel: FailureInfo | None = None  # reliability columns; None w/o failures
    svc: SvcInfo | None = None      # serving columns; None w/o service
    mal: MalInfo | None = None      # malleability columns; None w/o malleable


def result_from_state(jobs: JobSet, state: SimState,
                      deadline: jax.Array | None = None,
                      nref: jax.Array | None = None) -> SimResult:
    if jobs.dep_dst is None:
        ready = jobs.submit
    else:
        # a job becomes *ready* when its last dependency finishes (submit for
        # roots); dep finishes are final whenever the job released, so the
        # post-hoc segment-max over the edge list is exact for every DONE
        # job (O(E), padding edges scatter out of range and drop).
        J = jobs.capacity
        src_fin = state.finish[jnp.clip(jobs.dep_src, 0, J - 1)]
        dep_fin = jnp.zeros((J,), dtype=jnp.int32).at[jobs.dep_dst].max(
            src_fin, mode="drop")
        ready = jnp.maximum(jobs.submit, dep_fin)
    wait = jnp.where(jobs.valid, state.start - ready, 0).astype(jnp.int32)
    if state.rel is None:
        # pinned expression (and trace) order: the failure-free path must
        # lower to the exact pre-reliability HLO module (fingerprint-tested);
        # serving columns are appended AFTER construction (below) so this
        # expression order never changes with the svc subtree elided
        fin = jnp.where(jobs.valid & (state.jstate == DONE), state.finish, 0)
        res = SimResult(
            start=state.start,
            finish=state.finish,
            ready=ready,
            wait=wait,
            makespan=jnp.max(fin).astype(jnp.int32),
            n_events=state.n_events,
            done=(state.jstate == DONE) & jobs.valid,
            alloc_first=state.alloc_first,
            alloc_span=state.alloc_span,
            alloc_sum=state.alloc_sum,
            ev_time=state.ev_time,
            ev_free=state.ev_free,
            ev_lfb=state.ev_lfb,
        )
        return _with_mal(_with_svc(res, state, deadline), state, nref)
    # an aborted job reached DONE only to terminate the event loop; it is
    # not a completion — excluded from `done` and the makespan
    done = jobs.valid & (state.jstate == DONE) & ~state.rel.aborted
    fin = jnp.where(done, state.finish, 0)
    res = SimResult(
        start=state.start,
        finish=state.finish,
        ready=ready,
        wait=wait,
        makespan=jnp.max(fin).astype(jnp.int32),
        n_events=state.n_events,
        done=done,
        alloc_first=state.alloc_first,
        alloc_span=state.alloc_span,
        alloc_sum=state.alloc_sum,
        ev_time=state.ev_time,
        ev_free=state.ev_free,
        ev_lfb=state.ev_lfb,
        rel=FailureInfo(n_restarts=state.rel.n_restarts,
                        lost_work=state.rel.lost_work,
                        aborted=state.rel.aborted),
    )
    return _with_mal(_with_svc(res, state, deadline), state, nref)


def _with_svc(res: SimResult, state: SimState,
              deadline: jax.Array | None) -> SimResult:
    """Append serving outcome columns when the run carried a service plan.

    The SLO verdict is fixed at start time: a request meets its SLO iff it
    dispatched no later than ``submit + slo_wait`` (and actually
    completed).  A no-op (the same ``res`` object) when ``state.svc`` is
    ``None``, so the pinned serving-free expression order is untouched.
    """
    if state.svc is None:
        return res
    return dataclasses.replace(
        res,
        svc=SvcInfo(
            slo_met=res.done & (state.start <= deadline),
            deadline=deadline,
            cap_online=state.svc.cap_online,
        ),
    )


def _with_mal(res: SimResult, state: SimState,
              nref: jax.Array | None) -> SimResult:
    """Append malleability outcome columns when the run carried a plan.

    A no-op (the same ``res`` object) when ``state.mal`` is ``None``, so
    the pinned rigid expression order is untouched."""
    if state.mal is None:
        return res
    return dataclasses.replace(
        res,
        mal=MalInfo(
            width=state.mal.width,
            nref=nref,
            n_resizes=state.mal.n_resizes,
            node_s=state.mal.node_s,
            disp_dur=state.mal.disp_dur,
        ),
    )
