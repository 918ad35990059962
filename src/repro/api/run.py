"""The single entry point: ``run(scenario) -> Result`` (DESIGN.md §12).

``run`` dispatches on the spec — scalar-counter engine, topology-aware
allocation engine, or the conservative-window multicluster engine — and
always returns the unified :class:`repro.api.Result`.  ``run_ref`` drives
the host reference simulator (CQsim analogue) from the *same* spec, so

    run(s).matches(run_ref(s))

is the project's cross-engine validation predicate in one line.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro import alloc as _alloc
from repro import obs
from repro.core import engine
from repro.core.jobs import JobSet, POLICY_NAMES, make_jobset
from repro.core.parallel import simulate_multicluster, stack_jobsets

from repro.api.result import Result
from repro.api.scenario import Scenario


def _policy_name(policy) -> str:
    if isinstance(policy, str):
        return policy.lower()
    return POLICY_NAMES[int(policy)]


def build_jobset(scenario: Scenario, *, cluster: int = 0,
                 capacity: Optional[int] = None) -> JobSet:
    """Materialize one cluster's trace spec into a device ``JobSet``."""
    spec = scenario.trace_specs()[cluster]
    total_nodes = scenario.nodes_per_cluster()[cluster]
    trace = spec.materialize()
    if capacity is None:
        capacity = scenario.capacity
    if capacity is None:
        # ServiceTrace pads to max_jobs so the deadline/class columns stay
        # row-aligned with the job table across every rate point
        capacity = getattr(spec, "pad_capacity", None)
    return make_jobset(
        trace["submit"], trace["runtime"], trace["nodes"],
        trace.get("estimate"), trace.get("priority"),
        deps=trace.get("deps"),
        capacity=capacity,
        total_nodes=total_nodes,
    )


def _machine(scenario: Scenario):
    return scenario.topology.build() if scenario.topology is not None else None


def _failure_trace(scenario: Scenario):
    """The ONE materialized failure trace both engines consume (cached by
    the model's lru, so ``run`` and ``run_ref`` see identical arrays)."""
    if scenario.failures is None:
        return None
    return scenario.failures.materialize(int(scenario.total_nodes))


def _service_plan(scenario: Scenario):
    """The ONE materialized serving plan both engines consume (cached by
    the spec's lru, so ``run`` and ``run_ref`` see identical arrays)."""
    spec = scenario.trace_specs()[0]
    return spec.plan() if hasattr(spec, "plan") else None


def _mal_plan(scenario: Scenario):
    """The ONE materialized malleable plan both engines consume.

    ``materialize_plan`` normalizes and (submit, id)-sorts the trace with
    the same rules as ``make_jobset``, so the plan's dur/nref rows align
    with the job table rows in BOTH engines; the model-level lru keeps
    ``run`` and ``run_ref`` on identical arrays."""
    if scenario.malleable is None:
        return None
    from repro.malleable import materialize_plan

    spec = scenario.trace_specs()[0]
    capacity = scenario.capacity
    if capacity is None:
        capacity = getattr(spec, "pad_capacity", None)
    return materialize_plan(scenario.malleable, spec.materialize(),
                            total_nodes=int(scenario.total_nodes),
                            capacity=capacity)


def run(scenario: Scenario) -> Result:
    """Run one scenario on the JAX engine and return a unified ``Result``.

    Host spans: ``run.prepare`` (job table and contexts) and
    ``run.dispatch`` (the engine call)."""
    if scenario.multicluster is not None:
        return _run_multicluster(scenario)
    with obs.span("run.prepare"):
        jobs = build_jobset(scenario)
        contexts = dict(machine=_machine(scenario),
                        failures=_failure_trace(scenario),
                        service=_service_plan(scenario),
                        malleable=_mal_plan(scenario))
    with obs.span("run.dispatch"):
        res = engine.simulate(
            jobs,
            engine.policies_id(scenario.policy),
            int(scenario.total_nodes),
            alloc=scenario.alloc,
            contention=scenario.contention,
            max_events=scenario.max_events,
            **contexts,
        )
    return Result(scenario=scenario, backend="jax", raw=res, jobs=jobs)


def run_ref(scenario: Scenario) -> Result:
    """Run the SAME spec on the host reference simulator (bit-exact twin)."""
    from repro.refsim import simulate_reference

    if scenario.multicluster is not None:
        raise ValueError(
            "the reference simulator has no multicluster mode; validate the "
            "single-cluster scenario per cluster instead")
    spec = scenario.trace_specs()[0]
    machine = _machine(scenario)
    alloc_name = ("simple" if scenario.alloc is None
                  else _alloc.ALLOC_NAMES[_alloc.canonical_id(scenario.alloc)])
    out = simulate_reference(
        spec.materialize(),
        _policy_name(scenario.policy),
        total_nodes=int(scenario.total_nodes),
        machine=machine,
        alloc=alloc_name,
        contention=scenario.contention,
        failures=_failure_trace(scenario),
        service=_service_plan(scenario),
        malleable=_mal_plan(scenario),
    )
    return Result(scenario=scenario, backend="ref", raw=out)


# ---------------------------------------------------------------------------
# multicluster
# ---------------------------------------------------------------------------


def _multicluster_capacity(scenario: Scenario,
                           traces: Tuple[dict, ...]) -> int:
    """Uniform per-cluster row capacity: the largest cluster plus headroom
    for imported jobs (migration inserts rows; DESIGN.md §2)."""
    if scenario.capacity is not None:
        return scenario.capacity
    biggest = max(len(t["submit"]) for t in traces)
    mc = scenario.multicluster
    slack = 8 * mc.max_export if mc.migrate else 0
    return biggest + slack


def _default_horizon(traces, nodes_c, window: int) -> int:
    """Migration-round horizon when the spec leaves it None.

    Rounds must cover the *busy period*, not just the submission span — a
    congested cluster keeps a backlog (and load imbalance worth migrating)
    long after the last submit.  Per cluster we bound the drain time by
    aggregate demand, ``ceil(sum(nodes*runtime) / total_nodes)``, plus the
    longest single job; the horizon is the worst cluster's span + drain.
    Heuristic (head-of-line blocking can exceed it) — pass an explicit
    ``Multicluster(horizon=...)`` for precise control; events beyond the
    horizon still complete, they just stop triggering migration.
    """
    worst = 0
    for t, n in zip(traces, nodes_c):
        sub = np.asarray(t["submit"])
        rt = np.maximum(np.asarray(t["runtime"]), 1)
        est = np.asarray(t["estimate"]) if "estimate" in t else rt
        span = int(sub.max(initial=0) - sub.min(initial=0))
        nodes = np.clip(np.asarray(t["nodes"]), 1, n)
        drain = -(-int(np.sum(nodes * rt)) // int(n))   # ceil(work / machine)
        tail = max(drain, 2 * int(max(rt.max(initial=1), est.max(initial=1))))
        worst = max(worst, span + tail)
    return worst + 2 * window


def _run_multicluster(scenario: Scenario) -> Result:
    if scenario.topology is not None:
        raise ValueError(
            "multicluster scenarios run scalar-counter clusters; "
            "per-cluster topologies are not supported yet")
    mc = scenario.multicluster
    specs = scenario.trace_specs()
    nodes_c = scenario.nodes_per_cluster()
    traces = tuple(s.materialize() for s in specs)
    cap = _multicluster_capacity(scenario, traces)
    # clusters may mix DAG and plain traces: stack_jobsets pads the dep-free
    # tables (and ragged edge lists) with inert out-of-range edges to keep
    # the stacked pytree uniform
    jobsets = [
        make_jobset(t["submit"], t["runtime"], t["nodes"], t.get("estimate"),
                    t.get("priority"), deps=t.get("deps"), capacity=cap,
                    total_nodes=n)
        for t, n in zip(traces, nodes_c)
    ]
    horizon = mc.horizon
    if horizon is None:
        horizon = _default_horizon(traces, nodes_c, int(mc.window))
    res = simulate_multicluster(
        stack_jobsets(jobsets),
        engine.policies_id(scenario.policy),
        np.asarray(nodes_c, dtype=np.int32),
        window=int(mc.window),
        horizon=horizon,
        migrate=mc.migrate,
        max_export=mc.max_export,
        latency=mc.latency,
        load_imbalance_threshold=mc.load_imbalance_threshold,
        max_events=scenario.max_events,
    )
    return Result(scenario=scenario, backend="multicluster", raw=res)
