"""Unified result wrapper (DESIGN.md §12.3).

Before this layer the project had three divergent output shapes: the device
``SimResult`` pytree, ``simulate_np``'s dict-of-numpy, and
``multicluster_result_np``'s flattened per-cluster dict.  :class:`Result`
fronts all three: ``raw`` keeps whatever the backend produced, ``to_np()``
converts (lazily, cached) to the *one* canonical numpy schema —
``submit/runtime/nodes/start/finish/wait/valid/done/makespan/n_events``
plus the ``alloc_*``/``ev_*`` allocation fields when a topology was active —
and ``summary()`` derives the standard scalar metrics
(wait/makespan/utilization/fragmentation) via ``repro.core.metrics``.

Dependency-aware runs (DESIGN.md §13) add a ``ready`` column —
``max(submit, last dependency finish)`` — and ``wait`` is uniformly
``start - ready`` (== ``start - submit`` for dependency-free jobs), the
paper's Fig. 7 workflow wait metric.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro import obs
from repro.core import metrics
from repro.core.jobs import JobSet, SimResult

from repro.api.scenario import Scenario


@dataclasses.dataclass
class Result:
    """One simulation outcome, from any backend.

    ``backend`` ∈ {"jax", "ref", "multicluster"}.  ``raw`` is the backend's
    native object (``SimResult``, the reference simulator's numpy dict, or a
    ``MulticlusterResult``); ``jobs`` is the device job table for the JAX
    backends (None for "ref").
    """

    scenario: Scenario
    backend: str
    raw: Any
    jobs: Optional[JobSet] = None
    _np: Optional[Dict[str, np.ndarray]] = dataclasses.field(
        default=None, repr=False)

    # -- canonical numpy view ----------------------------------------------

    def to_np(self) -> Dict[str, np.ndarray]:
        """Canonical host-side result dict (cached)."""
        if self._np is None:
            self._np = self._materialize_np()
        return self._np

    def _materialize_np(self) -> Dict[str, np.ndarray]:
        if self.backend == "ref":
            return dict(self.raw)
        # the device-to-host transfer: the host blocks on the device here
        with obs.span("result.fetch"):
            if self.backend == "multicluster":
                from repro.core.parallel import multicluster_result_np
                return multicluster_result_np(self.raw)
            return simresult_to_np(
                self.raw, self.jobs,
                with_alloc=self.scenario.topology is not None,
                service=self._service_plan())

    def _service_plan(self):
        spec = self.scenario.trace_specs()[0]
        return spec.plan() if hasattr(spec, "plan") else None

    def __getitem__(self, key: str) -> np.ndarray:
        return self.to_np()[key]

    # -- derived metrics ----------------------------------------------------

    def summary(self) -> Dict[str, float]:
        """Scalar metrics: n_jobs, wait stats, bounded slowdown, makespan,
        utilization, throughput — plus job-span/fragmentation scalars when
        the scenario carried a topology."""
        with obs.span("result.summary"):
            out = self.to_np()
            total = int(np.sum(self.scenario.nodes_per_cluster()))
            s = metrics.summary(out, total)
            if "ev_time" in out and "alloc_span" in out:
                s.update(metrics.alloc_summary(out))
            if "n_restarts" in out:
                s.update(metrics.reliability_summary(out))
            if "slo_met" in out:
                plan = self._service_plan()
                names = plan.class_names if plan is not None else None
                s.update(metrics.slo_summary(out, class_names=names,
                                             total_nodes=total))
            if "mal_width" in out:
                s.update(metrics.malleable_summary(out))
            return s

    @property
    def makespan(self) -> int:
        return int(self.to_np()["makespan"])

    def matches(self, other: "Result", *, node_maps: bool = False) -> bool:
        """Bit-exact start/finish (and optionally allocation-fingerprint)
        agreement with another result over the shorter table — the
        cross-engine validation predicate (DESIGN.md §9)."""
        a, b = self.to_np(), other.to_np()
        n = min(int(a["valid"].sum()), int(b["valid"].sum()))
        keys = ["start", "finish"]
        if node_maps:
            keys += ["alloc_first", "alloc_span", "alloc_sum"]
        return all(bool(np.array_equal(a[k][:n], b[k][:n])) for k in keys)


def simresult_to_np(res: SimResult, jobs: JobSet, *, with_alloc: bool,
                    service=None) -> Dict[str, np.ndarray]:
    """``SimResult`` + ``JobSet`` -> the canonical numpy dict (the schema
    ``simulate_np`` established; shared by every backend).  The result and
    the job columns cross to the host in one ``jax.device_get``."""
    res, (submit, nodes, runtime, valid) = jax.device_get(
        (res, (jobs.submit, jobs.nodes, jobs.runtime, jobs.valid)))
    out = {
        "submit": submit,
        "nodes": nodes,
        "runtime": runtime,
        "start": res.start,
        "finish": res.finish,
        "ready": res.ready,
        "wait": res.wait,
        "makespan": int(res.makespan),
        "n_events": int(res.n_events),
        "done": res.done,
        "valid": valid,
    }
    if with_alloc:
        n_ev = out["n_events"]
        out["alloc_first"] = res.alloc_first
        out["alloc_span"] = res.alloc_span
        out["alloc_sum"] = res.alloc_sum
        out["ev_time"] = res.ev_time[:n_ev]
        out["ev_free"] = res.ev_free[:n_ev]
        out["ev_lfb"] = res.ev_lfb[:n_ev]
    if res.rel is not None:
        out["n_restarts"] = res.rel.n_restarts
        out["lost_work"] = res.rel.lost_work
        out["aborted"] = res.rel.aborted
    if res.mal is not None:
        # chosen/final width, reference width, resize count, node-second
        # ledger and dispatch-time dilated duration (DESIGN.md §17); rows
        # align with the job table like every other column
        out["mal_width"] = np.asarray(res.mal.width, dtype=np.int64)
        out["mal_nref"] = np.asarray(res.mal.nref, dtype=np.int64)
        out["mal_nresize"] = np.asarray(res.mal.n_resizes, dtype=np.int64)
        out["mal_node_s"] = np.asarray(res.mal.node_s, dtype=np.int64)
        out["mal_dur"] = np.asarray(res.mal.disp_dur, dtype=np.int64)
    if res.svc is not None:
        out["slo_met"] = res.svc.slo_met
        out["deadline"] = res.svc.deadline
        # capacity series: the engine logs the online level per consumed
        # tick (-1 = never consumed); the times come from the plan's tick
        # stream, which is how the refsim emits the same two columns
        cap = res.svc.cap_online
        used = cap >= 0
        out["cap_online"] = cap[used].astype(np.int64)
        if service is not None:
            out["class_id"] = np.asarray(service.class_id, dtype=np.int64)
            out["cap_time"] = np.asarray(
                service.tick_time, dtype=np.int64)[used]
    return out
