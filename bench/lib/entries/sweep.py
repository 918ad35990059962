"""Back-to-back ``repro.api.sweep`` calls, one static bucket each.

Each question is a backlog of ``jobs`` jobs of the configuration's
workload (one of the traffic's ``backlogs``, in turn), run on every point of
the traffic's ``axes`` (policy × alloc × MTBF) in one vmapped executable,
with that backlog's failure stream; on more than one chip the bucket is
sharded over a 1-D mesh of them, its lanes split evenly.  It ends when
every lane's summary is on the host.  The check runs the plain
reference on a seeded sample of lanes (every policy × alloc pair, each at a
random MTBF of a random question), each against its own scenario.
"""

from __future__ import annotations

import copy
import functools
import itertools

import numpy as np

from lib import stats, workload
from lib.entry import Entry, job_mismatches, keep_columns, small_machine


class SweepEntry(Entry):
    SHARDS = True

    @classmethod
    def small(cls, config, traffic):
        trf = copy.deepcopy(traffic)
        trf.update(jobs=48, backlogs=2)
        trf["axes"]["failures.mtbf"] = [2e5, 4e5]
        return small_machine(config), trf

    @functools.cached_property
    def mesh(self):
        """A 1-D mesh over the cell's chips, or None on one chip."""
        if len(self.devices) < 2:
            return None
        from jax.sharding import Mesh

        return Mesh(np.array(self.devices), ("lanes",))

    def _axes(self) -> dict:
        return {k: tuple(v) for k, v in self.traffic["axes"].items()}

    def warm_up(self) -> None:
        n = self.traffic["jobs"]
        drain = {"submit": np.zeros(n, np.int64),
                 "runtime": np.ones(n, np.int64),
                 "nodes": np.ones(n, np.int64),
                 "estimate": np.ones(n, np.int64)}
        self.call((self.scenario(drain, 1), None))

    def question(self, q: int):
        """Host inputs of question ``q``: its backlog and failure seed.

        The backlogs are the traffic's ``backlogs`` fixed reshuffles of the
        configuration's sample, each with a failure stream of its own, taken
        in turn from a point the seed picks.  A bucket's cost moves by about
        a tenth with its failure stream alone, and a window holds about
        ``backlogs`` buckets: so every run answers the same questions, and
        the seed moves their order and the lanes checked."""
        backlog_seed = self.backlog_seed(q)
        trace = workload.config_trace(self.config, self.traffic["jobs"],
                                      backlog_seed)
        return trace, workload.question_seed(backlog_seed, 0)

    def prepare(self, q: int):
        trace, fail_seed = self.question(q)
        return self.scenario(trace, fail_seed), (trace, fail_seed)

    def call(self, inp):
        grid = self.program.api.sweep(inp[0], self._axes(), mesh=self.mesh)
        return grid, grid.summaries()

    def collect(self, q: int, inp, out) -> dict:
        grid, _ = out
        cols = self.columns()
        lanes = [keep_columns(r.to_np(), cols) for r in grid.results]
        trace, fail_seed = inp[1]
        return {"jobs": self.traffic["jobs"] * len(lanes),
                "events": [ln["n_events"] for ln in lanes],
                "points": grid.points, "lanes": lanes, "trace": trace,
                "fail_seed": fail_seed}

    def control_record(self, q: int) -> dict:
        trace, fail_seed = self.question(q)
        points = [dict(zip(self._axes(), combo)) for combo in
                  itertools.product(*self._axes().values())]
        lanes = []
        for p in points:
            ref = self.reference(
                trace, policy=p["policy"], alloc=p["alloc"], reserve=False,
                failures=self.failure_stream(p["failures.mtbf"], fail_seed))
            ref["valid"] = np.ones(len(ref["start"]), dtype=bool)
            lanes.append(ref)
        return {"jobs": len(trace["submit"]) * len(lanes),
                "events": [ln["n_events"] for ln in lanes], "points": points,
                "lanes": lanes, "trace": trace, "fail_seed": fail_seed}

    def end_to_end(self, records, window_s):
        return {"sweep_jobs_per_s": stats.rate(
            sum(r["jobs"] for r in records), window_s)}

    def counters(self, records):
        ev = [np.asarray(r["events"], dtype=np.float64) for r in records]
        return {"lane_events": float(sum(e.sum() for e in ev)),
                "lockstep_iters": float(sum(e.max() for e in ev)),
                "lanes": float(sum(len(e) for e in ev))}

    def check(self, records):
        axes = self._axes()
        pairs = list(itertools.product(axes["policy"], axes["alloc"]))
        rng = np.random.default_rng(workload.question_seed(self.seed, 7))
        per_pair = self.traffic["check"]["lanes_per_pair"]
        bad_jobs = bad_events = compared = 0
        for pol, alloc in pairs:
            for _ in range(per_pair):
                rec = records[int(rng.integers(len(records)))]
                mtbf = axes["failures.mtbf"][
                    int(rng.integers(len(axes["failures.mtbf"])))]
                lane = next(i for i, p in enumerate(rec["points"])
                            if p == {"policy": pol, "alloc": alloc,
                                     "failures.mtbf": mtbf})
                ref = self.reference(
                    rec["trace"], policy=pol, alloc=alloc,
                    failures=self.failure_stream(mtbf, rec["fail_seed"]))
                prog = rec["lanes"][lane]
                bad_jobs += job_mismatches(prog, ref, self.columns())
                bad_events += int(prog["n_events"] != ref["n_events"])
                compared += len(ref["start"])
        return [("jobs_mismatched", bad_jobs, 0),
                ("lanes_events_mismatched", bad_events, 0)], compared


ENTRY = SweepEntry
