"""Back-to-back ``repro.api.run`` calls on fresh backlogs, or on the
traffic's ``backlogs`` fixed ones in turn (``Entry.backlog_seed``).

Each question is a backlog of ``jobs`` jobs of the configuration's workload
under its static policy: a scalar queue, or the configuration's machine
under its allocator with the backlog's own failure stream.  It ends
when the result's summary is on the host.  The check runs the plain
reference on a seeded sample of the questions the window finished, the last
one always among them, and compares every job's schedule and, on a
machine, its placement and its failures.
"""

from __future__ import annotations

import copy

import numpy as np

from lib import stats, workload
from lib.entry import Entry, job_mismatches, keep_columns, small_machine


class RunEntry(Entry):
    @classmethod
    def small(cls, config, traffic):
        return small_machine(config), dict(copy.deepcopy(traffic), jobs=300)

    def warm_up(self) -> None:
        n = self.traffic["jobs"]
        drain = {"submit": np.zeros(n, np.int64),
                 "runtime": np.ones(n, np.int64),
                 "nodes": np.ones(n, np.int64),
                 "estimate": np.ones(n, np.int64)}
        self.call((self.scenario(drain, 1), (drain, 1)))

    def question(self, q: int):
        """Host inputs of question ``q``: its backlog (``Entry.backlog_seed``)
        and the seed of its failure stream."""
        qs = self.backlog_seed(q)
        return (workload.config_trace(self.config, self.traffic["jobs"], qs),
                workload.question_seed(qs, 0))

    def failures(self, fail_seed: int):
        """The failure stream of the configuration's own MTBF, or None."""
        if not self.config.get("failures"):
            return None
        return self.failure_stream(self.config["failures"]["mtbf"], fail_seed)

    def prepare(self, q: int):
        trace, fail_seed = self.question(q)
        return self.scenario(trace, fail_seed), (trace, fail_seed)

    def call(self, inp):
        res = self.program.api.run(inp[0])
        return res, res.summary()

    def collect(self, q: int, inp, out) -> dict:
        res, _ = out
        kept = keep_columns(res.to_np(), self.columns())
        trace, fail_seed = inp[1]
        return {"jobs": self.traffic["jobs"], "events": kept["n_events"],
                "result": kept, "trace": trace, "fail_seed": fail_seed}

    def control_record(self, q: int) -> dict:
        trace, fail_seed = self.question(q)
        ref = self.reference(trace, reserve=False,
                             failures=self.failures(fail_seed))
        ref["valid"] = np.ones(len(ref["start"]), dtype=bool)
        return {"jobs": len(trace["submit"]), "events": ref["n_events"],
                "result": ref, "trace": trace, "fail_seed": fail_seed}

    def end_to_end(self, records, window_s):
        return {"jobs_per_s": stats.rate(sum(r["jobs"] for r in records),
                                         window_s)}

    def counters(self, records):
        return {"engine_events": float(sum(r["events"] for r in records))}

    def check(self, records):
        bad_jobs = bad_events = compared = 0
        for i in self.sample(len(records), self.traffic["check"]["questions"]):
            rec = records[i]
            ref = self.reference(rec["trace"],
                                 failures=self.failures(rec["fail_seed"]))
            bad_jobs += job_mismatches(rec["result"], ref, self.columns())
            bad_events += int(rec["result"]["n_events"] != ref["n_events"])
            compared += len(ref["start"])
        return [("jobs_mismatched", bad_jobs, 0),
                ("questions_events_mismatched", bad_events, 0)], compared


ENTRY = RunEntry
