"""Back-to-back ``repro.api.run`` calls on fresh backlogs.

Each question is a backlog of ``jobs`` jobs of the configuration's workload
under its static policy; it ends when the result's summary is on the host.
The check runs the plain reference on a seeded sample of the questions the
window finished, the last one always among them.
"""

from __future__ import annotations

import numpy as np

from lib import stats, workload
from lib.entry import Entry, job_mismatches, keep_columns


class RunEntry(Entry):
    def _scenario(self, trace: dict):
        api = self.program.api
        cfg = self.config
        if cfg["machine"].get("topology") is not None or cfg.get("failures"):
            raise ValueError("the run entry drives scalar queues without "
                             "failures; add a topology path before using it")
        return api.Scenario(trace=api.ArrayTrace.from_dict(trace),
                            total_nodes=cfg["machine"]["nodes"],
                            policy=cfg["policy"])

    def warm_up(self) -> None:
        n = self.traffic["jobs"]
        drain = {"submit": np.zeros(n, np.int64),
                 "runtime": np.ones(n, np.int64),
                 "nodes": np.ones(n, np.int64),
                 "estimate": np.ones(n, np.int64)}
        self.call((self._scenario(drain), drain))

    def question(self, q: int) -> dict:
        return workload.config_trace(self.config, self.traffic["jobs"],
                                     workload.question_seed(self.seed, q))

    def prepare(self, q: int):
        trace = self.question(q)
        return self._scenario(trace), trace

    def call(self, inp):
        res = self.program.api.run(inp[0])
        return res, res.summary()

    def collect(self, q: int, inp, out) -> dict:
        res, _ = out
        kept = keep_columns(res.to_np(), self.columns())
        return {"jobs": self.traffic["jobs"], "events": kept["n_events"],
                "result": kept, "trace": inp[1]}

    def control_record(self, q: int) -> dict:
        trace = self.question(q)
        ref = self.reference(trace, reserve=False)
        ref["valid"] = np.ones(len(ref["start"]), dtype=bool)
        return {"jobs": len(trace["submit"]), "events": ref["n_events"],
                "result": ref, "trace": trace}

    def end_to_end(self, records, window_s):
        return {"jobs_per_s": stats.rate(sum(r["jobs"] for r in records),
                                         window_s)}

    def counters(self, records):
        return {"engine_events": float(sum(r["events"] for r in records))}

    def check(self, records):
        bad_jobs = bad_events = compared = 0
        for i in self.sample(len(records), self.traffic["check"]["questions"]):
            rec = records[i]
            ref = self.reference(rec["trace"])
            bad_jobs += job_mismatches(rec["result"], ref, self.columns())
            bad_events += int(rec["result"]["n_events"] != ref["n_events"])
            compared += len(ref["start"])
        return [("jobs_mismatched", bad_jobs, 0),
                ("questions_events_mismatched", bad_events, 0)], compared


ENTRY = RunEntry
