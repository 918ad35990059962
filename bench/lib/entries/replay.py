"""Back-to-back ``repro.replay.replay_trace`` calls over fresh archives.

Each question streams an archive of ``jobs`` jobs of the configuration's
workload through the windowed replay (``window`` live slots), writing a
durable checkpoint every ``ckpt_every`` rounds into a directory under the
checkout that is cleared before each replay.  It ends when the replay's
summary is on the host.  The check runs the plain reference over the whole
archive of a seeded sample of replays (the last one always among them): the
replay's rounds, clock rebasing and stitching must give the schedule of
the whole trace.
"""

from __future__ import annotations

import os
import shutil

import numpy as np

from lib import stats, workload
from lib.entry import Entry, job_mismatches


class ReplayEntry(Entry):
    def _ckpt_dir(self) -> str:
        return os.path.join(self.workdir, "replay_ckpt")

    def _replay(self, trace: dict):
        from repro.replay import replay_trace

        cfg = self.config
        if cfg["machine"].get("topology") is not None or cfg.get("failures"):
            raise ValueError("the replay entry drives scalar queues without "
                             "failures; add a topology path before using it")
        t = self.traffic
        return replay_trace(dict(trace), cfg["policy"],
                            total_nodes=cfg["machine"]["nodes"],
                            window=t["window"], ckpt_dir=self._ckpt_dir(),
                            ckpt_every=t["ckpt_every"])

    def warm_up(self) -> None:
        n = 2 * self.traffic["window"] + 1
        drain = {"submit": np.arange(n, dtype=np.int64),
                 "runtime": np.ones(n, np.int64),
                 "nodes": np.ones(n, np.int64),
                 "estimate": np.ones(n, np.int64)}
        shutil.rmtree(self._ckpt_dir(), ignore_errors=True)
        self.call(drain)

    def question(self, q: int) -> dict:
        return workload.config_trace(self.config, self.traffic["jobs"],
                                     workload.question_seed(self.seed, q))

    def prepare(self, q: int):
        shutil.rmtree(self._ckpt_dir(), ignore_errors=True)
        return self.question(q)

    def call(self, inp):
        res = self._replay(inp)
        return res, res.summary()

    def collect(self, q: int, inp, out) -> dict:
        res, summary = out
        kept = {"start": res.start, "finish": res.finish, "done": res.done,
                "valid": np.ones(res.n_jobs, dtype=bool),
                "n_events": int(res.n_events)}
        return {"jobs": res.n_jobs, "events": int(res.n_events),
                "rounds": int(res.n_rounds), "flags": summary["flags"],
                "result": kept, "trace": inp}

    def control_record(self, q: int) -> dict:
        trace = self.question(q)
        ref = self.reference(trace, reserve=False)
        ref["valid"] = np.ones(len(ref["start"]), dtype=bool)
        return {"jobs": len(trace["submit"]), "events": ref["n_events"],
                "rounds": 0, "flags": {}, "result": ref, "trace": trace}

    def end_to_end(self, records, window_s):
        return {"replay_jobs_per_s": stats.rate(
            sum(r["jobs"] for r in records), window_s)}

    def counters(self, records):
        return {"engine_events": float(sum(r["events"] for r in records)),
                "replay_rounds": float(sum(r["rounds"] for r in records)),
                "replays": float(len(records))}

    def check(self, records):
        bad_jobs = bad_events = compared = 0
        for i in self.sample(len(records), self.traffic["check"]["questions"]):
            rec = records[i]
            ref = self.reference(rec["trace"])
            bad_jobs += job_mismatches(rec["result"], ref, self.columns())
            bad_events += int(rec["events"] != ref["n_events"])
            compared += len(ref["start"])
        return [("jobs_mismatched", bad_jobs, 0),
                ("replays_events_mismatched", bad_events, 0)], compared


ENTRY = ReplayEntry
