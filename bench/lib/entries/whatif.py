"""A closed loop of what-if queries to ``POST /query`` of ``repro.service``.

Set-up writes one queue of ``queue_jobs`` jobs of the configuration's
workload as an SWF file under the checkout (the fleet format the service
loads), serves it on a thread of this process, and warms up both query
shapes.  One client then posts queries back to back, ``capacity`` and
``placement`` in the traffic's ``mix`` order: a capacity query asks for
``deltas`` machine sizes of ``add_nodes`` drawn from ``[0, add_nodes_max]``,
a placement query for one candidate job of seeded width and runtime
submitted inside the backlog's span.  The check runs the plain reference
on the scenario each point of a seeded sample of answers stands for, and
compares the answer's summary (and candidate) field by field.
"""

from __future__ import annotations

import copy
import functools
import json
import os
import threading
import time
import urllib.request

import numpy as np

from lib import stats, workload
from lib.entry import Entry, small_machine

SUMMARY_FIELDS = ("n_jobs", "avg_wait", "p50_wait", "p95_wait", "p99_wait",
                  "max_wait", "avg_bounded_slowdown", "makespan",
                  "utilization", "throughput")
CANDIDATE_FIELDS = ("start", "finish", "wait")
QUEUE = "queue"
# question indices of set-up, clear of any window's
WARM_QUEUE, WARM_QUERY = 10**12, 10**12 + 1


def _post(url: str, doc: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=600) as r:
        return json.loads(r.read())


class WhatIfEntry(Entry):
    @classmethod
    def small(cls, config, traffic):
        return small_machine(config), dict(copy.deepcopy(traffic),
                                           queue_jobs=200)

    def warm_up(self) -> None:
        cfg = self.config
        if cfg["machine"].get("topology") is not None or cfg.get("failures"):
            raise ValueError("the what-if entry drives scalar queues "
                             "without failures; add a delta path first")
        api = self.program.api
        trace = self.queue_trace
        os.makedirs(self.workdir, exist_ok=True)
        path = os.path.join(self.workdir, "whatif_queue.swf")
        with open(path, "w", encoding="ascii") as f:
            f.write(workload.swf_lines(trace))
        fleet = {QUEUE: api.Scenario(trace=api.SwfTrace(path),
                                     total_nodes=cfg["machine"]["nodes"],
                                     policy=cfg["policy"])}
        from repro.service import make_server

        self.server = make_server(fleet)
        self.thread = threading.Thread(target=self.server.serve_forever,
                                       daemon=True)
        self.thread.start()
        mix = self.traffic["mix"]
        for kind in dict.fromkeys(mix):
            self.call(self._query(kind, workload.question_seed(self.seed,
                                                             WARM_QUERY)))

    @functools.cached_property
    def queue_trace(self) -> dict:
        """The served queue, its first submit at 0 (as the SWF loader
        rebases it)."""
        trace = workload.config_trace(
            self.config, self.traffic["queue_jobs"],
            workload.question_seed(self.seed, WARM_QUEUE))
        trace["submit"] = trace["submit"] - trace["submit"].min()
        return trace

    def _query(self, kind: str, qs: int) -> dict:
        rng = np.random.default_rng(qs)
        t = self.traffic
        if kind == "capacity":
            adds = rng.integers(0, t["add_nodes_max"] + 1, t["deltas"])
            return {"version": 1, "kind": "capacity", "queue": QUEUE,
                    "deltas": [{"add_nodes": int(a)} for a in adds]}
        w = self.config["workload"]
        runtime = int(np.clip(rng.lognormal(*w["runtime_lognorm"]), 1,
                              w["max_runtime"]))
        lo, hi = w["estimate_factor"]
        job = {"submit": int(rng.integers(0, int(self.queue_trace["submit"]
                                                 .max()) + 1)),
               "runtime": runtime,
               "nodes": int(2 ** rng.integers(0, w["node_pow2_max"] + 1)),
               "estimate": max(int(runtime * rng.uniform(lo, hi)), runtime)}
        return {"version": 1, "kind": "placement", "job": job}

    def prepare(self, q: int):
        mix = self.traffic["mix"]
        return self._query(mix[q % len(mix)],
                           workload.question_seed(self.seed, q))

    def call(self, inp):
        import jax

        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("query.http"):
            answer = _post(self.server.url + "/query", inp)
        latency = time.perf_counter() - t0
        if "error" in answer:
            raise RuntimeError(f"query failed: {answer['error']}")
        return answer, latency

    def collect(self, q: int, inp, out) -> dict:
        answer, latency = out
        return {"query": inp, "answer": answer, "latency_s": latency,
                "compiles": answer["cache"]["compiles"]}

    def end_to_end(self, records, window_s):
        return {"query_s_p90": stats.percentile(
            [r["latency_s"] for r in records], 90)}

    def counters(self, records):
        return {"queries": float(len(records)),
                "query_compiles": float(sum(r["compiles"] for r in records))}

    def control_record(self, q: int) -> dict:
        query = self.prepare(q)
        points = [{"summary": summ, "candidate": cand}
                  for summ, cand in self._expected(query, reserve=False)]
        return {"query": query, "answer": {"points": points},
                "latency_s": 0.0, "compiles": 0}

    def _expected(self, query: dict, reserve: bool = True):
        """[(summary, candidate or None)] the reference gives for each point
        of the answer to ``query``."""
        base = self.queue_trace
        nodes = self.config["machine"]["nodes"]
        out = []
        if query["kind"] == "capacity":
            for d in query["deltas"]:
                total = nodes + d["add_nodes"]
                ref = self.reference(base, total_nodes=total,
                                     reserve=reserve)
                out.append((stats.queue_summary(ref, total), None))
            return out
        job = query["job"]
        trace = {k: np.r_[base[k], job[k]] for k in
                 ("submit", "runtime", "nodes", "estimate")}
        ref = self.reference(trace, reserve=reserve)
        n = len(base["submit"])
        pos = int(np.nonzero(np.lexsort((np.arange(n + 1), trace["submit"]))
                             == n)[0][0])
        cand = {"start": int(ref["start"][pos]),
                "finish": int(ref["finish"][pos]),
                "wait": int(ref["wait"][pos])}
        summ = stats.queue_summary(ref, nodes)
        summ["candidate_wait"] = float(cand["wait"])
        out.append((summ, cand))
        return out

    def check(self, records):
        bad = compared = 0
        k = self.traffic["check"]
        for kind in dict.fromkeys(self.traffic["mix"]):
            idx = [i for i, r in enumerate(records)
                   if r["query"]["kind"] == kind]
            for j in self.sample(len(idx), k[kind], salt=len(kind)):
                rec = records[idx[j]]
                points = rec["answer"]["points"]
                want = self._expected(rec["query"])
                if len(points) != len(want):
                    bad += len(want) * len(SUMMARY_FIELDS)
                    continue
                for p, (summ, cand) in zip(points, want):
                    got = p.get("summary", {})
                    fields = SUMMARY_FIELDS + (
                        ("candidate_wait",) if cand else ())
                    bad += sum(got.get(f) != summ[f] for f in fields)
                    compared += len(fields)
                    if cand is not None:
                        gc = p.get("candidate") or {}
                        bad += sum(gc.get(f) != cand[f]
                                   for f in CANDIDATE_FIELDS)
                        compared += len(CANDIDATE_FIELDS)
        return [("fields_mismatched", bad, 0)], compared

    def close(self) -> None:
        server = getattr(self, "server", None)
        if server is not None:
            server.shutdown()
            server.server_close()
            self.thread.join(timeout=60)


ENTRY = WhatIfEntry
