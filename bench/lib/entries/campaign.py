"""Back-to-back ``repro.api.run`` calls, each on one workflow campaign: the
configuration's Montage mosaics as ``WorkflowTrace(kind="montage_campaign")``
on its slot pool under FCFS, one of the traffic's ``backlogs`` fixed
campaigns in turn (``Entry.backlog_seed``: the seed draws the runtimes,
never the shape).

A question is the whole ``run()``: the program generates the campaign,
lowers its dependencies, simulates it, and the result's summary reaches
the host.  Each record keeps the microseconds the program's counter
``deps.lower_us`` moved by in the question (None where the program has no
such counter).  The check runs the benchmark's own reference
(``lib/refsim_dag.py``) on the campaigns of a seeded sample of the
questions, the last one always among them, and compares every task's
start, finish and ready time and the number of events.  The reference
takes the campaign's layout from ``expected_campaign`` (built here from the
configuration's parameters, as its ``assumed`` layout states it) and only
the runtimes from the program; the check holds the program's campaign to
that layout, edge for edge, and each stage's mean runtime to the profile's.
The control releases a task when its last dependency starts.
"""

from __future__ import annotations

import copy

import numpy as np

from lib import harness, refsim_dag, stats
from lib.entry import Entry, job_mismatches, keep_columns

COLUMNS = ("start", "finish", "ready", "done")


STAGES = ("project", "diff", "concat", "bgmodel", "background", "imgtbl",
          "add", "shrink", "jpeg")


def mosaic_layout(params: dict) -> tuple:
    """``(stage, edges)`` of one mosaic as the configuration's ``assumed``
    layout states it, built here from the parameters alone: each task's
    stage index into ``STAGES`` (tasks numbered stage after stage, in the
    order of ``STAGES``, the tiles' mImgtbl and mAdd before the last one),
    and ``(task, dependency)`` pairs.

    The images fill a square grid row by row; the overlapping pairs are
    every horizontal neighbour, every vertical one, then the down-right and
    the down-left diagonals, each row by row, up to ``pairs``; the images
    split into ``sqrt(tiles) x sqrt(tiles)`` square tiles of the grid."""
    images, tiles = params["images"], params["tiles"]
    side = 1
    while side * side < images:
        side += 1
    k = 1
    while k * k < tiles:
        k += 1
    cell = {divmod(i, side): i for i in range(images)}
    pairs = []
    for dr, dc in ((0, 1), (1, 0), (1, 1), (1, -1)):
        for i in range(images):
            r, c = divmod(i, side)
            j = cell.get((r + dr, c + dc)) if 0 <= c + dc < side else None
            if j is not None:
                pairs.append((i, j))
    pairs = pairs[:params["pairs"]]
    counts = (images, len(pairs), 1, 1, images, tiles + 1, tiles + 1, tiles,
              1)
    first = [sum(counts[:s]) for s in range(len(counts))]
    proj, diff, concat, bgm, back, imgt, add, shrink, jpeg = first
    edges = []
    for p, (a, b) in enumerate(pairs):
        edges += [(diff + p, proj + a), (diff + p, proj + b)]
    edges += [(concat, diff + p) for p in range(len(pairs))]
    edges.append((bgm, concat))
    for i in range(images):
        r, c = divmod(i, side)
        tile = (r * k // side) * k + c * k // side
        edges += [(back + i, proj + i), (back + i, bgm),
                  (imgt + tile, back + i)]
    for t in range(tiles):
        edges += [(add + t, imgt + t), (shrink + t, add + t),
                  (imgt + tiles, shrink + t)]
    edges += [(add + tiles, imgt + tiles), (jpeg, add + tiles)]
    stage = np.repeat(np.arange(len(counts)), counts)
    return stage, np.asarray(edges, dtype=np.int64)


def expected_campaign(params: dict) -> dict:
    """What the generator must give for ``params``, but the runtimes:
    ``submit`` (mosaic *m* at ``m * gap``), ``stage`` of every task, and
    the campaign's ``deps`` pairs, each mosaic's layout offset by its
    first task."""
    stage, edges = mosaic_layout(params)
    n, m = len(stage), params["mosaics"]
    return {"submit": np.repeat(np.arange(m, dtype=np.int64) * params["gap"],
                                n),
            "stage": np.tile(stage, m),
            "deps": (edges[None] + (np.arange(m) * n)[:, None, None]
                     ).reshape(-1, 2)}


def _edge_keys(pairs, n: int) -> np.ndarray:
    pairs = np.asarray(pairs, dtype=np.int64).reshape(-1, 2)
    return np.sort(pairs[:, 0] * n + pairs[:, 1])


def off_profile(trace: dict, want: dict, workload: dict) -> tuple:
    """``(campaigns_off_shape, stages_off_mean)`` of a generated campaign
    against ``want`` (:func:`expected_campaign`): whether its tasks, submit
    times, slots or edge set differ at all; and how many stages' mean
    runtime lies further from the profile's mean than six standard errors
    of the workload's ``runtime_cv``, plus half a tick of rounding."""
    n = len(want["submit"])
    submit = np.asarray(trace["submit"])
    off = (len(submit) != n
           or not np.array_equal(submit, want["submit"])
           or not np.array_equal(np.asarray(trace["nodes"]), np.ones(n))
           or len(trace["deps"]) != len(want["deps"])
           or not np.array_equal(_edge_keys(trace["deps"], n),
                                 _edge_keys(want["deps"], n)))
    if off:
        return 1, 0
    runtime = np.asarray(trace["runtime"], dtype=np.float64)
    params, cv = workload["params"], workload["runtime_cv"]
    bad = 0
    for s, name in enumerate(STAGES):
        got = runtime[want["stage"] == s]
        mean = params[f"{name}_s"] / workload["tick_s"]
        tol = 6 * cv * mean / np.sqrt(len(got)) + 0.5
        bad += int(got.min() < 1 or abs(got.mean() - mean) > tol)
    return 0, bad


class CampaignEntry(Entry):
    @classmethod
    def small(cls, config, traffic):
        cfg = copy.deepcopy(config)
        cfg["machine"]["nodes"] = 32
        cfg["workload"]["params"].update(mosaics=2, images=40, pairs=110,
                                         tiles=4)
        return cfg, dict(copy.deepcopy(traffic), backlogs=2)

    def _api(self):
        return (self.program or harness.import_program()).api

    def spec(self, seed: int):
        params = tuple(sorted(self.config["workload"]["params"].items()))
        return self._api().WorkflowTrace(kind="montage_campaign", seed=seed,
                                         params=params)

    def _scenario(self, spec):
        return self._api().Scenario(trace=spec,
                                    total_nodes=self.config["machine"]["nodes"],
                                    policy=self.config["policy"])

    def lower_us(self):
        from repro import obs

        return obs.counters().get("deps.lower_us")

    def warm_up(self) -> None:
        # a campaign of the cell's own shape on a seed no question uses
        spec = self.spec(self.config["workload"]["sample_seed"])
        self.call((self._scenario(spec), spec, None))

    def prepare(self, q: int):
        spec = self.spec(self.backlog_seed(q))
        return self._scenario(spec), spec, self.lower_us()

    def call(self, inp):
        res = self.program.api.run(inp[0])
        return res, res.summary()

    def collect(self, q: int, inp, out) -> dict:
        res, _ = out
        kept = keep_columns(res.to_np(), COLUMNS)
        before, after = inp[2], self.lower_us()
        return {"jobs": int(kept["valid"].sum()), "events": kept["n_events"],
                "result": kept, "spec": inp[1],
                "lower_us": None if after is None else after - (before or 0)}

    def control_record(self, q: int) -> dict:
        spec = self.spec(self.backlog_seed(q))
        ref = refsim_dag.simulate(spec.materialize(),
                                  self.config["machine"]["nodes"],
                                  release="start")
        ref["valid"] = ref["done"]
        return {"jobs": len(ref["start"]), "events": ref["n_events"],
                "result": ref, "spec": spec, "lower_us": None}

    def end_to_end(self, records, window_s):
        return {"jobs_per_s": stats.rate(sum(r["jobs"] for r in records),
                                         window_s)}

    def counters(self, records):
        out = {"engine_events": float(sum(r["events"] for r in records)),
               "questions": float(len(records))}
        lowered = [r["lower_us"] for r in records]
        if all(v is not None for v in lowered):
            out["deps_lower_us"] = float(sum(lowered))
        return out

    def check(self, records):
        workload = self.config["workload"]
        want = expected_campaign(workload["params"])
        bad_jobs = bad_events = bad_shape = bad_means = compared = 0
        for i in self.sample(len(records), self.traffic["check"]["questions"]):
            rec = records[i]
            # copies: the program lowered this very dict (the cache's)
            trace = {k: np.array(v, copy=True)
                     for k, v in rec["spec"].materialize().items()}
            shape, means = off_profile(trace, want, workload)
            bad_shape += shape
            bad_means += means
            # the reference takes the layout built here, and of the
            # program's campaign only the runtimes
            ref = refsim_dag.simulate(
                {"submit": want["submit"], "runtime": trace["runtime"],
                 "nodes": np.ones(len(want["submit"]), dtype=np.int64),
                 "deps": want["deps"]}, self.config["machine"]["nodes"])
            bad_jobs += job_mismatches(rec["result"], ref, COLUMNS)
            bad_events += int(rec["result"]["n_events"] != ref["n_events"])
            compared += len(ref["start"])
        return [("jobs_mismatched", bad_jobs, 0),
                ("questions_events_mismatched", bad_events, 0),
                ("campaigns_off_shape", bad_shape, 0),
                ("stages_off_mean", bad_means, 0)], compared


ENTRY = CampaignEntry
