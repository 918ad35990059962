"""What every entry point of the benchmark has in common.

An entry drives one public entry of the program with back-to-back
questions.  The harness calls, in order: :meth:`Entry.warm_up` (set-up),
then for each question of the window :meth:`prepare`, :meth:`call` and
:meth:`collect`, then :meth:`end_to_end` and :meth:`counters`, and, once the
window is closed and the program's state is dropped, :meth:`check`.  A
traffic file names its entry under ``"entry"``; the harness loads
``lib/entries/<entry>.py`` and takes its ``ENTRY`` class.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

from lib import refsim, workload

JOB_COLUMNS = ("start", "finish", "done")
MACHINE_COLUMNS = ("alloc_first", "alloc_span", "alloc_sum")
FAILURE_COLUMNS = ("n_restarts", "lost_work")


class Entry:
    """One entry point under test (module docstring)."""

    def __init__(self, program, config: dict, traffic: dict, seed: int,
                 workdir: str):
        self.program = program      # namespace of the program's modules
        self.config = config
        self.traffic = traffic
        self.seed = int(seed)
        self.workdir = workdir

    # -- the window ---------------------------------------------------------

    def warm_up(self) -> None:
        raise NotImplementedError

    def prepare(self, q: int):
        raise NotImplementedError

    def call(self, inp):
        raise NotImplementedError

    def collect(self, q: int, inp, out) -> dict:
        raise NotImplementedError

    def end_to_end(self, records: List[dict], window_s: float
                   ) -> Dict[str, float]:
        raise NotImplementedError

    def counters(self, records: List[dict]) -> Dict[str, float]:
        return {}

    def check(self, records: List[dict]) -> Tuple[List[tuple], int]:
        """``([(name, value, limit), ...], n_compared)`` after the window."""
        raise NotImplementedError

    def close(self) -> None:
        pass

    # -- shared helpers -----------------------------------------------------

    def sample(self, n_items: int, k: int, salt: int = 0) -> List[int]:
        """``k`` distinct indices of ``n_items`` drawn from the seed, the
        last one always among them (the longest-waited answer)."""
        if n_items <= 0:
            return []
        rng = np.random.default_rng(
            workload.question_seed(self.seed, 1_000_003 + salt))
        k = min(k, n_items)
        rest = rng.permutation(n_items - 1)[:k - 1].tolist()
        return sorted(set(rest) | {n_items - 1})

    def machine_groups(self):
        topo = self.config["machine"].get("topology")
        if topo is None:
            return None
        return np.arange(self.config["machine"]["nodes"]) // topo["shape"][1]

    def reference(self, trace: dict, *, policy=None, alloc=None,
                  total_nodes=None, failures=None, reserve=True) -> dict:
        """The plain reference's schedule of ``trace`` under this
        configuration (overrides as a sweep point or a delta sets them)."""
        cfg = self.config
        fcfg = cfg.get("failures") or {}
        return refsim.simulate(
            trace, policy or cfg["policy"],
            total_nodes=total_nodes or cfg["machine"]["nodes"],
            groups=self.machine_groups(), alloc=alloc or cfg.get("alloc")
            or "simple", failures=failures,
            checkpoint_interval=fcfg.get("checkpoint_interval", 0),
            restart_overhead=fcfg.get("restart_overhead", 0),
            reserve=reserve)

    def failure_stream(self, mtbf: float, seed: int) -> dict:
        f = self.config["failures"]
        return workload.failure_stream(
            mtbf=mtbf, seed=seed, n_nodes=self.config["machine"]["nodes"],
            horizon=f["horizon"], max_failures=f["max_failures"],
            mean_repair=f["mean_repair"])

    def columns(self) -> Tuple[str, ...]:
        cols = JOB_COLUMNS
        if self.config["machine"].get("topology") is not None:
            cols += MACHINE_COLUMNS
        if self.config.get("failures"):
            cols += FAILURE_COLUMNS
        return cols


def job_mismatches(prog: dict, ref: dict, columns) -> int:
    """Jobs whose value differs from the reference in any column (rows in
    (submit, rank) order; the program's padding rows are cut)."""
    n = len(ref["start"])
    valid = np.asarray(prog.get("valid", np.ones(n, bool)), dtype=bool)
    if int(valid.sum()) != n:
        return n
    bad = np.zeros(n, dtype=bool)
    for c in columns:
        a = np.asarray(prog[c])[valid].astype(np.int64)
        bad |= a != np.asarray(ref[c]).astype(np.int64)
    return int(bad.sum())


def keep_columns(res_np: dict, columns) -> dict:
    """The host columns a check needs from one result."""
    out = {c: np.asarray(res_np[c]) for c in columns}
    out["valid"] = np.asarray(res_np["valid"], dtype=bool)
    out["n_events"] = int(res_np["n_events"])
    return out
