"""The benchmark's plain reference for a slot pool with task dependencies.

A host event simulator written for the benchmark alone (it imports nothing
from the program).  ``slots`` identical slots run the tasks of ``trace``
under FCFS:

- a task is *released* when every task it depends on has finished, and
  joins the queue at ``max(submit, release)``, its ``ready`` time;
- an event is a time at which a task finishes or joins the queue: the
  event finishes every task due, releases their dependents, queues every
  released task whose submit time has come, then starts queued tasks in
  (submit, input index) order while the head's slots are free (a head that
  does not fit blocks the queue);
- a started task holds its slots for its runtime.

Duplicate dependency pairs count once.  Rows of the answer are in (submit,
input index) order, the program's row order.  ``release="start"`` is the
control: a task is released when its last dependency *starts*, which
breaks the guarantee that a task starts only after every task it depends
on has finished.
"""

from __future__ import annotations

import heapq
from typing import Dict

import numpy as np


def simulate(trace: Dict[str, np.ndarray], slots: int, *,
             release: str = "finish") -> Dict[str, np.ndarray]:
    """``{start, finish, ready, done, n_events}`` of ``trace`` (``submit``,
    ``runtime``, ``nodes``, ``deps`` as (task, dependency) pairs in input
    order) on ``slots`` slots under FCFS."""
    if release not in ("finish", "start"):
        raise ValueError(f"release at {release!r}")
    submit = np.asarray(trace["submit"], dtype=np.int64)
    n = len(submit)
    order = np.lexsort((np.arange(n), submit))
    row = np.empty(n, dtype=np.int64)
    row[order] = np.arange(n)
    sub = submit[order].tolist()
    run = np.maximum(np.asarray(trace["runtime"], np.int64), 1)[order].tolist()
    need = np.asarray(trace["nodes"], dtype=np.int64)[order].tolist()

    pairs = np.asarray(trace["deps"], dtype=np.int64).reshape(-1, 2)
    pairs = np.unique(row[pairs], axis=0)             # (dependent, dependency)
    unmet = np.bincount(pairs[:, 0], minlength=n).tolist()
    by_dep = pairs[np.argsort(pairs[:, 1], kind="stable")]
    bounds = np.searchsorted(by_dep[:, 1], np.arange(n + 1)).tolist()
    dependents = by_dep[:, 0].tolist()

    start = [-1] * n
    finish = [-1] * n
    ready = sub[:]
    released = [(sub[i], i) for i in range(n) if unmet[i] == 0]
    heapq.heapify(released)        # (ready, row): released, not yet queued
    queue = []                     # rows waiting, FCFS by row
    running = []                   # (finish, row)
    free, done, events = int(slots), 0, 0

    def free_dependents(i, t):
        for k in dependents[bounds[i]:bounds[i + 1]]:
            unmet[k] -= 1
            if unmet[k] == 0:
                ready[k] = max(sub[k], t)
                heapq.heappush(released, (ready[k], k))

    while done < n:
        t = min(running[0][0] if running else np.iinfo(np.int64).max,
                released[0][0] if released else np.iinfo(np.int64).max)
        if not running and not released:
            raise RuntimeError("tasks left that can never be released")
        events += 1
        while running and running[0][0] <= t:
            _, i = heapq.heappop(running)
            free += need[i]
            done += 1
            if release == "finish":
                free_dependents(i, t)
        while released and released[0][0] <= t:
            heapq.heappush(queue, heapq.heappop(released)[1])
        while queue and need[queue[0]] <= free:
            i = heapq.heappop(queue)
            start[i], finish[i] = t, t + run[i]
            free -= need[i]
            heapq.heappush(running, (finish[i], i))
            if release == "start":
                free_dependents(i, t)
    return {"start": np.asarray(start, np.int64),
            "finish": np.asarray(finish, np.int64),
            "ready": np.asarray(ready, np.int64),
            "done": np.ones(n, dtype=bool), "n_events": events}
