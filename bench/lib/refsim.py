"""The plain reference: an event-driven scheduling simulator on the host.

It implements the semantics the two configurations state, and imports
nothing of the program.  One event takes, in this order: the completions due
at the clock, the failures and repairs due at the clock, the arrivals due at
the clock, then a scheduling pass that starts jobs until the policy's
selector blocks.  Jobs are ranked by (submit, input index).

- ``fcfs``: the head of the queue starts when it fits, else nothing starts.
- ``sjf``: the job with the smallest estimate (then rank) starts when it
  fits, else nothing starts.
- ``backfill`` (EASY): the head starts when it fits.  Otherwise it gets a
  reservation at the shadow time, the earliest release (by the estimates of
  running jobs) at which enough nodes are free for it, and the lowest-ranked
  waiting job that fits now starts if it ends by the shadow time or uses no
  more than the nodes left over at the shadow time.  With
  ``reserve=False`` the reservation is dropped and any job that fits may
  start: that breaks EASY's guarantee and is the benchmark's control.
- Machine mode places concrete nodes: ``simple`` takes the lowest free ids,
  ``contiguous`` the best-fitting free run, ``spread`` round-robins over
  groups, ``topo`` fills the groups with the most free nodes first.
- Failures: a node that fails kills the job on it (machine mode), or the
  job covering slot ``node % up_nodes`` of the running jobs' node count in
  rank order (scalar mode).  A killed job is requeued at its rank with the
  work since its last checkpoint, plus the restart overhead, added back.

This is a trimmed copy of the program's own host simulator
(``repro.refsim``) with the paths neither configuration uses taken out.
Its cost per event grows with the waiting queue; at the configurations'
offered load of about 0.8 the queue stays short.
"""

from __future__ import annotations

import heapq
from dataclasses import dataclass
from typing import Dict, List, Optional

import numpy as np

from lib.workload import FAIL, INF_TIME

POLICIES = ("fcfs", "sjf", "backfill")
ALLOCS = ("simple", "contiguous", "spread", "topo")


@dataclass
class _Job:
    idx: int
    submit: int
    runtime: int
    estimate: int
    nodes: int
    start: int = -1
    finish: int = -1
    remaining: int = -1
    alloc_first: int = -1
    alloc_span: int = 0
    alloc_sum: int = 0
    last_start: int = -1
    n_restarts: int = 0
    lost_work: int = 0


# ---------------------------------------------------------------------------
# node placement
# ---------------------------------------------------------------------------


def _free_runs(owner: np.ndarray):
    """Maximal free runs as (length, start), in start order."""
    runs, start = [], None
    for i, busy in enumerate(owner >= 0):
        if busy:
            if start is not None:
                runs.append((i - start, start))
                start = None
        elif start is None:
            start = i
    if start is not None:
        runs.append((len(owner) - start, start))
    return runs


def _largest_free_run(owner: np.ndarray) -> int:
    return max((r[0] for r in _free_runs(owner)), default=0)


def _place(alloc: str, group: np.ndarray, owner: np.ndarray,
           need: int) -> np.ndarray:
    free_ids = np.nonzero(owner < 0)[0]
    if alloc == "simple":
        return free_ids[:need]
    if alloc == "contiguous":
        fits = [r for r in _free_runs(owner) if r[0] >= need]
        if not fits:
            return free_ids[:need]
        _, start = min(fits)
        return np.arange(start, start + need)
    if alloc == "spread":
        rank: Dict[int, int] = {}
        keyed = []
        for i in free_ids:
            g = int(group[i])
            rank[g] = rank.get(g, 0) + 1
            keyed.append((rank[g], g, int(i)))
        keyed.sort()
        return np.array(sorted(k[2] for k in keyed[:need]), dtype=np.int64)
    if alloc == "topo":
        per_group: Dict[int, list] = {}
        for i in free_ids:
            per_group.setdefault(int(group[i]), []).append(int(i))
        order = sorted(per_group, key=lambda g: (-len(per_group[g]), g))
        chosen: list = []
        for g in order:
            chosen.extend(per_group[g])
        return np.array(sorted(chosen[:need]), dtype=np.int64)
    raise ValueError(f"unknown allocation strategy {alloc!r}")


# ---------------------------------------------------------------------------
# the simulator
# ---------------------------------------------------------------------------


def _select(policy: str, waiting: List[_Job], running, free: int, cap: int,
            clock: int, reserve: bool) -> Optional[_Job]:
    if not waiting:
        return None
    if policy == "sjf":
        head = min(waiting, key=lambda j: (j.estimate, j.idx))
        return head if head.nodes <= cap else None
    head = min(waiting, key=lambda j: j.idx)
    if head.nodes <= cap:
        return head
    if policy == "fcfs":
        return None
    if not reserve:
        cands = [j for j in waiting if j is not head and j.nodes <= cap]
        return min(cands, key=lambda j: j.idx) if cands else None
    rel = sorted((max(j.last_start + j.estimate, clock + 1), j.idx, j.nodes)
                 for j in running)
    cum, shadow, extra = free, None, free
    for t, _idx, n in rel:
        cum += n
        if cum >= head.nodes:
            shadow, extra = t, cum - head.nodes
            break
    cands = [j for j in waiting
             if j is not head and j.nodes <= cap
             and ((shadow is not None and clock + j.estimate <= shadow)
                  or j.nodes <= min(free, extra))]
    return min(cands, key=lambda j: j.idx) if cands else None


def simulate(trace: Dict[str, np.ndarray], policy: str, *, total_nodes: int,
             groups: Optional[np.ndarray] = None, alloc: str = "simple",
             failures: Optional[dict] = None, requeue: bool = True,
             checkpoint_interval: int = 0, restart_overhead: int = 0,
             reserve: bool = True) -> Dict[str, np.ndarray]:
    """Schedule ``trace`` (host arrays) and return per-job columns in rank
    order: ``start``, ``finish``, ``done``, ``wait`` and, in machine mode
    (``groups``: the group id of every node), ``alloc_first``/
    ``alloc_span``/``alloc_sum``; with ``failures`` (a
    :func:`lib.workload.failure_stream`) ``n_restarts``/``lost_work``."""
    if policy not in POLICIES:
        raise ValueError(f"unknown policy {policy!r}")
    submit = np.asarray(trace["submit"], dtype=np.int64)
    submit = submit - (submit.min() if len(submit) else 0)
    runtime = np.maximum(np.asarray(trace["runtime"], dtype=np.int64), 1)
    estimate = np.maximum(np.asarray(trace.get("estimate", runtime),
                                     dtype=np.int64), 1)
    nodes = np.clip(np.asarray(trace["nodes"], dtype=np.int64), 1,
                    total_nodes)
    order = np.lexsort((np.arange(len(submit)), submit))
    jobs = [_Job(i, int(submit[o]), int(runtime[o]), int(estimate[o]),
                 int(nodes[o]), remaining=int(runtime[o]))
            for i, o in enumerate(order)]
    n = len(jobs)

    next_arrival = 0
    waiting: List[_Job] = []
    heap: List[tuple] = []
    running: Dict[int, _Job] = {}
    free = total_nodes
    clock = 0
    n_events = 0
    live = n
    owner = (np.full(total_nodes, -1, dtype=np.int64)
             if groups is not None else None)
    if failures is not None:
        st_time = failures["time"]
        st_node = failures["node"]
        st_kind = failures["kind"]
        n_stream = int((st_time < INF_TIME).sum())
    ptr = 0
    down = (np.zeros(total_nodes, dtype=bool)
            if failures is not None and owner is not None else None)

    def owner_view() -> np.ndarray:
        return owner if down is None else np.where(down, total_nodes, owner)

    def cap_now() -> int:
        if owner is None:
            return free
        view = owner_view()
        if alloc == "contiguous":
            return _largest_free_run(view)
        return int((view < 0).sum())

    def kill(j: _Job) -> None:
        nonlocal free
        el = clock - j.last_start
        saved = (el // checkpoint_interval) * checkpoint_interval \
            if checkpoint_interval > 0 else 0
        lost = el - saved
        del running[j.idx]
        free += j.nodes
        if owner is not None:
            owner[owner == j.idx] = -1
        if not requeue:
            raise NotImplementedError("only the requeue kill rule is stated")
        j.remaining = max(j.finish - clock + lost + restart_overhead, 1)
        j.finish = -1
        j.n_restarts += 1
        j.lost_work += lost + restart_overhead
        waiting.append(j)

    def more_events() -> bool:
        if failures is None:
            return bool(next_arrival < n or heap)
        return live > 0

    while more_events():
        while heap and (heap[0][1] not in running
                        or running[heap[0][1]].finish != heap[0][0]):
            heapq.heappop(heap)
        sources = []
        if next_arrival < n:
            sources.append(jobs[next_arrival].submit)
        if heap:
            sources.append(heap[0][0])
        if failures is not None and ptr < n_stream:
            sources.append(int(st_time[ptr]))
        clock = min(sources)
        n_events += 1
        while heap and heap[0][0] <= clock:
            fin, idx = heapq.heappop(heap)
            j = running.get(idx)
            if j is None or j.finish != fin:
                continue
            del running[idx]
            free += j.nodes
            live -= 1
            if owner is not None:
                owner[owner == idx] = -1
        while failures is not None and ptr < n_stream \
                and st_time[ptr] <= clock:
            node, kind = int(st_node[ptr]), int(st_kind[ptr])
            ptr += 1
            if kind == FAIL:
                if owner is not None:
                    if down[node]:
                        continue
                    victim = int(owner[node])
                    down[node] = True
                    free -= 1
                    if victim >= 0:
                        kill(running[victim])
                else:
                    busy = sum(j.nodes for j in running.values())
                    slot = node % max(free + busy, 1)
                    free -= 1
                    if slot < busy:
                        cum = 0
                        for j in sorted(running.values(), key=lambda v: v.idx):
                            cum += j.nodes
                            if cum > slot:
                                kill(j)
                                break
            else:
                if owner is not None:
                    if not down[node]:
                        continue
                    down[node] = False
                free += 1
        while next_arrival < n and jobs[next_arrival].submit <= clock:
            waiting.append(jobs[next_arrival])
            next_arrival += 1
        while True:
            j = _select(policy, waiting, list(running.values()), free,
                        cap_now(), clock, reserve)
            if j is None:
                break
            waiting.remove(j)
            if j.start < 0:
                j.start = clock
            j.last_start = clock
            if owner is not None:
                ids = _place(alloc, groups, owner_view(), j.nodes)
                owner[ids] = j.idx
                j.alloc_span = len(np.unique(groups[ids]))
                j.alloc_first = int(ids.min())
                j.alloc_sum = int((ids + 1).sum())
            j.finish = clock + j.remaining
            free -= j.nodes
            running[j.idx] = j
            heapq.heappush(heap, (j.finish, j.idx))

    out = {
        "submit": np.array([j.submit for j in jobs], dtype=np.int64),
        "runtime": np.array([j.runtime for j in jobs], dtype=np.int64),
        "nodes": np.array([j.nodes for j in jobs], dtype=np.int64),
        "start": np.array([j.start for j in jobs], dtype=np.int64),
        "finish": np.array([j.finish for j in jobs], dtype=np.int64),
        "n_events": n_events,
    }
    out["wait"] = out["start"] - out["submit"]
    out["done"] = out["start"] >= 0
    if failures is not None:
        out["n_restarts"] = np.array([j.n_restarts for j in jobs],
                                     dtype=np.int64)
        out["lost_work"] = np.array([j.lost_work for j in jobs],
                                    dtype=np.int64)
    if owner is not None:
        out["alloc_first"] = np.array([j.alloc_first for j in jobs],
                                      dtype=np.int64)
        out["alloc_span"] = np.array([j.alloc_span for j in jobs],
                                     dtype=np.int64)
        out["alloc_sum"] = np.array([j.alloc_sum for j in jobs],
                                    dtype=np.int64)
    return out
