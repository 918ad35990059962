"""Inputs of the benchmark, made from the seed alone.

Everything here is the benchmark's own copy of arithmetic the program also
has, so that no later change to the program can change what it is fed or
what it is compared against:

- :func:`synthetic_trace` is the bursty, heavy-tailed job generator of
  ``repro.traces.synthetic.synthetic_trace`` (same draws in the same order);
- :func:`failure_stream` is ``repro.reliability.FailureModel.materialize``
  followed by ``merge_stream``: the per-node renewal process of failures
  and repairs, merged into one time-ordered stream;
- :func:`question_seed` gives every question of a run a seed of its own,
  derived from ``--seed`` and the question's index, so no host cache of the
  program ever serves a repeated question.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np

INF_TIME = 2**30 - 1      # the engine's int32 "never" sentinel
FAIL, REPAIR = 0, 1


def question_seed(seed: int, index: int) -> int:
    """A 63-bit seed for question ``index`` of the run started with
    ``seed`` (any non-negative integer, wider than 32 bits included)."""
    state = np.random.SeedSequence([int(seed), int(index)]).generate_state(
        1, np.uint64)
    return int(state[0] >> np.uint64(1))


def synthetic_trace(n_jobs: int, *, seed: int, mean_interarrival: float,
                    runtime_lognorm, max_runtime: int, node_pow2_max: int,
                    large_frac: float, total_nodes: int, estimate_factor,
                    burstiness: float) -> Dict[str, np.ndarray]:
    """Markov-modulated arrivals (a ``burstiness`` share of gaps cut by 8),
    lognormal runtimes clipped to ``max_runtime``, power-of-two widths up to
    ``2**node_pow2_max`` plus a ``large_frac`` tail of wide jobs, and
    estimates of ``runtime × U(estimate_factor)``."""
    rng = np.random.default_rng(seed)
    burst = rng.random(n_jobs) < burstiness
    gaps = rng.exponential(mean_interarrival, n_jobs)
    gaps = np.where(burst, gaps / 8.0, gaps)
    submit = np.cumsum(gaps).astype(np.int64)

    mu, sigma = runtime_lognorm
    runtime = np.clip(rng.lognormal(mu, sigma, n_jobs), 1,
                      max_runtime).astype(np.int64)

    pows = rng.integers(0, node_pow2_max + 1, n_jobs)
    nodes = (2 ** pows).astype(np.int64)
    big = rng.random(n_jobs) < large_frac
    nodes = np.where(big, rng.integers(total_nodes // 4, total_nodes + 1,
                                       n_jobs), nodes)
    nodes = np.clip(nodes, 1, total_nodes)

    lo, hi = estimate_factor
    estimate = np.clip((runtime * rng.uniform(lo, hi, n_jobs)).astype(
        np.int64), runtime, None)
    return {"submit": submit, "runtime": runtime, "nodes": nodes,
            "estimate": estimate}


def config_trace(config: dict, n_jobs: int, seed: int) -> Dict[str, np.ndarray]:
    """``n_jobs`` jobs of the configuration's workload, reshuffled by
    ``seed``.

    Every seed gets the same set of arrival gaps and the same set of jobs
    (runtime, width and estimate together): one sample of ``n_jobs`` drawn
    with the configuration's ``sample_seed``, its gaps and its jobs each put
    in an order of the seed's.  So the offered load, and with it the work of
    a question, is the same for every seed, while the schedule is not: with
    heavy-tailed runtimes a fresh sample per seed moved the load of a
    512-job backlog between 0.44 and 0.67.
    """
    w = config["workload"]
    sample = synthetic_trace(
        n_jobs, seed=w["sample_seed"],
        mean_interarrival=w["mean_interarrival"],
        runtime_lognorm=tuple(w["runtime_lognorm"]),
        max_runtime=w["max_runtime"], node_pow2_max=w["node_pow2_max"],
        large_frac=w["large_frac"], total_nodes=w["total_nodes"],
        estimate_factor=tuple(w["estimate_factor"]),
        burstiness=w["burstiness"])
    rng = np.random.default_rng(seed)
    gaps = np.diff(sample["submit"], prepend=0)
    jobs = rng.permutation(n_jobs)
    out = {k: sample[k][jobs] for k in ("runtime", "nodes", "estimate")}
    out["submit"] = np.cumsum(rng.permutation(gaps))
    return out


def offered_load(trace: Dict[str, np.ndarray], total_nodes: int) -> float:
    """Node-seconds asked for over node-seconds the machine has between the
    first and the last arrival."""
    span = float(trace["submit"].max() - trace["submit"].min())
    return float((trace["nodes"] * trace["runtime"]).sum()) / (
        total_nodes * span)


def failure_stream(*, mtbf: float, seed: int, n_nodes: int, horizon: int,
                   max_failures: int, mean_repair: int) -> dict:
    """Exponential up-times of mean ``mtbf`` and repairs of mean
    ``mean_repair`` on every node, in ``[0, horizon)``; the earliest
    ``max_failures`` (failure, repair) pairs, merged into one stream
    ordered by time with failures before repairs on ties."""
    rng = np.random.default_rng(seed)
    events = []
    for node in range(n_nodes):
        t = 0
        for _ in range(max_failures):
            dt = -mtbf * math.log1p(-rng.random())
            t_fail = t + max(1, int(math.ceil(dt)))
            if t_fail >= horizon:
                break
            r = -mean_repair * math.log1p(-rng.random())
            t_repair = min(t_fail + max(1, int(math.ceil(r))), INF_TIME - 1)
            events.append((t_fail, node, t_repair))
            t = t_repair
    events.sort()
    truncated = len(events) > max_failures
    events = events[:max_failures]
    fail_t = np.full(max_failures, INF_TIME, dtype=np.int64)
    fail_n = np.zeros(max_failures, dtype=np.int64)
    rep_t = np.full(max_failures, INF_TIME, dtype=np.int64)
    for i, (tf, node, tr) in enumerate(events):
        fail_t[i], fail_n[i], rep_t[i] = tf, node, tr
    times = np.concatenate([fail_t, rep_t])
    nodes = np.concatenate([fail_n, fail_n])
    kinds = np.concatenate([np.full(max_failures, FAIL),
                            np.full(max_failures, REPAIR)])
    order = np.argsort(times, kind="stable")
    return {"time": times[order], "node": nodes[order], "kind": kinds[order],
            "n_failures": len(events), "truncated": truncated}


def swf_lines(trace: Dict[str, np.ndarray]) -> str:
    """The trace as Standard Workload Format text: 18 fields per job, with
    the run time, the requested processors and the requested time set."""
    rows = []
    for i, (s, r, n, e) in enumerate(zip(trace["submit"], trace["runtime"],
                                         trace["nodes"], trace["estimate"])):
        rows.append(f"{i + 1} {int(s)} -1 {int(r)} {int(n)} -1 -1 {int(n)} "
                    f"{int(e)} -1 1 -1 -1 -1 -1 -1 -1 -1")
    return "; synthetic SDSC-SP2-shaped queue\n" + "\n".join(rows) + "\n"
