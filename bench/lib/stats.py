"""Rate, percentile and summary arithmetic of the benchmark.

:func:`percentile` is the exact linear-interpolation percentile of
``repro.core.metrics.percentiles`` (identical to ``numpy.percentile``), and
:func:`queue_summary` the scalar queue metrics of ``metrics.summary`` plus
the ``p99_wait`` the what-if service adds; both are copied here so the
reference side of a comparison computes them without the program.
"""

from __future__ import annotations

from typing import Dict

import numpy as np


def percentile(values, q: float) -> float:
    """Exact percentile ``q`` (0–100) of ``values``; NaN when empty."""
    v = np.sort(np.asarray(values, dtype=np.float64).ravel())
    if v.size == 0:
        return float("nan")
    pos = q / 100.0 * (v.size - 1)
    lo = int(np.floor(pos))
    hi = min(lo + 1, v.size - 1)
    t = pos - lo
    d = v[hi] - v[lo]
    return float(v[hi] - d * (1.0 - t) if t >= 0.5 else v[lo] + d * t)


def rate(count: float, seconds: float) -> float:
    """Work per second over a whole window."""
    if seconds <= 0:
        raise ValueError(f"window of {seconds} s")
    return float(count) / float(seconds)


def queue_summary(res: Dict[str, np.ndarray], total_nodes: int
                  ) -> Dict[str, float]:
    """Scalar metrics of one finished queue over its completed jobs."""
    done = np.asarray(res["done"], dtype=bool)
    submit = np.asarray(res["submit"])[done]
    start = np.asarray(res["start"])[done]
    finish = np.asarray(res["finish"])[done]
    nodes = np.asarray(res["nodes"])[done]
    runtime = np.asarray(res["runtime"])[done]
    if len(submit) == 0:
        return {}
    wait = (start - submit).astype(np.float64)
    run = runtime.astype(np.float64)
    bsld = np.maximum((wait + run) / np.maximum(run, 10.0), 1.0)
    makespan = float(finish.max() - submit.min())
    node_seconds = float((nodes.astype(np.float64) * run).sum())
    return {
        "n_jobs": float(len(submit)),
        "avg_wait": float(wait.mean()),
        "p50_wait": percentile(wait, 50),
        "p95_wait": percentile(wait, 95),
        "p99_wait": percentile(wait, 99),
        "max_wait": float(wait.max()),
        "avg_bounded_slowdown": float(bsld.mean()),
        "makespan": makespan,
        "utilization": (node_seconds / (total_nodes * makespan)
                        if makespan > 0 else 0.0),
        "throughput": float(len(submit)) / makespan if makespan > 0 else 0.0,
    }
