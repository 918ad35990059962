"""What-if service benchmark (DESIGN.md §20): cold vs warm query latency.

The service's performance claim is that a long-running planner amortizes
XLA compiles across queries: the first query against a scenario bucket
pays the compile (cold), every subsequent query — different candidate
values, different deltas, same shapes — reuses the persistent executable
(warm).  This benchmark measures both paths for each query family against
the built-in demo fleet and pins the cache counters next to the timings,
so a regression that silently re-compiles per query (e.g. a static-key
change that buckets by candidate *values*) shows up as warm_compiles > 0
and a warm/cold ratio near 1.

Emits ``fig_whatif/<family>/<path>`` CSV rows and a machine-readable
``results/fig_whatif.json`` (schema 1, uploaded by the CI service-smoke
job next to the other benchmark artifacts).
"""

from __future__ import annotations

import json
import os
import time

from benchmarks import common
from repro.api import cache_stats, reset_cache_stats
from repro.service import (
    CapacityPlanner, JobRequest, Objective, ScenarioDelta, WhatIfQuery,
    demo_fleet,
)


def _queries(smoke: bool):
    # lowest point sized so the demo fleet's padded failure capacity is not
    # saturated (a truncated stream measures the cutoff, not reliability)
    mtbf_grid = (500e3, 2000e3) if smoke else (500e3, 1000e3, 2000e3, 4000e3)
    deltas = (0, 64) if smoke else (0, 32, 64, 128)
    return {
        "placement": [
            WhatIfQuery(kind="placement",
                        job=JobRequest(submit=0, runtime=400, nodes=w))
            for w in (4, 16, 48)],
        "capacity": [
            WhatIfQuery(kind="capacity", queue="batch",
                        deltas=tuple(ScenarioDelta(add_nodes=d)
                                     for d in deltas))],
        "reliability": [
            WhatIfQuery(kind="reliability", queue="flaky",
                        mtbf_grid=mtbf_grid,
                        objective=Objective(metric="goodput", goal="max"))],
    }


def _run(smoke: bool, outdir: str = "results") -> None:
    os.makedirs(outdir, exist_ok=True)
    report = {"schema": 1, "smoke": smoke, "device": common.device_info(),
              "generated_unix": time.time(), "cases": {}}
    families = _queries(smoke)

    for family, queries in families.items():
        # cold: drop the cached runners so the first answer recompiles
        planner = CapacityPlanner(demo_fleet())
        reset_cache_stats(clear=True)
        t0 = time.time()
        for q in queries:
            planner.answer(q)
        cold_s = time.time() - t0
        cold = cache_stats()

        reset_cache_stats()
        t0 = time.time()
        for q in queries:
            planner.answer(q)
        warm_s = time.time() - t0
        warm = cache_stats()
        assert warm.compiles == 0, (
            f"{family}: warm pass recompiled {warm.compiles}x — the "
            "persistent-executable contract regressed")

        for path, secs, stats in (("cold", cold_s, cold),
                                  ("warm", warm_s, warm)):
            report["cases"][f"{family}_{path}"] = {
                "run_s": secs, "n_queries": len(queries),
                "compiles": stats.compiles, "hits": stats.hits,
            }
            common.emit(f"fig_whatif/{family}/{path}",
                        secs / len(queries),
                        f"compiles={stats.compiles}:hits={stats.hits}")

    report["finished_unix"] = time.time()
    out = os.path.join(outdir, "fig_whatif.json")
    with open(out, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
        f.write("\n")
    print(f"# wrote {out}", flush=True)


def main() -> None:
    _run(smoke=False)


def smoke() -> None:
    _run(smoke=True)


if __name__ == "__main__":
    import sys

    smoke() if "--smoke" in sys.argv else main()
