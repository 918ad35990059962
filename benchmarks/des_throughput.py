"""Measured §Perf track: DES engine throughput (events/s), JAX vs reference.

This is the paper-side performance benchmark that hillclimbs iterate on —
per-policy event throughput on a fixed trace, a deps-heavy workflow case
exercising the sparse dependency counters + batched scheduling pass
(DESIGN.md §14), and the Pallas queue_select hot-spot microbenchmark at
scheduler-relevant queue sizes.

Besides the human-readable CSV rows it emits a machine-readable
``results/BENCH_engine.json`` — one entry per case with events/s, run time
and the compile/run split — so future PRs have a perf trajectory to regress
against (acceptance floor for this PR: >= 3x events/s on the deps-heavy
workflow case vs the dense-matrix engine, >= 1.0x on no-deps FCFS).
"""

from __future__ import annotations

import json
import os
import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import device_info, emit, series_to_csv, time_call
from repro.core.engine import simulate
from repro.core.jobs import POLICY_IDS, make_jobset
from repro.kernels.queue_select.ops import queue_select
from repro.refsim import simulate_reference
from repro.traces import sdsc_sp2_like
from repro.traces.workflows import galactic_like, workflow_to_trace

BENCH_JSON = "BENCH_engine.json"


def _measure(jobs, policy: str, total_nodes: int, iters: int = 3,
             service=None, malleable=None) -> dict:
    """events/s for one compiled engine call, with the compile/run split.

    The first call pays trace+compile; steady-state is the median of at
    least ``iters`` further calls, repeating (up to 15) until ~0.6 s of
    samples accumulate so millisecond-scale cases aren't at the mercy of
    scheduler noise.  ``n_events`` comes from the result itself, so the
    rate is exact for any schedule.
    """
    pol = POLICY_IDS[policy]
    t0 = time.perf_counter()
    res = simulate(jobs, pol, total_nodes, service=service,
                   malleable=malleable)
    res.n_events.block_until_ready()
    first = time.perf_counter() - t0
    times = []
    while len(times) < iters or (sum(times) < 0.6 and len(times) < 15):
        t0 = time.perf_counter()
        res = simulate(jobs, pol, total_nodes, service=service,
                       malleable=malleable)
        res.n_events.block_until_ready()
        times.append(time.perf_counter() - t0)
    run_s = float(np.median(times))
    n_events = int(res.n_events)
    return {
        "n_events": n_events,
        "run_s": run_s,
        "events_per_s": n_events / run_s,
        "compile_s": max(first - run_s, 0.0),
    }


def _galactic_jobs(tiles: int, width: int, total_nodes: int):
    """The deps-heavy workload: a chain-of-montage-tiles Galactic Plane DAG
    lowered onto the cluster (PR 3's workload at benchmark scale)."""
    trace = workflow_to_trace(galactic_like(tiles=tiles, width=width, seed=0))
    jobs = make_jobset(
        trace["submit"], trace["runtime"], trace["nodes"], trace["estimate"],
        deps=trace["deps"], total_nodes=total_nodes,
    )
    meta = {"n_jobs": len(trace["submit"]), "n_edges": len(trace["deps"]),
            "total_nodes": total_nodes}
    return jobs, meta


def run_bench(outdir: str = "results", *, smoke: bool = False) -> dict:
    os.makedirs(outdir, exist_ok=True)
    # schema 4: the report names the device that took its numbers
    # (schema 3: queue_select cases are timed compiled and carry
    # bytes/tile/mode so GB/s figures are comparable across cases;
    # schema 2 added generated_unix/finished_unix); pinned by
    # tests/test_bench_schema.py — bump the version when keys change
    report: dict = {"schema": 4, "smoke": smoke, "cases": {},
                    "generated_unix": time.time(), "device": device_info()}

    # ---- no-deps policy throughput on the SDSC-SP2-like trace --------------
    J = 200 if smoke else 2000
    total_nodes = 128
    trace = sdsc_sp2_like(J, seed=13)
    jobs = make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], total_nodes=total_nodes)
    rows = []
    for pol in ("fcfs", "sjf", "bestfit", "backfill"):
        m = _measure(jobs, pol, total_nodes)
        t0 = time.perf_counter()
        ref = simulate_reference(trace, pol, total_nodes=total_nodes)
        t_ref = time.perf_counter() - t0
        ref_rate = ref["n_events"] / t_ref
        report["cases"][f"nodeps_{pol}"] = {
            **m, "trace": "sdsc_sp2_like", "n_jobs": J,
            "total_nodes": total_nodes, "ref_events_per_s": ref_rate,
        }
        rows.append((pol, m["run_s"], m["events_per_s"], t_ref, ref_rate))
        emit(f"des_throughput_{pol}", m["run_s"],
             f"jax_events_per_s={m['events_per_s']:.0f};"
             f"ref_events_per_s={ref_rate:.0f}")
    series_to_csv(os.path.join(outdir, "des_throughput.csv"),
                  ["policy", "t_jax_s", "jax_events_per_s", "t_ref_s",
                   "ref_events_per_s"], rows)

    # ---- deps-heavy workflow cases (sparse counters + batched pass) --------
    wf_cases = ([("galactic_smoke", 2, 5, 16)] if smoke else
                [("galactic521", 8, 20, 64), ("galactic8k", 200, 12, 256)])
    for name, tiles, width, nodes in wf_cases:
        gjobs, meta = _galactic_jobs(tiles, width, nodes)
        for pol in ("fcfs", "backfill") if not smoke else ("fcfs",):
            m = _measure(gjobs, pol, nodes, iters=1 if name == "galactic8k" else 3)
            report["cases"][f"{name}_{pol}"] = {**m, **meta}
            emit(f"des_throughput_{name}_{pol}", m["run_s"],
                 f"jax_events_per_s={m['events_per_s']:.0f};"
                 f"n_edges={meta['n_edges']}")

    # ---- open-arrival serving case (deadline state + autoscale ticks) ------
    from repro.api import (AutoscalePolicy, Scenario, ServiceClass,
                           ServiceTrace, build_jobset)

    svc_spec = ServiceTrace(
        horizon=4096 if smoke else 1 << 16, rate=0.04, seed=5,
        max_jobs=256 if smoke else 4096,
        classes=(ServiceClass("interactive", nodes=1, mean_runtime=30,
                              slo_wait=60),
                 ServiceClass("batch", nodes=8, mean_runtime=600,
                              dist="exponential", slo_wait=1800, weight=0.3)),
        autoscale=AutoscalePolicy(up_threshold=48, down_threshold=8,
                                  min_nodes=16, max_nodes=64, step=8,
                                  interval=256,
                                  max_ticks=16 if smoke else 256))
    svc_scn = Scenario(trace=svc_spec, total_nodes=64, policy="fcfs")
    svc_jobs = build_jobset(svc_scn)
    m = _measure(svc_jobs, "fcfs", 64, service=svc_spec.plan())
    report["cases"]["serving_open_fcfs"] = {
        **m, "trace": "service_poisson", "n_jobs": svc_spec.plan().n_requests,
        "total_nodes": 64,
    }
    emit("des_throughput_serving_open_fcfs", m["run_s"],
         f"jax_events_per_s={m['events_per_s']:.0f};"
         f"n_requests={svc_spec.plan().n_requests}")

    # ---- moldable width choice on the no-deps trace (DESIGN.md §17) --------
    from repro.malleable import MalleableModel, make_mal_ctx, materialize_plan

    mal_model = MalleableModel(curve="amdahl", param=0.1, min_width=1,
                               max_width=16, mode="moldable")
    mal_plan = materialize_plan(mal_model, trace, total_nodes=total_nodes)
    m = _measure(jobs, "backfill", total_nodes,
                 malleable=make_mal_ctx(mal_plan))
    report["cases"]["moldable_backfill"] = {
        **m, "trace": "sdsc_sp2_like", "n_jobs": J,
        "total_nodes": total_nodes, "n_widths": mal_plan.n_widths,
    }
    emit("des_throughput_moldable_backfill", m["run_s"],
         f"jax_events_per_s={m['events_per_s']:.0f};"
         f"n_widths={mal_plan.n_widths}")

    # ---- streaming trace replay (DESIGN.md §19) ----------------------------
    # archive-scale jobs/s through the bounded-window crash-safe runner; the
    # arrival rate puts utilization ~0.76, so the backlog stays inside the
    # window (no doubling ladder) — replay_smoke.py covers degraded paths
    from repro.replay import replay_trace
    from repro.traces import synthetic_trace

    RJ = 2_000 if smoke else 200_000
    rwin = 512 if smoke else 4096
    rtrace = synthetic_trace(RJ, seed=3, mean_interarrival=220.0)
    t0 = time.perf_counter()
    rres = replay_trace(rtrace, "backfill", total_nodes=128, window=rwin)
    t_rep = time.perf_counter() - t0
    rsum = rres.summary()
    report["cases"]["trace_replay"] = {
        # single-shot timing: the per-window-shape compiles are part of a
        # real replay, so they stay inside run_s (conservative rate)
        "run_s": t_rep,
        "n_events": rsum["n_events"],
        "events_per_s": rsum["n_events"] / t_rep,
        "compile_s": 0.0,
        "n_jobs": RJ,
        "jobs_per_s": RJ / t_rep,
        "window": rsum["window"],
        "peak_live": rsum["peak_live"],
        "n_rounds": rsum["n_rounds"],
        "trace": "synthetic", "total_nodes": 128,
    }
    emit("trace_replay", t_rep,
         f"jobs_per_s={RJ / t_rep:.0f};rounds={rsum['n_rounds']};"
         f"peak_live={rsum['peak_live']}")

    # ---- scheduler hot-spot kernel at production queue sizes ---------------
    # Timed on the *compiled* default lowering (Pallas on TPU, blocked jnp
    # reduction elsewhere — ISSUE 8: the old interpret=True default timed
    # the Pallas Python interpreter, reading 0.04 GB/s at N=1M).  GB/s is
    # derived from the actual argument nbytes, not a hardcoded element size.
    rng = np.random.default_rng(0)
    tile = 8192
    for N in ((65_536,) if smoke else (65_536, 1_048_576)):
        scores = jnp.asarray(rng.integers(0, 1 << 20, N).astype(np.int32))
        feas = jnp.asarray((rng.random(N) < 0.1).astype(np.int32))
        t = time_call(lambda: queue_select(scores, feas, tile=tile))
        nbytes = int(scores.nbytes) + int(feas.nbytes)
        gbps = (nbytes / t) / 1e9
        report["cases"][f"queue_select_N{N}"] = {
            "run_s": t, "GBps": gbps, "bytes": nbytes, "tile": tile,
            "mode": "compiled",
        }
        emit(f"queue_select_N{N}", t, f"compiled;tile={tile};GBps={gbps:.2f}")

    report["finished_unix"] = time.time()
    path = os.path.join(outdir, BENCH_JSON)
    with open(path, "w") as f:
        json.dump(report, f, indent=1, sort_keys=True)
    print(f"# wrote {path} (device: {report['device']})", flush=True)
    return report


def main(outdir: str = "results") -> None:
    run_bench(outdir, smoke=False)


def smoke(outdir: str = "results") -> None:
    """CI dry pass: tiny sizes, same artifact schema (uploaded by CI)."""
    run_bench(outdir, smoke=True)


if __name__ == "__main__":
    import sys
    smoke() if "--smoke" in sys.argv else main()
