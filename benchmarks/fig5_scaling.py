"""Paper Fig. 5: parallel performance of the scheduler.

The paper scales MPI ranks; our SPMD analogue has two measurable axes on
this 1-physical-core container:

  (a) *vectorized ensemble*: a B-seed ``sweep()`` (ONE vmapped executable)
      vs. a serial ``run()`` loop over the same scenarios — the SIMD
      parallelism that maps 1:1 onto devices;
  (b) *job-size scaling*: events/second as the per-simulation job count
      grows (the paper's "greater speedup for larger jobs" effect —
      vector lanes amortize fixed per-event cost);
  (c) *device-partitioned run*: the mesh-sharded sweep over 1, 2 and 4
      of this process's devices (in-process: a device belongs to one
      process).  Rehearse it on virtual CPU devices with
      ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.

Both sides of (a) go through the Scenario API end-to-end (trace
materialization + job-table build + device run), so the comparison is
apples-to-apples for what a user actually calls.
"""

from __future__ import annotations

import os

import numpy as np

from benchmarks.common import emit, series_to_csv, time_call
from repro.api import Scenario, SyntheticTrace, run, sweep

BASE = Scenario(trace=SyntheticTrace(n_jobs=300, seed=100, kind="das2"),
                total_nodes=400, policy="backfill")


def bench_ensemble(outdir: str):
    J = 300
    rows = []
    for B in (1, 4, 16, 64):
        seeds = [100 + i for i in range(B)]

        # return the n_events arrays so time_call's block_until_ready waits
        # for the async device work, not just the host-side dispatch
        t_sweep = time_call(
            lambda: [r.raw.n_events
                     for r in sweep(BASE, axes={"trace.seed": seeds}).results])
        t_loop = time_call(
            lambda: [run(BASE.with_(**{"trace.seed": s})).raw.n_events
                     for s in seeds],
            warmup=1, iters=1)
        events = B * 2 * J
        rows.append((B, t_loop, t_sweep, t_loop / t_sweep, events / t_sweep))
        emit(f"fig5_ensemble_B{B}", t_sweep,
             f"speedup_vs_serial={t_loop / t_sweep:.2f};"
             f"events_per_s={events / t_sweep:.0f}")
    series_to_csv(os.path.join(outdir, "fig5_ensemble.csv"),
                  ["batch", "t_serial_s", "t_sweep_s", "speedup",
                   "events_per_s"], rows)


def bench_job_size(outdir: str):
    rows = []
    for J in (200, 1000, 4000):
        scn = BASE.with_(policy="fcfs", trace=SyntheticTrace(
            n_jobs=J, seed=100, kind="das2"))
        t = time_call(lambda: run(scn).raw.n_events)
        rows.append((J, t, 2 * J / t))
        emit(f"fig5_jobsize_J{J}", t, f"events_per_s={2 * J / t:.0f}")
    series_to_csv(os.path.join(outdir, "fig5_jobsize.csv"),
                  ["jobs", "seconds", "events_per_s"], rows)


def bench_devices(outdir: str):
    """The mesh-sharded sweep over 1, 2, 4 of this process's devices.

    Runs in-process: a device belongs to one process, so the sweep cannot
    move to children once this process has touched JAX.  Device counts the
    host does not have are skipped and say so.
    """
    import jax
    from jax.sharding import Mesh

    B, J = 16, 200
    base = Scenario(trace=SyntheticTrace(n_jobs=J, seed=0, kind="das2"),
                    total_nodes=400, policy="backfill")
    axes = {"trace.seed": list(range(B))}
    devices = jax.devices()
    rows = []
    for d in (1, 2, 4):
        if d > len(devices):
            print(f"# fig5_devices_{d} skipped: {len(devices)} device(s)",
                  flush=True)
            continue
        mesh = Mesh(np.array(devices[:d]), ("sim",))
        warm = sweep(base, axes=axes, mesh=mesh)
        events = int(sum(np.asarray(r.raw.n_events) for r in warm.results))
        t = time_call(
            lambda: [r.raw.n_events
                     for r in sweep(base, axes=axes, mesh=mesh).results],
            warmup=0, iters=1)
        rows.append((d, t, events / t))
        emit(f"fig5_devices_{d}", t,
             f"events_per_s={events / t:.0f};"
             f"platform={devices[0].platform}")
    series_to_csv(os.path.join(outdir, "fig5_devices.csv"),
                  ["devices", "seconds", "events_per_s"], rows)


def main(outdir: str = "results") -> None:
    os.makedirs(outdir, exist_ok=True)
    bench_ensemble(outdir)
    bench_job_size(outdir)
    bench_devices(outdir)


if __name__ == "__main__":
    main()
