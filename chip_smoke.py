#!/usr/bin/env python3
"""Bring-up run of the scheduler's main path on a TPU.

    python chip_smoke.py             # one chip: run, sweep, replay, whatif
    python chip_smoke.py --chips 4   # four chips: the sharded sweep and the
                                     # sharded multicluster run, each against
                                     # its one-device twin, and nothing else

Everything runs in this one process: a chip belongs to one process, so the
what-if server runs on a thread.  Each phase prints one JSON line with its
wall seconds, compile seconds and what it counted.  Each result is checked
bit-exactly against the host reference simulator (or, on four chips,
against the same program on one device); a mismatch or any other failure
ends the run with a non-zero exit and no ``ok`` line.  Without a TPU the
script exits non-zero at once.  The last line is one JSON object naming
the device.  This is a bring-up check, not a benchmark: its seconds come
from one cold run.

The sizes are an HPC centre's: a 16k-job SDSC-SP2-shaped backlog on 128
nodes, a 1,024-node dragonfly with node failures, a 48-point policy ×
allocator × MTBF bucket, a 100k-job archive replay and a what-if fleet of
4,096-job queues.  ``SIZES["tiny"]`` is the same program at toy sizes for
rehearsal on the CPU (see ``tests/test_chip_smoke.py``).
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import shutil
import sys
import threading
import time
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "results", "chip_smoke")  # replay checkpoints
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import (  # noqa: E402
    FailureModel, Result, Scenario, SweepResult, SyntheticTrace, Topology,
    cache_stats, reset_cache_stats, run, run_ref, sweep,
)
from repro.compile_cache import enable_compile_cache  # noqa: E402

SEED = 2025
SWEEP_AXES = {"policy": ("fcfs", "sjf", "backfill"),
              "alloc": ("simple", "contiguous", "spread", "topo")}


@dataclasses.dataclass(frozen=True)
class Sizes:
    run_jobs: int          # SDSC-SP2 queue, 128 nodes, scalar mode
    machine: tuple         # dragonfly (groups, nodes per group)
    machine_jobs: int      # machine-mode run (topo placement + failures)
    sweep_jobs: int        # per lane of the 48-lane bucket
    mtbf: tuple            # per-node MTBF grid (seconds) of the sweep
    horizon: int           # failure horizon (seconds), covers the makespan
    max_failures: int      # padded failure capacity
    replay_jobs: int
    replay_window: int
    replay_prefix: int     # checked against the reference simulator
    whatif_jobs: int
    whatif_mesh: tuple     # mesh2d (rows, cols) of the contiguous queue
    mc_jobs: int           # per cluster of the four-chip multicluster run


SIZES = {
    "full": Sizes(run_jobs=16_384, machine=(32, 32), machine_jobs=4096,
                  sweep_jobs=2048, mtbf=(5e7, 1e8, 2e8, 4e8),
                  horizon=1 << 22, max_failures=256, replay_jobs=100_000,
                  replay_window=4096, replay_prefix=20_000,
                  whatif_jobs=4096, whatif_mesh=(32, 32), mc_jobs=4096),
    "tiny": Sizes(run_jobs=256, machine=(4, 8), machine_jobs=128,
                  sweep_jobs=64, mtbf=(2e5, 4e5, 8e5, 1.6e6),
                  horizon=1 << 18, max_failures=256, replay_jobs=3000,
                  replay_window=256, replay_prefix=1000, whatif_jobs=128,
                  whatif_mesh=(4, 8), mc_jobs=120),
}

class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# compile accounting
# ---------------------------------------------------------------------------

_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


class CompileMeter:
    """Seconds JAX spent tracing, lowering and compiling, and the number
    of XLA compiles, read from ``jax.monitoring``."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in _COMPILE_EVENTS:
            self.seconds += duration
            if event == _COMPILE_EVENTS[-1]:
                self.compiles += 1


@functools.lru_cache(maxsize=None)
def compile_meter() -> CompileMeter:
    """The process's one meter (a listener cannot be taken back)."""
    return CompileMeter()


class Phase:
    """Wall seconds, compile seconds and XLA compiles of one phase; prints
    its JSON line on a clean exit."""

    def __init__(self, name: str):
        self.name = name
        self.counts: dict = {}
        self.meter = compile_meter()

    def __enter__(self) -> "Phase":
        self._c0 = (self.meter.seconds, self.meter.compiles)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is None:
            line = {"phase": self.name,
                    "wall_s": time.perf_counter() - self._t0,
                    "compile_s": self.meter.seconds - self._c0[0],
                    "compiles": self.meter.compiles - self._c0[1],
                    **self.counts}
            print(json.dumps(line), flush=True)
        return False


def timed(fn, *args, **kwargs):
    """(result, seconds) with the device work finished inside the clock."""
    t0 = time.perf_counter()
    out = fn(*args, **kwargs)
    # Result and SweepResult are not pytrees: wait on the arrays they hold
    if isinstance(out, SweepResult):
        jax.block_until_ready([r.raw for r in out.results])
    else:
        jax.block_until_ready(out.raw if isinstance(out, Result) else out)
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# scenarios
# ---------------------------------------------------------------------------


def sdsc_scaled(n_jobs: int, n_nodes: int, seed: int) -> SyntheticTrace:
    """The SDSC-SP2 generator's shape (``traces.sdsc_sp2_like``) on an
    ``n_nodes`` machine: job widths scale with the machine (powers of two
    up to ``n_nodes`` plus the 6% wide tail) and arrivals keep their rate,
    so the offered load stays that of the 128-node log."""
    pow2 = int(np.log2(n_nodes))
    return SyntheticTrace(n_jobs=n_jobs, seed=seed, kind="generic", params=(
        ("mean_interarrival", 430.0), ("runtime_lognorm", (6.2, 1.9)),
        ("max_runtime", 18 * 3600), ("node_pow2_max", pow2),
        ("large_frac", 0.06), ("total_nodes", n_nodes),
        ("estimate_factor", (1.2, 5.0)), ("burstiness", 0.4)))


def machine_scenario(sz: Sizes, n_jobs: int, mtbf: float) -> Scenario:
    topo = Topology.dragonfly(*sz.machine)
    return Scenario(
        trace=sdsc_scaled(n_jobs, topo.n_nodes, SEED + 1),
        topology=topo, alloc="topo", policy="backfill",
        failures=FailureModel(mtbf=mtbf, seed=SEED, horizon=sz.horizon,
                              max_failures=sz.max_failures))


def check_untruncated(scn: Scenario) -> int:
    ft = scn.failures.materialize(int(scn.total_nodes))
    check(not ft.truncated, f"failure stream truncated at "
          f"{scn.failures.max_failures} (mtbf={scn.failures.mtbf:g})")
    return ft.n_failures


def check_same(name: str, res, ref, *, node_maps: bool) -> None:
    a, b = res.to_np(), ref.to_np()
    check(res.matches(ref, node_maps=node_maps),
          f"{name}: start/finish differ from the reference simulator")
    check(int(a["n_events"]) == int(b["n_events"]),
          f"{name}: {a['n_events']} events, reference {b['n_events']}")
    check(int(a["done"].sum()) == int(b["done"].sum()),
          f"{name}: done counts differ from the reference simulator")


def leaves_equal(a, b) -> bool:
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y)) for x, y in zip(la, lb))


# ---------------------------------------------------------------------------
# one-chip phases
# ---------------------------------------------------------------------------


def phase_run(sz: Sizes) -> None:
    scalar = Scenario(
        trace=SyntheticTrace(n_jobs=sz.run_jobs, seed=SEED, kind="sdsc_sp2"),
        total_nodes=128, policy="backfill")
    machine = machine_scenario(sz, sz.machine_jobs, sz.mtbf[0])
    with Phase("run") as ph:
        n_fail = check_untruncated(machine)
        for name, scn in (("scalar", scalar), ("machine", machine)):
            res, dev_s = timed(run, scn)
            t0 = time.perf_counter()
            ref = run_ref(scn)
            ref_s = time.perf_counter() - t0
            check_same(f"run/{name}", res, ref,
                       node_maps=scn.topology is not None)
            ph.counts[name] = {
                "jobs": scn.trace.n_jobs, "nodes": int(scn.total_nodes),
                "events": int(res.raw.n_events), "device_s": dev_s,
                "ref_s": ref_s}
        ph.counts["machine"]["failures"] = n_fail


def phase_sweep(sz: Sizes) -> None:
    base = machine_scenario(sz, sz.sweep_jobs, sz.mtbf[0])
    axes = {**SWEEP_AXES, "failures.mtbf": sz.mtbf}
    with Phase("sweep") as ph:
        for m in sz.mtbf:
            check_untruncated(base.with_(**{"failures.mtbf": m}))
        reset_cache_stats(clear=True)
        grid, dev_s = timed(sweep, base, axes)
        stats = cache_stats()
        check(grid.n_compiles == 1 and stats.compiles == 1,
              f"sweep compiled {stats.compiles} executables in "
              f"{grid.n_compiles} buckets, want 1")
        lane = int(np.random.default_rng(SEED).integers(len(grid)))
        t0 = time.perf_counter()
        check_same(f"sweep/lane{lane}", grid[lane],
                   run_ref(grid[lane].scenario), node_maps=True)
        ref_s = time.perf_counter() - t0
        events = np.array([int(r.raw.n_events) for r in grid.results])
        ph.counts.update({
            "lanes": len(grid), "jobs_per_lane": sz.sweep_jobs,
            "nodes": int(base.total_nodes), "events": int(events.sum()),
            "lane_occupancy": float(events.sum()
                                    / (len(events) * events.max())),
            "sweep_compiles": stats.compiles, "checked_lane": lane,
            "point": grid.points[lane], "device_s": dev_s, "ref_s": ref_s})


def phase_replay(sz: Sizes, out_dir: str) -> None:
    from repro.refsim import replay_reference
    from repro.replay import replay_trace
    from repro.traces import synthetic_trace

    nodes, window = 128, sz.replay_window
    with Phase("replay") as ph:
        trace = synthetic_trace(sz.replay_jobs, seed=SEED,
                                mean_interarrival=220.0)
        ckpt = os.path.join(out_dir, "replay_ckpt")
        shutil.rmtree(ckpt, ignore_errors=True)
        full, dev_s = timed(replay_trace, trace, "backfill",
                            total_nodes=nodes, window=window, ckpt_dir=ckpt)
        s = full.summary()
        check(s["n_done"] + s["n_aborted"] == sz.replay_jobs,
              f"replay finished {s['n_done']} + {s['n_aborted']} of "
              f"{sz.replay_jobs} jobs")
        check(s["peak_live"] <= s["window"], "replay window overflowed")
        check(bool(os.listdir(ckpt)), "replay wrote no checkpoint")

        pfx = {k: v[:sz.replay_prefix] for k, v in trace.items()}
        part = replay_trace(dict(pfx), "backfill", total_nodes=nodes,
                            window=window)
        t0 = time.perf_counter()
        ref = replay_reference(dict(pfx), "backfill", total_nodes=nodes)
        ref_s = time.perf_counter() - t0
        check(np.array_equal(part.start, ref["start"])
              and np.array_equal(part.done, ref["done"])
              and np.array_equal(part.finish[part.done],
                                 ref["finish"][ref["done"]])
              and part.n_events == int(ref["n_events"]),
              "replay prefix differs from the reference simulator")
        ph.counts.update({
            "jobs": sz.replay_jobs, "nodes": nodes, "window": s["window"],
            "events": s["n_events"], "rounds": s["n_rounds"],
            "peak_live": s["peak_live"], "checked_prefix": sz.replay_prefix,
            "device_s": dev_s, "jobs_per_s": sz.replay_jobs / dev_s,
            "ref_s": ref_s})


def whatif_fleet(sz: Sizes) -> dict:
    rows, cols = sz.whatif_mesh
    return {
        "batch": Scenario(
            trace=SyntheticTrace(n_jobs=sz.whatif_jobs, seed=SEED,
                                 kind="sdsc_sp2"),
            total_nodes=128, policy="fcfs"),
        "mesh": Scenario(
            trace=sdsc_scaled(sz.whatif_jobs, rows * cols, SEED + 2),
            topology=Topology.mesh2d(rows, cols), policy="sjf",
            alloc="contiguous"),
        "flaky": Scenario(
            trace=SyntheticTrace(n_jobs=sz.whatif_jobs, seed=SEED + 3,
                                 kind="sdsc_sp2"),
            total_nodes=128, policy="backfill",
            failures=FailureModel(mtbf=sz.mtbf[1], seed=SEED,
                                  horizon=sz.horizon,
                                  max_failures=sz.max_failures)),
    }


def _http(url: str, doc=None) -> dict:
    data = None if doc is None else json.dumps(doc).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=1200) as r:
        return json.loads(r.read())


def phase_whatif(sz: Sizes) -> None:
    from repro.service import enriched_summary, make_server
    from repro.service.planner import jsonable

    fleet = whatif_fleet(sz)
    queries = {
        "placement": {"version": 1, "kind": "placement",
                      "job": {"submit": 0, "runtime": 3600, "nodes": 64}},
        "capacity": {"version": 1, "kind": "capacity", "queue": "batch",
                     "deltas": [{"add_nodes": d} for d in (0, 32, 64, 128)]},
        "reliability": {"version": 1, "kind": "reliability",
                        "queue": "flaky", "mtbf_grid": list(sz.mtbf),
                        "objective": {"metric": "goodput", "goal": "max"}},
    }
    server = make_server(fleet)
    thread = threading.Thread(target=server.serve_forever, daemon=True)
    thread.start()
    try:
        with Phase("whatif") as ph:
            for m in sz.mtbf:
                check_untruncated(fleet["flaky"].with_(
                    **{"failures.mtbf": m}))
            for family, q in queries.items():
                lat = {}
                for path in ("cold", "warm"):
                    t0 = time.perf_counter()
                    ans = _http(server.url + "/query", q)
                    lat[path] = time.perf_counter() - t0
                    check(ans.get("kind") == family,
                          f"whatif/{family}/{path}: {ans}")
                    lat[f"{path}_compiles"] = ans["cache"]["compiles"]
                check(lat["cold_compiles"] >= 1,
                      f"whatif/{family}: cold query compiled nothing")
                check(lat["warm_compiles"] == 0,
                      f"whatif/{family}: warm query compiled "
                      f"{lat['warm_compiles']} times")
                ph.counts[family] = lat
            status = _http(server.url + "/fleet")
            t0 = time.perf_counter()
            for name, scn in fleet.items():
                want = json.loads(json.dumps(
                    jsonable(enriched_summary(run_ref(scn)))))
                check(status["queues"][name]["summary"] == want,
                      f"whatif/{name}: baseline differs from the reference "
                      "simulator")
            ph.counts.update({"queues": {n: s.trace.n_jobs
                                         for n, s in fleet.items()},
                              "ref_s": time.perf_counter() - t0})
    finally:
        server.shutdown()
        server.server_close()
        thread.join()


# ---------------------------------------------------------------------------
# four-chip phases
# ---------------------------------------------------------------------------


def _allreduce_in_loops(hlo: str) -> dict:
    """Which while-loop computations of an HLO module hold an all-reduce."""
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line.startswith(" ") and "{" in line:
            name = line.split()[1] if line.startswith("ENTRY") \
                else line.split()[0]
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    conds, bodies = set(), set()
    for lines in comps.values():
        for line in lines:
            if " while(" in line:
                for part in line.split(", "):
                    if part.startswith("condition="):
                        conds.add(part.split("=", 1)[1].strip())
                    elif part.startswith("body="):
                        bodies.add(part.split("=", 1)[1].strip().rstrip("}"))
    holds = {n for n, ls in comps.items()
             if any("all-reduce(" in ln for ln in ls)}
    return {"all_reduce_total": hlo.count("all-reduce("),
            "in_while_condition": sorted(holds & conds),
            "in_while_body": sorted(holds & bodies)}


def phase_sharded_sweep(sz: Sizes, mesh) -> None:
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.api.sweep import _bucket_fn, _bucket_program

    base = machine_scenario(sz, sz.sweep_jobs, sz.mtbf[0])
    axes = {**SWEEP_AXES, "failures.mtbf": sz.mtbf}
    with Phase("sharded_sweep") as ph:
        one, one_s = timed(sweep, base, axes)
        four, four_s = timed(sweep, base, axes, mesh=mesh)
        for i, (a, b) in enumerate(zip(one.results, four.results)):
            check(leaves_equal(a.raw, b.raw),
                  f"sharded sweep point {one.points[i]} differs from the "
                  "one-device run")
        # the batched program itself: where its outputs live, and whether
        # the lockstep loop synchronizes the devices every iteration
        bucket = [base.with_(**p) for p in four.points]
        key, args, machine, _ = _bucket_program(bucket, mesh)
        fn = _bucket_fn(*key)
        args = tuple(jax.device_put(a, NamedSharding(mesh, P(key[6])))
                     for a in args)
        out = jax.block_until_ready(fn(*args, machine))
        span = {d.id for leaf in jax.tree.leaves(out)
                for d in leaf.sharding.device_set}
        check(len(span) == mesh.size, f"sharded sweep outputs live on "
              f"{len(span)} devices, want {mesh.size}")
        hlo = fn.lower(*args, machine).compile().as_text()
        ph.counts.update({
            "lanes": len(four), "devices": sorted(span),
            "jobs_per_lane": sz.sweep_jobs, "one_device_s": one_s,
            "sharded_s": four_s, **_allreduce_in_loops(hlo)})


def phase_sharded_multicluster(sz: Sizes, mesh) -> None:
    from repro.core.jobs import POLICY_IDS, make_jobset
    from repro.core.parallel import (multicluster_result_np,
                                     simulate_multicluster, stack_jobsets)
    from repro.traces import sdsc_sp2_like

    C, J, nodes = 2 * mesh.size, sz.mc_jobs, 128
    traces = [sdsc_sp2_like(J, seed=SEED + c) for c in range(C)]
    jobs = stack_jobsets([
        make_jobset(t["submit"], t["runtime"], t["nodes"], t["estimate"],
                    capacity=J + 32, total_nodes=nodes) for t in traces])
    horizon = int(max(t["submit"].max() for t in traces)) + 500_000
    kw = dict(window=20_000, horizon=horizon, migrate=True, max_export=4)
    with Phase("sharded_multicluster") as ph:
        one, one_s = timed(simulate_multicluster, jobs, POLICY_IDS["backfill"],
                           [nodes] * C, mesh=None, **kw)
        four, four_s = timed(simulate_multicluster, jobs,
                             POLICY_IDS["backfill"], [nodes] * C, mesh=mesh,
                             **kw)
        check(leaves_equal(one, four),
              "sharded multicluster differs from the one-device run")
        span = {d.id for leaf in jax.tree.leaves(four.state)
                for d in leaf.sharding.device_set}
        check(len(span) == mesh.size, f"multicluster state lives on "
              f"{len(span)} devices, want {mesh.size}")
        out = multicluster_result_np(four)
        check(out["dropped"] == 0 and not out["saturated"],
              f"multicluster dropped {out['dropped']} jobs or saturated")
        check(int(out["done"].sum()) == C * J,
              f"multicluster finished {int(out['done'].sum())} of {C * J}")
        ph.counts.update({
            "clusters": C, "jobs_per_cluster": J, "nodes_per_cluster": nodes,
            "migrated": out["migrated"], "devices": sorted(span),
            "one_device_s": one_s, "sharded_s": four_s})


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def run_phases(sz: Sizes, chips: int, out_dir: str) -> None:
    if chips == 1:
        phase_run(sz)
        phase_sweep(sz)
        phase_replay(sz, out_dir)
        phase_whatif(sz)
        return
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:chips]), ("sim",))
    phase_sharded_sweep(sz, mesh)
    phase_sharded_multicluster(sz, mesh)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the main path on one chip (default); 4: the "
                         "sharded paths against their one-device twins")
    args = ap.parse_args(argv)

    devices = jax.devices()
    dev = devices[0]
    print(json.dumps({"platform": dev.platform, "device_kind": dev.device_kind,
                      "device_count": len(devices)}), flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 2
    print(json.dumps({"compile_cache": enable_compile_cache()}), flush=True)
    sz = SIZES["full"]
    print(json.dumps({"sizes": dataclasses.asdict(sz)}), flush=True)
    os.makedirs(OUT_DIR, exist_ok=True)
    try:
        run_phases(sz, args.chips, OUT_DIR)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
