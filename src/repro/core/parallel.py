"""Parallel discrete-event simulation (paper Figs. 5-6, DESIGN.md §2).

Two parallelization modes, both SPMD-native:

1. **Ensemble** — many independent simulations (trace shards, policy sweeps,
   parameter studies) batched with ``vmap`` and sharded across devices with
   ``shard_map``.  This is the weak-scaling mode the paper exercises by
   growing job counts per rank.

2. **Multi-cluster conservative windows** — one simulation partitioned into
   K clusters, each advanced independently over a time window ``W`` and then
   synchronized.  Job *migration* messages emitted in window ``k`` carry a
   latency >= W, so they cannot affect window ``k`` — the window is a valid
   conservative lookahead bound, exactly SST's synchronization contract,
   expressed with ``shard_map`` + ``all_gather`` instead of MPI.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import alloc as _alloc
from repro.core.engine import simulate, simulate_window
from repro.core.jobs import (
    DONE, INF_TIME, PENDING, WAITING,
    JobSet, SimResult, SimState,
)

# ---------------------------------------------------------------------------
# ensemble mode
# ---------------------------------------------------------------------------


def stack_jobsets(jobsets: list[JobSet]) -> JobSet:
    """Stack equally-sized JobSets into a leading batch dimension.

    Members may mix edge-free tables (``dep_dst is None``) and edge lists of
    *different* padded lengths (e.g. a sweep over DAG seeds where each seed
    generates a different edge count, or one seed generates zero edges):
    every member is padded to the longest edge list with inert out-of-range
    edges (index = capacity, the same padding ``make_jobset`` emits), so the
    stacked pytree is uniform.  Padding edges scatter out of bounds and
    drop, so schedules are unchanged.  The stack keeps ``submit_sorted``
    only where every member has it.
    """
    if not all(j.submit_sorted for j in jobsets):
        jobsets = [dataclasses.replace(j, submit_sorted=False)
                   for j in jobsets]
    if any(j.dep_dst is not None for j in jobsets):
        ecap = max(j.edge_capacity for j in jobsets)

        def pad_edges(j: JobSet) -> JobSet:
            extra = ecap - j.edge_capacity
            if extra == 0:
                return j
            fill = jnp.full((extra,), j.capacity, dtype=jnp.int32)
            if j.dep_dst is None:
                return dataclasses.replace(j, dep_dst=fill, dep_src=fill)
            return dataclasses.replace(
                j,
                dep_dst=jnp.concatenate([j.dep_dst, fill]),
                dep_src=jnp.concatenate([j.dep_src, fill]),
            )

        jobsets = [pad_edges(j) for j in jobsets]
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *jobsets)


def simulate_ensemble(
    jobs_b: JobSet,
    policies_b,
    total_nodes_b,
    *,
    machine=None,
    alloc_b=None,
    contention=None,
    failures_b=None,
    mesh: Optional[Mesh] = None,
    max_events: Optional[int] = None,
) -> SimResult:
    """vmap-batched simulation, optionally sharded over a 1-D device mesh.

    ``jobs_b`` leaves have leading batch dim B; ``policies_b``/``total_nodes_b``
    are i32[B].  With a mesh, B must divide evenly across the ``sim`` axis;
    each device advances its ensemble members fully independently (zero
    cross-device communication — the embarrassingly-parallel mode).

    Allocation sweep axis (DESIGN.md §11): with ``machine`` (one static
    topology broadcast to all members) ``alloc_b`` is an i32[B] of placement
    strategy ids — strategy is ensemble data, exactly like policy.

    Reliability sweep axis (DESIGN.md §15): ``failures_b`` is a stacked fail
    ctx — ``jax.tree.map(jnp.stack, *[make_fail_ctx(t) for t in traces])``
    — whose leaves carry a leading B dim; per-member failure streams are
    ensemble data too (uniform ``max_failures`` padding required).
    """
    policies_b = jnp.asarray(policies_b, dtype=jnp.int32)
    total_nodes_b = jnp.asarray(total_nodes_b, dtype=jnp.int32)
    if machine is None:
        if alloc_b is not None or contention is not None:
            raise ValueError(
                "alloc_b/contention require machine=; without a Machine the "
                "ensemble runs in scalar-counter mode and would silently "
                "ignore them")
        if failures_b is None:
            fn = jax.vmap(functools.partial(simulate, max_events=max_events))
            args = (jobs_b, policies_b, total_nodes_b)
        else:
            fn = jax.vmap(
                lambda j, p, t, f: simulate(j, p, t, failures=f,
                                            max_events=max_events))
            args = (jobs_b, policies_b, total_nodes_b, failures_b)
    else:
        bad = np.asarray(total_nodes_b) != machine.n_nodes
        if bad.any():
            raise ValueError(
                f"machine has {machine.n_nodes} nodes but total_nodes_b "
                f"contains {sorted(set(np.asarray(total_nodes_b)[bad].tolist()))}")
        if alloc_b is None:
            alloc_b = jnp.zeros_like(policies_b)
        # one shared canonicalizer (repro.alloc.canonical_id) handles str/int
        # ids, numpy arrays, and mixed str/int sequences identically here, in
        # make_alloc_ctx, and in the Scenario sweep layer
        alloc_b = jnp.asarray(_alloc.canonical_id(alloc_b), dtype=jnp.int32)
        if failures_b is None:
            fn = jax.vmap(
                lambda j, p, t, a: simulate(
                    j, p, t, machine=machine, alloc=a, contention=contention,
                    max_events=max_events)
            )
            args = (jobs_b, policies_b, total_nodes_b, alloc_b)
        else:
            fn = jax.vmap(
                lambda j, p, t, a, f: simulate(
                    j, p, t, machine=machine, alloc=a, contention=contention,
                    failures=f, max_events=max_events)
            )
            args = (jobs_b, policies_b, total_nodes_b, alloc_b, failures_b)
    if mesh is None:
        return jax.jit(fn)(*args)

    axis = mesh.axis_names[0]
    shard = NamedSharding(mesh, P(axis))
    args = tuple(jax.device_put(a, shard) for a in args)
    out_shard = jax.tree.map(lambda _: shard, jax.eval_shape(fn, *args))
    return jax.jit(fn, out_shardings=out_shard)(*args)


def simulate_alloc_sweep(
    jobs: JobSet,
    policy,
    total_nodes,
    machine,
    strategies=("simple", "contiguous", "spread", "topo"),
    *,
    contention=None,
    mesh: Optional[Mesh] = None,
    max_events: Optional[int] = None,
) -> SimResult:
    """Run ONE trace under every allocation strategy as a batched ensemble.

    Legacy shim: ``repro.api.sweep(scenario, axes={"alloc": strategies})``
    is the general form (any axis grid, static-bucket compilation, unified
    results) and reproduces this function bit-for-bit (regression-tested in
    ``tests/test_api.py``).  Kept for callers that already hold a
    ``JobSet``.

    Returns a ``SimResult`` whose leaves have leading dim ``len(strategies)``
    in the order given — the "same trace, different allocators, different
    makespans" scenario family from DESIGN.md §11.
    """
    B = len(strategies)
    jobs_b = stack_jobsets([jobs] * B)
    policies_b = jnp.full((B,), int(policy), dtype=jnp.int32)
    total_nodes_b = jnp.full((B,), int(total_nodes), dtype=jnp.int32)
    alloc_b = jnp.asarray(_alloc.canonical_id(list(strategies)),
                          dtype=jnp.int32)
    return simulate_ensemble(
        jobs_b, policies_b, total_nodes_b, machine=machine, alloc_b=alloc_b,
        contention=contention, mesh=mesh, max_events=max_events,
    )


# ---------------------------------------------------------------------------
# multi-cluster conservative-window mode
# ---------------------------------------------------------------------------


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class MulticlusterResult:
    """Final per-cluster tables: leaves shaped [C, J]."""

    jobs: JobSet          # post-migration job tables (valid marks ownership)
    state: SimState
    migrated: jax.Array   # i32[C] jobs exported by each cluster
    dropped: jax.Array    # i32[C] imports dropped for lack of free rows (should be 0)
    saturated: jax.Array  # bool[C] any round hit the event cap with events still due


def _queue_load(jobs: JobSet, state: SimState) -> jax.Array:
    """Pending work metric: node-seconds waiting in queue (estimates)."""
    waiting = (state.jstate == WAITING) | (state.jstate == PENDING)
    return jnp.sum(
        jnp.where(waiting, jobs.nodes * jnp.minimum(jobs.estimate, 1 << 16), 0)
    ).astype(jnp.int32)


def _export_jobs(jobs: JobSet, state: SimState, t_hi, latency, max_export: int,
                 enable: jax.Array):
    """Pick up to ``max_export`` *tail* waiting/pending jobs to offload.

    Tail = largest submit time first (least FCFS-urgent), so migration never
    reorders the local head-of-queue.  Jobs with dependency edges (either
    direction) are pinned to their cluster: the dependency matrix is local,
    so exporting either endpoint of an edge would sever it (DESIGN.md §13).
    Returns (jobs', state', packet).
    """
    J = jobs.capacity
    movable = ((state.jstate == WAITING) | (state.jstate == PENDING)) & jobs.valid
    if jobs.dep_dst is not None:
        # rows touched by any live edge (either endpoint) are pinned;
        # padding / neutralized edges hold index J and drop out
        has_edges = (
            jnp.zeros((J,), bool)
            .at[jobs.dep_dst].set(True, mode="drop")
            .at[jobs.dep_src].set(True, mode="drop")
        )
        movable = movable & ~has_edges
    # rank movable jobs by descending submit (non-movable sort last)
    key = jnp.where(movable, -jobs.submit, jnp.int32(INF_TIME))
    order = jnp.argsort(key)  # ascending => movable with largest submit first
    take = jnp.arange(J) < jnp.where(enable, max_export, 0)
    n_movable = jnp.sum(movable.astype(jnp.int32))
    take = take & (jnp.arange(J) < n_movable)
    sel_rows = order[:max_export]
    sel_ok = take[:max_export]

    new_submit = jnp.maximum(jobs.submit[sel_rows], t_hi + latency)
    packet = {
        "submit": jnp.where(sel_ok, new_submit, INF_TIME).astype(jnp.int32),
        "runtime": jobs.runtime[sel_rows].astype(jnp.int32),
        "estimate": jobs.estimate[sel_rows].astype(jnp.int32),
        "nodes": jobs.nodes[sel_rows].astype(jnp.int32),
        "priority": jobs.priority[sel_rows].astype(jnp.int32),
        "ok": sel_ok,
    }
    # remove exported jobs locally
    remove = jnp.zeros((J,), bool).at[sel_rows].set(sel_ok)
    jobs = dataclasses.replace(jobs, valid=jobs.valid & ~remove)
    state = dataclasses.replace(
        state, jstate=jnp.where(remove, DONE, state.jstate)
    )
    return jobs, state, packet


def _import_jobs(jobs: JobSet, state: SimState, flat):
    """Insert gathered packets destined to this cluster into free rows."""
    J = jobs.capacity
    ok = flat["ok"]
    n_imp = jnp.sum(ok.astype(jnp.int32))
    free_rows_order = jnp.argsort(jnp.where(jobs.valid, 1, 0), stable=True)
    n_free = jnp.sum((~jobs.valid).astype(jnp.int32))
    slot = jnp.cumsum(ok.astype(jnp.int32)) - 1           # slot per packet
    can = ok & (slot < n_free)
    rows = free_rows_order[jnp.clip(slot, 0, J - 1)]
    rows = jnp.where(can, rows, J)  # J = out-of-bounds => dropped by mode="drop"

    # imported jobs are dependency-free by construction (_export_jobs pins
    # edge endpoints), but neutralize edges touching the landing rows
    # defensively — both endpoints move to the out-of-range pad index J —
    # so a reused row can never inherit stale edges
    new_dst, new_src = jobs.dep_dst, jobs.dep_src
    if new_dst is not None:
        hit = jnp.isin(new_dst, rows) | jnp.isin(new_src, rows)
        new_dst = jnp.where(hit, jnp.int32(J), new_dst)
        new_src = jnp.where(hit, jnp.int32(J), new_src)
    jobs = JobSet(
        submit=jobs.submit.at[rows].set(flat["submit"], mode="drop"),
        runtime=jobs.runtime.at[rows].set(flat["runtime"], mode="drop"),
        estimate=jobs.estimate.at[rows].set(flat["estimate"], mode="drop"),
        nodes=jobs.nodes.at[rows].set(flat["nodes"], mode="drop"),
        priority=jobs.priority.at[rows].set(flat["priority"], mode="drop"),
        valid=jobs.valid.at[rows].set(True, mode="drop"),
        dep_dst=new_dst,
        dep_src=new_src,
    )
    n_unmet = state.n_unmet
    if new_dst is not None:
        n_unmet = n_unmet.at[rows].set(0, mode="drop")  # landing rows dep-free
    state = dataclasses.replace(
        state,
        jstate=state.jstate.at[rows].set(PENDING, mode="drop"),
        n_unmet=n_unmet,
        start=state.start.at[rows].set(INF_TIME, mode="drop"),
        finish=state.finish.at[rows].set(INF_TIME, mode="drop"),
        rsv_finish=state.rsv_finish.at[rows].set(INF_TIME, mode="drop"),
        remaining=state.remaining.at[rows].set(flat["runtime"], mode="drop"),
    )
    dropped = n_imp - jnp.minimum(n_imp, n_free)
    return jobs, state, dropped


def simulate_multicluster(
    jobs_c: JobSet,
    policy,
    nodes_c,
    *,
    window: int,
    horizon: int,
    mesh: Optional[Mesh] = None,
    migrate: bool = True,
    max_export: int = 8,
    latency: Optional[int] = None,
    load_imbalance_threshold: float = 1.5,
    max_events: Optional[int] = None,
) -> MulticlusterResult:
    """Conservative-window multi-cluster simulation.

    ``jobs_c`` leaves are [C, J]; ``nodes_c`` is i32[C].  Each round: every
    cluster simulates events in ``(r*W, (r+1)*W]`` independently; clusters
    whose queue load exceeds ``threshold * mean`` export up to ``max_export``
    tail jobs to the least-loaded cluster, with arrival latency >= ``W``
    (the conservative lookahead).  With ``mesh`` the cluster dimension is
    sharded via ``shard_map``; without, it runs vmapped on one device with
    identical semantics (the collective degenerates to an identity gather).
    """
    C = jobs_c.submit.shape[0]
    J = jobs_c.submit.shape[1]
    # imports land in free rows with later submits, so row order stops
    # being FCFS order after the first migration round
    jobs_c = dataclasses.replace(jobs_c, submit_sorted=False)
    policy = jnp.asarray(policy, dtype=jnp.int32)
    nodes_c = jnp.asarray(nodes_c, dtype=jnp.int32)
    latency = int(latency if latency is not None else window)
    if latency < window:
        raise ValueError("migration latency must be >= window for conservative sync")
    n_rounds = int(np.ceil(horizon / window)) + 1
    ev_cap = max_events if max_events is not None else 2 * J + 8

    def local_sim(jobs, nodes, axis_name):
        # jobs leaves [Cl, J]; runs on one shard (or the whole batch w/o mesh)
        state = jax.vmap(SimState.init, in_axes=(0, 0))(jobs, nodes)

        def round_body(r, carry):
            jobs, state, mig, drop, sat = carry
            t_hi = (r + 1) * jnp.int32(window)
            state, sat_r = jax.vmap(
                lambda j, s: simulate_window(policy, j, s, t_hi, ev_cap)
            )(jobs, state)
            sat = sat | sat_r
            if not migrate:
                return jobs, state, mig, drop, sat

            load_l = jax.vmap(_queue_load)(jobs, state)          # [Cl]
            if axis_name is not None:
                loads = jax.lax.all_gather(load_l, axis_name).reshape(-1)  # [C]
                my0 = jax.lax.axis_index(axis_name) * load_l.shape[0]
            else:
                loads = load_l
                my0 = 0
            mean_load = jnp.mean(loads.astype(jnp.float32))
            dest = jnp.argmin(loads).astype(jnp.int32)           # global id
            gids = my0 + jnp.arange(load_l.shape[0], dtype=jnp.int32)
            over = (
                (load_l.astype(jnp.float32) > load_imbalance_threshold * mean_load)
                & (gids != dest)
                & (loads[dest] < load_l)
            )
            jobs, state, pkt = jax.vmap(
                lambda j, s, en: _export_jobs(j, s, t_hi, jnp.int32(latency),
                                              max_export, en)
            )(jobs, state, over)
            pkt["dest"] = jnp.broadcast_to(dest, pkt["ok"].shape).astype(jnp.int32)
            mig = mig + jax.vmap(lambda o: jnp.sum(o.astype(jnp.int32)))(pkt["ok"])

            if axis_name is not None:
                gpkt = {k: jax.lax.all_gather(v, axis_name) for k, v in pkt.items()}
                gpkt = {k: v.reshape((-1,) + v.shape[3:]) for k, v in gpkt.items()}
            else:
                gpkt = {k: v.reshape((-1,) + v.shape[2:]) for k, v in pkt.items()}

            def imp(j, s, gid):
                flat = dict(gpkt)
                flat["ok"] = gpkt["ok"] & (gpkt["dest"] == gid)
                j, s, d = _import_jobs(j, s, flat)
                return j, s, d

            jobs, state, d = jax.vmap(imp)(jobs, state, gids)
            return jobs, state, mig, drop + d, sat

        mig0 = jnp.zeros((jobs.submit.shape[0],), jnp.int32)
        sat0 = jnp.zeros((jobs.submit.shape[0],), bool)
        carry = (jobs, state, mig0, jnp.zeros_like(mig0), sat0)
        jobs, state, mig, drop, sat = jax.lax.fori_loop(
            0, n_rounds, round_body, carry)
        # drain any events beyond the horizon (no migration afterwards)
        state, sat_d = jax.vmap(
            lambda j, s: simulate_window(policy, j, s, jnp.int32(INF_TIME), ev_cap)
        )(jobs, state)
        return jobs, state, mig, drop, sat | sat_d

    if mesh is None:
        jobs, state, mig, drop, sat = jax.jit(
            lambda j, n: local_sim(j, n, None)
        )(jobs_c, nodes_c)
    else:
        axis = mesh.axis_names[0]
        fn = jax.shard_map(
            lambda j, n: local_sim(j, n, axis),
            mesh=mesh,
            in_specs=(P(axis), P(axis)),
            out_specs=P(axis),
            check_vma=False,
        )
        jobs, state, mig, drop, sat = jax.jit(fn)(jobs_c, nodes_c)

    return MulticlusterResult(jobs=jobs, state=state, migrated=mig,
                              dropped=drop, saturated=sat)


def multicluster_result_np(res: MulticlusterResult) -> dict:
    """Flatten per-cluster tables to one host-side result dict."""
    jobs, state = res.jobs, res.state
    flat = lambda a: np.asarray(a).reshape(-1)
    valid = flat(jobs.valid)
    done = flat(state.jstate) == DONE
    out = {
        "submit": flat(jobs.submit),
        "runtime": flat(jobs.runtime),
        "nodes": flat(jobs.nodes),
        "start": flat(state.start),
        "finish": flat(state.finish),
        "valid": valid,
        "done": done & valid,
        "migrated": int(np.asarray(res.migrated).sum()),
        "dropped": int(np.asarray(res.dropped).sum()),
        "saturated": bool(np.asarray(res.saturated).any()),
    }
    if jobs.dep_dst is not None:
        dst = np.asarray(jobs.dep_dst)                     # [C, E]
        src = np.asarray(jobs.dep_src)
        fin = np.asarray(state.finish)                     # [C, J]
        C, J = fin.shape
        dep_fin = np.zeros((C, J), dtype=fin.dtype)
        for c in range(C):                                 # host side, C small
            live = dst[c] < J
            np.maximum.at(dep_fin[c], dst[c][live], fin[c][src[c][live]])
        out["ready"] = np.maximum(np.asarray(jobs.submit), dep_fin).reshape(-1)
    else:
        out["ready"] = out["submit"]
    out["wait"] = out["start"] - out["ready"]
    fin = out["finish"][out["done"]]
    out["makespan"] = int(fin.max(initial=0))
    return out
