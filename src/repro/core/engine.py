"""The discrete-event engine (paper §2.2, Algorithm 1) as a jit-able loop.

Event semantics, pinned identically in ``repro.refsim`` (DESIGN.md §8):

  1. advance clock to min(next arrival, next completion),
  2. process *all* completions with finish <= clock (reclaim nodes),
  3. process *all* arrivals with submit <= clock (enqueue),
  4. run the scheduling pass: repeatedly ask the policy selector for a job
     and start it, until the selector returns -1.

Dependencies (paper §3, DESIGN.md §13): when the job table carries a
``dep_dst``/``dep_src`` edge list, a PENDING job arrives only when
``submit <= clock`` AND every dependency is DONE.  Dependents of a
completing job are re-evaluated at the completion event itself (completions
run before arrivals), so a released dependent joins the wait queue — and
competes in the scheduling pass — at its last dependency's finish time.
The release test is the incremental counter ``SimState.n_unmet == 0``
(DESIGN.md §14): completions decrement the counters in O(E) — CSR-gather
in ``simulate``, scatter-add fallback in windows — replacing the two
O(J²) dense-matrix reductions the engine used to pay per event.
``dep_dst is None`` statically elides every release check, compiling to
the exact seed event graph.

Each event consumes at least one arrival or completion, so the loop runs at
most ``2*J + 1`` iterations; ``max_events`` is a safety cap on top.

Fast scheduling pass (DESIGN.md §14): when the job table carries
dependencies, the policy is *statically* known to be a blocking
head-of-queue discipline (FCFS/SJF/LJF), and the placement feasibility cap
is the free counter (scalar-counter mode, or the count-capped
``simple``/``spread`` strategies), the per-event scheduling pass reads the
entire feasible prefix off a loop-invariant queue permutation (one sort
per *call*, one O(J) cumsum per event) instead of re-running the policy
selector after every start — DAG stage fronts start whole release waves
in a single event.  When that permutation is the identity (a
``make_jobset`` table, FCFS or EASY's FCFS phase) and the cap is the
scalar counter, the pass reads the queue in row order and applies the
starts as masks: no gather and no loop.  Dependency-free tables, backfill,
bestfit, preempt and the geometry-capped strategies keep the per-start
loop (with the selector dispatched statically where known); the choice is
made at trace time (a traced policy — e.g. a ``vmap``-ped sweep axis —
always takes the seed loop), so no path pays for another's.

Malleable jobs (DESIGN.md §17): with a ``malleable`` plan the engine gains
moldable width choice at dispatch — among placement-feasible widths the
scheduler picks the one with the minimum dilated runtime (ties to the
narrowest), the job's node footprint becomes its *current width*, and every
fit check / completion / demand reduction reads the width through an
effective-jobs view — plus, in elastic mode, a fourth event source: a
deterministic resize-tick stream under which queue pressure shrinks the
widest running job (freeing nodes for the queue) and idle capacity grows
the narrowest one, and a §15 node failure shrinks its victim by one node
instead of requeueing it while the victim still has width to give.
``malleable=None`` statically elides all of it: ``SimState.mal`` is
``None`` and the rigid executable is HLO-identical to the pre-malleable
engine (fingerprint-tested).

Node allocation (DESIGN.md §11): with a ``Machine`` the engine additionally
maintains the per-node occupancy map.  Completions free the completing
jobs' nodes, starts place concrete nodes via the chosen strategy, and the
policy fit checks use ``placeable_cap`` — for the count-based strategies
that cap *is* the scalar free counter, so ``alloc="simple"`` with
contention off reproduces the seed scalar-counter schedule bit-for-bit.

Reliability (DESIGN.md §15): with a ``failures`` model the event loop gains
a third event source — a pre-materialized, padded failure/repair stream
(``repro.reliability``) consumed through a per-event pointer.  A failure
takes one node out of service until its repair; if the node was busy, the
running job is killed and either *requeues* (re-enters the wait queue at
its submit rank, re-charged for the work since its last checkpoint plus a
restart overhead) or *aborts* (terminates; dependents release with
after-any semantics).  Down nodes are masked out of every placement and
fit check by painting them with an out-of-range owner id at the strategy
call sites — the strategies themselves are untouched.  ``failures=None``
statically elides all of it: ``SimState.rel`` is ``None`` (no leaves, not
zero-size placeholders) and the no-failure executable is HLO-identical to
the pre-reliability engine (fingerprint-tested).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro import alloc as _alloc
from repro import obs
from repro.core import policies
from repro.core.jobs import (
    BACKFILL, DONE, FCFS, INF_TIME, LJF, PENDING, PREEMPT, RUNNING, SJF,
    WAITING, JobSet, SimResult, SimState, result_from_state,
)
from repro.malleable.model import make_mal_ctx
from repro.reliability.model import FAIL, REQUEUE, make_fail_ctx
from repro.serving.model import make_svc_ctx

# An allocation context is either None (seed scalar-counter mode) or the
# pytree tuple (machine, strategy_i32, contention); its None-ness is static
# at trace time, so the scalar path compiles with zero allocation overhead.
AllocCtx = tuple

# Policies whose scheduling pass is a blocking head-of-(re)ordered-queue —
# eligible for the batched sort+cumsum pass when known at trace time.
_BLOCKING_POLICIES = (FCFS, SJF, LJF)
# Strategies whose placement-feasibility cap IS the free counter; contiguous
# (largest-free-run cap) and topo keep the per-start loop (DESIGN.md §14).
_COUNT_CAPPED = (_alloc.SIMPLE, _alloc.SPREAD)


def _concrete_int(x) -> Optional[int]:
    """``int(x)`` when ``x`` is concrete at trace time, else ``None``.

    Traced values (vmap sweep axes, jit arguments) return ``None`` — the
    caller falls back to the fully dynamic seed path.
    """
    if x is None:
        return None
    try:
        return int(x)
    except (TypeError, ValueError, jax.errors.ConcretizationTypeError):
        return None


def _static_policy_hint(policy) -> Optional[int]:
    """Concrete policy id clamped to the selector table, or ``None``.

    THE one place the static hint is derived (``simulate`` and
    ``simulate_window`` both call it), mirroring the dynamic path's
    ``jnp.clip(policy, 0, 5)`` so a stray id picks the same selector
    either way.
    """
    p = _concrete_int(policy)
    if p is None:
        return None
    return min(max(p, 0), len(policies.SELECTOR_TABLE) - 1)


def _release_nodes(state_owner: jax.Array, released: jax.Array,
                   capacity: int) -> jax.Array:
    """Free every node whose owning job row is in the ``released`` mask."""
    own = state_owner
    hit = (own >= 0) & released[jnp.clip(own, 0, capacity - 1)]
    return jnp.where(hit, jnp.int32(-1), own)


def _owner_eff(jobs: JobSet, state: SimState) -> jax.Array:
    """The occupancy map as the placement strategies should see it.

    With reliability active, down nodes are painted with the out-of-range
    owner id ``capacity`` — "busy, owned by nobody" — so every existing
    ``owner < 0`` free test and ``owner >= 0`` busy test excludes them
    without touching the strategies (DESIGN.md §15).  The *true*
    ``node_owner`` map (which release scatters read) never holds the
    sentinel, so a down node can never be freed by a job completion.

    The serving autoscaler (DESIGN.md §16) masks scaled-out nodes the
    same way: an offline node is "busy, owned by nobody" to every
    strategy, and since scale-down only ever takes *free* nodes, the true
    ``node_owner`` map never references an offline node either.
    """
    if state.rel is None and state.svc is None:
        return state.node_owner
    own = state.node_owner
    if state.svc is not None:
        own = jnp.where(state.svc.offline, jnp.int32(jobs.capacity), own)
    if state.rel is not None:
        own = jnp.where(state.rel.down, jnp.int32(jobs.capacity), own)
    return own


def _jobs_eff(jobs: JobSet, state: SimState) -> JobSet:
    """The job table as fit checks and node accounting should see it.

    With malleability active, each job's node footprint is its *current
    width* (``min_width`` while waiting — the width a dispatch is
    guaranteed to be able to choose — and the running width thereafter),
    not its rigid request.  The same ``jobs`` object comes back when
    ``state.mal`` is ``None``, so the rigid paths trace unchanged
    (DESIGN.md §17).
    """
    if state.mal is None:
        return jobs
    return dataclasses.replace(jobs, nodes=state.mal.width)


def _ratio_ceil(r: jax.Array, dur_new: jax.Array,
                dur_old: jax.Array) -> jax.Array:
    """``max(1, ceil(r * dur_new / dur_old))`` — the width re-dilation of a
    remaining wall time, in float32 with a pinned operation order
    ``(r * dur_new) / dur_old`` mirrored bit-exactly (np.float32 scalar
    ops, same order) in ``repro.refsim`` (DESIGN.md §17)."""
    v = (r.astype(jnp.float32) * dur_new.astype(jnp.float32)) \
        / dur_old.astype(jnp.float32)
    return jnp.maximum(jnp.ceil(v).astype(jnp.int32), 1)


def _start_job(jobs: JobSet, state: SimState, idx: jax.Array,
               ctx: Optional[AllocCtx],
               mctx: Optional[tuple] = None) -> SimState:
    """Allocate nodes to job ``idx`` and schedule its completion event.

    Uses ``state.remaining`` (== runtime unless previously preempted) and
    records only the FIRST start time (dispatch-latency metric).  With an
    allocation context, concrete nodes are placed by the strategy, the
    occupancy map and allocation fingerprints update, and contention dilates
    the remaining runtime by the allocation's group span.
    """
    start = state.clock
    if state.rel is not None:
        state = dataclasses.replace(
            state, rel=dataclasses.replace(
                state.rel,
                last_start=state.rel.last_start.at[idx].set(start)))
    if mctx is not None:
        # moldable width choice (DESIGN.md §17): among placement-feasible
        # widths pick the minimum dilated duration, ties to the narrowest
        # (argmin returns the first minimum).  The policy admitted this job
        # at its effective (minimum) width, so at least one width fits.
        dur_t, _, _, wlo = mctx[0], mctx[1], mctx[2], mctx[3]
        W = dur_t.shape[1]
        dur_row = dur_t[idx]
        widths = wlo + jnp.arange(W, dtype=jnp.int32)
        if ctx is None:
            cap = state.free
        else:
            cap = _alloc.placeable_cap(ctx[1], _owner_eff(jobs, state))
        k = jnp.argmin(jnp.where(widths <= cap, dur_row,
                                 jnp.int32(INF_TIME))).astype(jnp.int32)
        w = wlo + k
        # fresh dispatch (prev_w == 0 sentinel) reads the dur table exactly;
        # a redispatch after a requeue converts the re-charged remaining
        # (wall units at the pre-kill width) to the new width
        prev = state.mal.prev_w[idx]
        prev_k = jnp.clip(prev - wlo, 0, W - 1)
        dil_rem = jnp.where(
            prev == 0, dur_row[k],
            _ratio_ceil(state.remaining[idx], dur_row[k], dur_row[prev_k]))
        if ctx is not None:
            with jax.named_scope("place"):
                machine, strategy, _ = ctx
                mask = _alloc.place(strategy, machine,
                                    _owner_eff(jobs, state), w)
                span = _alloc.group_span(machine, mask)
                first, asum = _alloc.alloc_fingerprint(mask)
                state = dataclasses.replace(
                    state,
                    node_owner=jnp.where(mask, idx, state.node_owner),
                    alloc_first=state.alloc_first.at[idx].set(first),
                    alloc_span=state.alloc_span.at[idx].set(span),
                    alloc_sum=state.alloc_sum.at[idx].set(asum),
                )
        m = state.mal
        state = dataclasses.replace(state, mal=dataclasses.replace(
            m,
            width=m.width.at[idx].set(w),
            prev_w=m.prev_w.at[idx].set(w),
            seg_start=m.seg_start.at[idx].set(start),
            disp_dur=m.disp_dur.at[idx].set(dur_row[k]),
        ))
        fin = start + dil_rem
        rsv = start + jobs.estimate[idx]
        first_start = jnp.minimum(state.start[idx], start)
        return dataclasses.replace(
            state,
            jstate=state.jstate.at[idx].set(RUNNING),
            start=state.start.at[idx].set(first_start),
            finish=state.finish.at[idx].set(fin),
            rsv_finish=state.rsv_finish.at[idx].set(rsv),
            free=state.free - w,
        )
    if ctx is None:
        dil_rem = state.remaining[idx]
    else:
        with jax.named_scope("place"):
            machine, strategy, con = ctx
            mask = _alloc.place(strategy, machine, _owner_eff(jobs, state),
                                jobs.nodes[idx])
            span = _alloc.group_span(machine, mask)
            first, asum = _alloc.alloc_fingerprint(mask)
            dil_rem = _alloc.dilate(con, state.remaining[idx], span)
            state = dataclasses.replace(
                state,
                node_owner=jnp.where(mask, idx, state.node_owner),
                alloc_first=state.alloc_first.at[idx].set(first),
                alloc_span=state.alloc_span.at[idx].set(span),
                alloc_sum=state.alloc_sum.at[idx].set(asum),
            )
    fin = start + dil_rem
    rsv = start + jobs.estimate[idx]
    first_start = jnp.minimum(state.start[idx], start)
    return dataclasses.replace(
        state,
        jstate=state.jstate.at[idx].set(RUNNING),
        start=state.start.at[idx].set(first_start),
        finish=state.finish.at[idx].set(fin),
        rsv_finish=state.rsv_finish.at[idx].set(rsv),
        free=state.free - jobs.nodes[idx],
    )


def _preempt_for(jobs: JobSet, state: SimState, idx: jax.Array,
                 ctx: Optional[AllocCtx]) -> SimState:
    """Suspend the minimal set of strictly-lower-priority running jobs so
    that job ``idx`` fits (paper §5 future work: preemption capability).

    Victims are chosen most-preemptible-first: (priority desc, row desc).
    Suspended jobs keep their elapsed work (remaining shrinks) and return to
    WAITING with their original submit time/FCFS rank.  Victims release
    their concrete nodes; the reclaim test is free-count based, so under the
    ``contiguous`` strategy the subsequent placement may fall back to
    scattered first-fit (DESIGN.md §11.2).
    """
    with jax.named_scope("place"):
        J = jobs.capacity
        need = jobs.nodes[idx] - state.free
        running = state.jstate == RUNNING
        lower = running & (jobs.priority > jobs.priority[idx])
        # order victims by (priority desc, row desc) via a two-stage
        # lexicographic sort — the packed key ``-(priority*J + row)`` the
        # seed engine used overflows int32 for priorities near INF_TIME
        # (mirrors select_preempt's two-stage argmin; non-victims sort
        # last)
        rows = jnp.arange(J, dtype=jnp.int32)
        big = jnp.int32(INF_TIME)
        order = jnp.lexsort((jnp.where(lower, -rows, big),
                             jnp.where(lower, -jobs.priority, big)))
        nodes_o = jnp.where(lower, jobs.nodes, 0)[order]
        cum = jnp.cumsum(nodes_o)
        # preempt the minimal prefix whose cumulative nodes cover the deficit
        take_rank = jnp.where(cum - nodes_o < jnp.maximum(need, 0), True,
                              False)
        take_rank = take_rank & (nodes_o > 0)
        victim = jnp.zeros((J,), bool).at[order].set(take_rank)
        freed = jnp.sum(jnp.where(victim, jobs.nodes, 0)).astype(jnp.int32)
        new_remaining = jnp.where(
            victim, jnp.maximum(state.finish - state.clock, 1), state.remaining
        )
        node_owner = (state.node_owner if ctx is None
                      else _release_nodes(state.node_owner, victim, J))
        return dataclasses.replace(
            state,
            jstate=jnp.where(victim, WAITING, state.jstate),
            finish=jnp.where(victim, INF_TIME, state.finish),
            rsv_finish=jnp.where(victim, INF_TIME, state.rsv_finish),
            remaining=new_remaining,
            free=state.free + freed,
            node_owner=node_owner,
        )


def _select(policy: jax.Array, jobs: JobSet, state: SimState,
            ctx: Optional[AllocCtx],
            static_policy: Optional[int] = None) -> jax.Array:
    """Policy selection under the active allocation feasibility cap.

    Policies read node requests through the effective-jobs view: with
    malleability active a waiting job asks for its *minimum* width (any
    admitted job is guaranteed a feasible dispatch width) and a running
    job occupies its current width (backfill's shadow math)."""
    cap = (state.free if ctx is None
           else _alloc.placeable_cap(ctx[1], _owner_eff(jobs, state)))
    return policies.select(policy, _jobs_eff(jobs, state), state, cap,
                           static_policy=static_policy)


# The batched pass's two ways to read the queue: in row order, where the
# table's rows are already in blocking order (``ROW_ORDER`` also stands in
# for the permutation as ``fast_order``), or through ``blocking_order``
ROW_ORDER = "row_order"
PERMUTED = "permuted"


def blocking_order(jobs: JobSet, static_policy: int) -> jax.Array:
    """Loop-invariant queue permutation for a blocking policy.

    The blocking policies key on ``submit``/``estimate``/``-estimate``,
    all invariant for the lifetime of a ``simulate`` (or window) call — so
    the (key, row) sort the batched pass needs is computed ONCE per call,
    outside the event loop, not once per event (stable sort ⇒ ties break
    by row, matching ``_lex_argmin``).  Backfill's blocking phase is FCFS
    (the EASY head keys on ``submit``), so it shares the FCFS permutation.
    """
    key = {FCFS: jobs.submit, SJF: jobs.estimate,
           LJF: -jobs.estimate, BACKFILL: jobs.submit}[static_policy]
    return jnp.argsort(key, stable=True)


def _row_order_pass(jobs: JobSet, state: SimState) -> SimState:
    """``_batched_pass`` on a table whose row order is its blocking order
    (``ROW_ORDER``), in scalar-counter mode: no gather, no loop.

    The queue is read in row order, and the whole feasible prefix starts
    at once as masked updates of exactly the leaves ``place_slim``
    (``place_slim_rel``) writes one start at a time — the starts touch
    distinct rows and only subtract from ``free``, so applying them
    together is bit-identical to applying them in turn (DESIGN.md §14.2).
    """
    waiting = state.jstate == WAITING
    cum = jnp.cumsum(jnp.where(waiting, jobs.nodes, 0))
    take = waiting & (cum <= state.free)      # longest feasible prefix
    t0 = state.clock
    new = dataclasses.replace(
        state,
        jstate=jnp.where(take, RUNNING, state.jstate),
        start=jnp.where(take, jnp.minimum(state.start, t0), state.start),
        finish=jnp.where(take, t0 + state.remaining, state.finish),
        rsv_finish=jnp.where(take, t0 + jobs.estimate, state.rsv_finish),
        free=state.free - jnp.sum(jnp.where(take, jobs.nodes, 0)),
    )
    if state.rel is None:
        return new
    # reliability: every dispatch stamps its checkpoint base (DESIGN.md §15)
    return dataclasses.replace(new, rel=dataclasses.replace(
        state.rel,
        last_start=jnp.where(take, t0, state.rel.last_start)))


def _batched_pass(jobs: JobSet, state: SimState, ctx: Optional[AllocCtx],
                  order: jax.Array | str) -> SimState:
    """Start the whole feasible prefix of the waiting queue in one shot.

    For a blocking head-of-queue policy with a free-counter feasibility cap,
    the sequential pass is: walk waiting jobs in policy-key order, start
    each while it still fits, stop at the first that does not.  Node counts
    are >= 1, so the started set is exactly the longest key-ordered waiting
    prefix whose node cumsum stays <= free — one O(J) cumsum over the
    precomputed ``blocking_order`` permutation replaces the whole
    select-one-start-one loop (DESIGN.md §14), bit-identical to it by
    construction.  (Non-waiting rows contribute zero to the cumsum, and the
    cumsum strictly increases across waiting rows, so masking with the
    waiting flag yields exactly the sequential prefix.)  The starts are
    then applied selector-free, in key order; with a count-capped strategy
    the same loop additionally runs each job's node placement.  ``order``
    is ``ROW_ORDER`` when the permutation is the identity
    (``_row_order_pass``).
    """
    if isinstance(order, str):
        return _row_order_pass(jobs, state)
    waiting = state.jstate == WAITING
    w_sorted = waiting[order]
    cum = jnp.cumsum(jnp.where(w_sorted, jobs.nodes[order], 0))
    take = (cum <= state.free) & w_sorted     # longest feasible prefix
    n_take = jnp.cumsum(take.astype(jnp.int32))
    n_started = n_take[-1]

    # Apply the starts one row at a time, in key order: the i-th started
    # row is found by binary search on the running take-count
    # (scatter-free compaction).  Placement needs the order; in scalar
    # mode the loop stands in for a scatter through the permutation
    # (a table in row order takes ``_row_order_pass`` instead).
    if ctx is not None:
        # allocation mode: placements mutate the node map, so reuse the
        # full `_start_job` (the fori carries the whole state)
        def place(i, st):
            pos = jnp.searchsorted(n_take, i + 1)
            return _start_job(jobs, st, order[pos], ctx)

        return jax.lax.fori_loop(0, n_started, place, state)

    # scalar-counter mode: carry ONLY the five leaves a start touches —
    # XLA copies every carried buffer at the loop boundary per event, so a
    # full-state carry would tax the (common) zero-start event with ~10
    # J-sized copies and halve trickle-workload throughput
    if state.rel is not None:
        # reliability adds exactly one more leaf: the checkpoint base
        # ``last_start`` every dispatch must stamp (DESIGN.md §15)
        def place_slim_rel(i, carry):
            jstate, start, finish, rsv, free, last = carry
            pos = jnp.searchsorted(n_take, i + 1)
            idx = order[pos]
            t0 = state.clock
            return (
                jstate.at[idx].set(RUNNING),
                start.at[idx].set(jnp.minimum(start[idx], t0)),
                finish.at[idx].set(t0 + state.remaining[idx]),
                rsv.at[idx].set(t0 + jobs.estimate[idx]),
                free - jobs.nodes[idx],
                last.at[idx].set(t0),
            )

        jstate, start, finish, rsv, free, last = jax.lax.fori_loop(
            0, n_started, place_slim_rel,
            (state.jstate, state.start, state.finish, state.rsv_finish,
             state.free, state.rel.last_start),
        )
        return dataclasses.replace(
            state, jstate=jstate, start=start, finish=finish,
            rsv_finish=rsv, free=free,
            rel=dataclasses.replace(state.rel, last_start=last))

    def place_slim(i, carry):
        jstate, start, finish, rsv, free = carry
        pos = jnp.searchsorted(n_take, i + 1)
        idx = order[pos]
        t0 = state.clock
        return (
            jstate.at[idx].set(RUNNING),
            start.at[idx].set(jnp.minimum(start[idx], t0)),
            finish.at[idx].set(t0 + state.remaining[idx]),
            rsv.at[idx].set(t0 + jobs.estimate[idx]),
            free - jobs.nodes[idx],
        )

    jstate, start, finish, rsv, free = jax.lax.fori_loop(
        0, n_started, place_slim,
        (state.jstate, state.start, state.finish, state.rsv_finish,
         state.free),
    )
    return dataclasses.replace(
        state, jstate=jstate, start=start, finish=finish, rsv_finish=rsv,
        free=free)


def _batched_backfill_pass(jobs: JobSet, state: SimState,
                           ctx: Optional[AllocCtx],
                           order: jax.Array | str) -> SimState:
    """One whole EASY-backfill scheduling pass per event (DESIGN.md §18).

    Phase A — the blocking prefix: EASY starts the FCFS head while it
    fits, which is exactly the blocking batched pass over the submit-order
    permutation.  Phase B — the backfill window: once the head blocks, its
    shadow reservation is computed ONCE.  The shadow TIME is loop-invariant
    under admissions (DESIGN.md §18 proves it from the count-capped premise
    ``free < head_need``), and the ``extra`` budget follows a one-line
    lexicographic rule against the reach entry, so candidates are admitted
    under the shrinking (free, extra) budget without re-sorting anything.
    The admitted set is NOT a prefix of the queue (first-fit skips
    infeasible candidates), so phase B keeps a short while_loop — but each
    iteration is one masked O(J) argmin, and the per-select top-k/sort of
    the seed loop is gone.  Bit-identical to the seed selector loop by the
    invariance argument; the differential grids in
    ``tests/test_engine_fastpath.py`` pin it against refsim.
    """
    # Phase A — the FCFS prefix — runs only when the head can actually
    # start: on trickle workloads most events arrive with the head still
    # blocked, and the prefix machinery (sorted gather + two cumsums)
    # would tax every one of them for zero starts.  The head-fits test is
    # free-count based, which IS the placement cap on every phase-B
    # eligible path (count-capped premise, DESIGN.md §18).
    waiting0 = state.jstate == WAITING
    head0 = policies.lex_argmin(jobs.submit, waiting0)
    head0_fits = ((head0 >= 0)
                  & (jobs.nodes[jnp.maximum(head0, 0)] <= state.free))

    def _phase_a(st: SimState) -> tuple[SimState, jax.Array]:
        st = _batched_pass(jobs, st, ctx, order)
        return st, policies.lex_argmin(jobs.submit, st.jstate == WAITING)

    state, head = jax.lax.cond(head0_fits, _phase_a,
                               lambda st: (st, head0), state)
    head_safe = jnp.maximum(head, 0)
    head_need = jobs.nodes[head_safe]
    idxs = jnp.arange(jobs.capacity, dtype=jnp.int32)
    waiting = state.jstate == WAITING
    # necessary condition for ANY admission: some non-head waiting job fits
    # the free count.  Checking it first (~2 O(J) passes) skips the shadow
    # walk and the guaranteed-failing pick on the frequent backlogged
    # events where nothing fits — the single biggest per-event saving on
    # congested traces.
    any_fit = jnp.any(waiting & (idxs != head_safe)
                      & (jobs.nodes <= state.free))

    def window(st: SimState) -> SimState:
        shadow, extra0, k_row0 = policies.backfill_shadow(jobs, st,
                                                          head_need)
        # release times and estimates are fixed within the event, so each
        # candidate's ends-by-shadow verdict is loop-invariant too
        ends_by = (st.clock + jobs.estimate) <= shadow

        def pick(jstate, free, extra):
            # fits-now compares against the free *count*: phase B is only
            # reached with a count-capped (or scalar) feasibility cap,
            # where ``placeable_cap == state.free`` (same invariant the
            # blocking batched pass rests on)
            cand = ((jstate == WAITING) & (idxs != head_safe)
                    & (jobs.nodes <= free)
                    & (ends_by | (jobs.nodes <= jnp.minimum(free, extra))))
            return policies.lex_argmin(jobs.submit, cand)

        def cond(carry):
            return carry[3] >= 0

        def body(carry):
            st, extra, k_row, idx = carry
            st = _start_job(jobs, st, idx, ctx)
            # the admission consumed reserve nodes iff its release entry
            # (clamped time, row) sorts after the reach entry — a release
            # tie at the shadow breaks by row, exactly like the rel sort
            t_c = jnp.maximum(st.clock + jobs.estimate[idx], st.clock + 1)
            after = (t_c > shadow) | ((t_c == shadow) & (idx > k_row))
            extra = extra - jnp.where(after, jobs.nodes[idx], 0)
            # overdraw (reachable only on a release tie at the shadow, via
            # an ends-by admission wider than the reserve): the reach entry
            # moved within the tie group — recompute it.  While-guarded so
            # the rare case costs nothing under vmap; the shadow time is
            # unchanged by §18, only (extra, k_row) refresh.
            def _redo(carry):
                _sh, ex2, kr2 = policies.backfill_shadow(jobs, st,
                                                         head_need)
                return jnp.bool_(True), ex2, kr2

            _, extra, k_row = jax.lax.while_loop(
                lambda c: ~c[0], _redo, (extra >= 0, extra, k_row))
            return st, extra, k_row, pick(st.jstate, st.free, extra)

        st, _, _, _ = jax.lax.while_loop(
            cond, body,
            (st, extra0, k_row0, pick(st.jstate, st.free, extra0)))
        return st

    # with no waiting head there is nothing to backfill against (a head
    # that still fits cannot exist after phase A), and with no fitting
    # candidate there is nothing the window could admit
    return jax.lax.cond((head >= 0) & any_fit, window, lambda s: s, state)


def _schedule_pass(policy: jax.Array, jobs: JobSet, state: SimState,
                   ctx: Optional[AllocCtx],
                   static_policy: Optional[int] = None,
                   fast_order: Optional[jax.Array | str] = None,
                   mctx: Optional[tuple] = None) -> SimState:
    """Start jobs until the policy blocks (Algorithm 1 lines 16-21).

    Dispatches *at trace time* between the batched prefix pass (when the
    caller precomputed a ``blocking_order`` permutation) and the per-start
    selector loop — a traced policy (``static_policy is None``) always
    compiles the seed loop, so vmapped sweeps pay nothing extra.
    """
    if fast_order is not None:
        if static_policy == BACKFILL:
            return _batched_backfill_pass(jobs, state, ctx, fast_order)
        return _batched_pass(jobs, state, ctx, fast_order)

    def cond(carry):
        _, idx = carry
        return idx >= 0

    def body(carry):
        st, idx = carry
        if static_policy is None or static_policy == PREEMPT:
            # the preempt guard reads the effective node request — with
            # malleability a selected job always fits at its minimum width,
            # so the preempt branch never fires (and the preempt policy
            # itself is rejected with malleable= at the API layer)
            need = (jobs.nodes[idx] if mctx is None
                    else st.mal.width[idx])
            st = jax.lax.cond(
                need <= st.free,
                lambda s: s,
                lambda s: _preempt_for(jobs, s, idx, ctx),  # preempt only
                st,
            )
        st = _start_job(jobs, st, idx, ctx, mctx)
        return st, _select(policy, jobs, st, ctx, static_policy)

    state, _ = jax.lax.while_loop(
        cond, body, (state, _select(policy, jobs, state, ctx, static_policy))
    )
    return state


def dep_csr(jobs: JobSet) -> Optional[tuple]:
    """Loop-invariant CSR row bounds over the (dst-sorted) edge list.

    ``dep_dst`` is emitted dst-ascending by ``make_jobset`` with padding
    (index ``capacity``) at the tail, so per-row edge ranges are two
    ``searchsorted`` arrays computed once per ``simulate`` call.  The event
    loop then updates ``n_unmet`` with gathers + one cumsum instead of an
    E-sized scatter-add (~100x cheaper on XLA:CPU; padding edges sit past
    every row's range and drop out for free).  Returns ``None`` for
    edge-free tables.  Callers whose edge lists may have lost dst order
    (multicluster windows after defensive edge neutralization) must keep
    the scatter-add fallback.
    """
    if jobs.dep_dst is None:
        return None
    J = jobs.capacity
    rows = jnp.arange(J + 1, dtype=jobs.dep_dst.dtype)
    bounds = jnp.searchsorted(jobs.dep_dst, rows)
    return bounds[:-1], bounds[1:]


def _process_rel_events(jobs: JobSet, state: SimState,
                        ctx: Optional[AllocCtx], rel: tuple,
                        mctx: Optional[tuple] = None) -> SimState:
    """Consume every failure/repair stream entry with time <= clock.

    Entries are processed one at a time in stream order (an inner
    ``while_loop`` over the pointer) because each kill changes the running
    set the next kill's victim rule reads.  Semantics, pinned identically
    in ``repro.refsim`` (DESIGN.md §15):

    - *fail* in machine mode: node ``ev_node`` goes down; if it was owned
      by a job, that job is the victim.  In scalar-counter mode nodes are
      anonymous: with ``busy`` running node-seconds and ``n_up`` nodes in
      service, slot ``ev_node % n_up`` hits a running job iff it lands in
      ``[0, busy)`` (utilization-proportional), and the victim is the job
      covering the slot in row-order node cumsum.
    - victim *requeue*: back to WAITING at its submit rank, remaining
      re-charged by the work since its last checkpoint (all of it when
      ``checkpoint_interval == 0``) plus the restart overhead.
    - victim *abort*: DONE + ``aborted``; ``finish`` records the kill
      time, and dependents release (after-any), so DAGs never deadlock.
    - *repair*: the node returns to service.

    The per-node renewal construction guarantees a node never fails while
    down; the machine-mode guards (``down[node]``) only make the
    semantics total under hand-built streams.

    With an *elastic* malleable plan (DESIGN.md §17), a failure whose
    victim still has width to give (``width > min_width``) sheds exactly
    the failed node instead of dying: the job keeps its other nodes and
    its elapsed work, its remaining wall time re-dilates to the narrower
    width, and ``n_resizes`` ticks up.  At ``width == min_width`` the
    normal requeue/abort semantics apply (a requeue resets the width to
    ``min_width`` but remembers the pre-kill width, the basis of the
    redispatch re-dilation).
    """
    ev_time, ev_node, ev_kind, requeue, ckpt, overhead = rel
    K = ev_time.shape[0]
    J = jobs.capacity
    # static: elastic malleability (tick stream present) enables the
    # shrink-instead-of-requeue path; moldable plans keep rigid kills
    mal_shrink = mctx is not None and mctx[2].shape[0] > 0
    # A finished simulation never needs its remaining stream entries — and
    # under vmap this guard is load-bearing: a batched while_loop keeps
    # executing (and discarding) finished members' bodies, and without it a
    # done member whose clock snaps to its leftover stream tail re-drains
    # that whole tail on EVERY lockstep iteration (measured 50-100x on
    # heterogeneous-MTBF sweeps; live members always pass the guard, so
    # semantics are untouched).
    live = jnp.any(state.jstate != DONE)

    def cond(st: SimState):
        p = st.rel.ptr
        return (p < K) & (ev_time[jnp.minimum(p, K - 1)] <= st.clock) & live

    def body(st: SimState) -> SimState:
        r = st.rel
        e = jnp.minimum(r.ptr, K - 1)
        node = ev_node[e]
        is_fail = ev_kind[e] == FAIL
        eff_nodes = jobs.nodes if mctx is None else st.mal.width

        if ctx is None:
            runn = st.jstate == RUNNING
            rn = jnp.where(runn, eff_nodes, 0)
            busy = jnp.sum(rn)
            n_up = st.free + busy
            slot = node % jnp.maximum(n_up, 1)
            cum = jnp.cumsum(rn)
            victim = jnp.argmax(cum > slot).astype(jnp.int32)
            has_victim = is_fail & (slot < busy)
            goes_down = is_fail
            comes_up = ~is_fail
            new_down = r.down                     # [0] placeholder
        else:
            own = st.node_owner[node]
            was_down = r.down[node]
            has_victim = is_fail & (own >= 0) & ~was_down
            victim = jnp.maximum(own, 0)
            goes_down = is_fail & ~was_down
            comes_up = ~is_fail & was_down
            new_down = r.down.at[node].set(is_fail)

        # failure-shrink (elastic malleability only): a victim with width
        # to give sheds the failed node instead of dying
        w_v = eff_nodes[victim]
        if mal_shrink:
            wlo = mctx[3]
            shrink = has_victim & (w_v > wlo)
            kill = has_victim & ~shrink
        else:
            shrink = jnp.asarray(False)
            kill = has_victim

        # checkpoint rework: work since the last checkpoint (the whole run
        # when ckpt == 0) is lost and re-charged on requeue; remaining is
        # in the same post-dilation units preemption pins (DESIGN.md §11)
        el = st.clock - r.last_start[victim]
        saved = jnp.where(ckpt > 0, (el // jnp.maximum(ckpt, 1)) * ckpt, 0)
        lost = el - saved
        req = requeue == REQUEUE
        kill_req = kill & req
        kill_abort = kill & ~req
        new_rem = jnp.maximum(st.finish[victim] - st.clock + lost + overhead,
                              1)

        jstate = st.jstate.at[victim].set(jnp.where(
            kill,
            jnp.where(req, jnp.int32(WAITING), jnp.int32(DONE)),
            st.jstate[victim]))
        finish = st.finish.at[victim].set(jnp.where(
            kill, jnp.where(req, jnp.int32(INF_TIME), st.clock),
            st.finish[victim]))
        rsv = st.rsv_finish.at[victim].set(jnp.where(
            kill, jnp.int32(INF_TIME), st.rsv_finish[victim]))
        remaining = st.remaining.at[victim].set(jnp.where(
            kill_req, new_rem, st.remaining[victim]))
        n_restarts = r.n_restarts.at[victim].add(kill_req.astype(jnp.int32))
        lost_work = r.lost_work.at[victim].add(jnp.where(
            kill_req, lost + overhead, jnp.where(kill_abort, el, 0)))
        aborted = r.aborted.at[victim].set(kill_abort | r.aborted[victim])

        n_unmet = st.n_unmet
        if jobs.dep_dst is not None:
            dec = ((jobs.dep_src == victim) & kill_abort).astype(jnp.int32)
            n_unmet = n_unmet.at[jobs.dep_dst].add(-dec, mode="drop")

        # a kill frees the victim's whole (effective) footprint; a shrink
        # frees exactly the failed node — which then immediately goes down,
        # so the free counter nets zero on a shrink
        freed = jnp.where(kill, w_v, jnp.where(shrink, 1, 0))
        free = (st.free + freed - goes_down.astype(jnp.int32)
                + comes_up.astype(jnp.int32))

        node_owner = st.node_owner
        if ctx is not None:
            vmask = jnp.zeros((J,), bool).at[victim].set(kill)
            node_owner = _release_nodes(st.node_owner, vmask, J)
            if mal_shrink:
                # the shrink releases the failed node specifically
                node_owner = node_owner.at[node].set(jnp.where(
                    shrink, jnp.int32(-1), node_owner[node]))

        if mal_shrink:
            W = mctx[0].shape[1]
            k_old = jnp.clip(w_v - wlo, 0, W - 1)
            k_new = jnp.clip(w_v - 1 - wlo, 0, W - 1)
            sh_rem = _ratio_ceil(st.finish[victim] - st.clock,
                                 mctx[0][victim, k_new],
                                 mctx[0][victim, k_old])
            finish = finish.at[victim].set(jnp.where(
                shrink, st.clock + sh_rem, finish[victim]))
            if ctx is not None:
                own_mask = node_owner == victim
                s_first, s_asum = _alloc.alloc_fingerprint(own_mask)
                s_span = _alloc.group_span(ctx[0], own_mask)
                st = dataclasses.replace(
                    st,
                    alloc_first=st.alloc_first.at[victim].set(jnp.where(
                        shrink, s_first, st.alloc_first[victim])),
                    alloc_span=st.alloc_span.at[victim].set(jnp.where(
                        shrink, s_span, st.alloc_span[victim])),
                    alloc_sum=st.alloc_sum.at[victim].set(jnp.where(
                        shrink, s_asum, st.alloc_sum[victim])),
                )

        mal = st.mal
        if mctx is not None:
            m = st.mal
            touched = kill | shrink
            closed = jnp.where(touched,
                               w_v * (st.clock - m.seg_start[victim]), 0)
            new_w = jnp.where(shrink, w_v - 1,
                              jnp.where(kill_req, mctx[3], w_v))
            mal = dataclasses.replace(
                m,
                width=m.width.at[victim].set(jnp.where(
                    touched, new_w, m.width[victim])),
                prev_w=m.prev_w.at[victim].set(jnp.where(
                    shrink, new_w, m.prev_w[victim])),
                seg_start=m.seg_start.at[victim].set(jnp.where(
                    shrink, st.clock, m.seg_start[victim])),
                node_s=m.node_s.at[victim].add(closed),
                n_resizes=m.n_resizes.at[victim].add(
                    shrink.astype(jnp.int32)),
            )

        new_rel = dataclasses.replace(
            r, ptr=r.ptr + 1,
            n_restarts=n_restarts, lost_work=lost_work, aborted=aborted,
            down=new_down)
        return dataclasses.replace(
            st, jstate=jstate, finish=finish, rsv_finish=rsv,
            remaining=remaining, n_unmet=n_unmet, free=free,
            node_owner=node_owner, rel=new_rel, mal=mal)

    return jax.lax.while_loop(cond, body, state)


def _process_capacity_ticks(jobs: JobSet, state: SimState,
                            ctx: Optional[AllocCtx], svc: tuple) -> SimState:
    """Consume every autoscaler tick with time <= clock (DESIGN.md §16).

    Ticks are processed one at a time in stream order (an inner
    ``while_loop`` over the pointer) because each tick's capacity change
    feeds the next tick's bounds.  Semantics, pinned identically in
    ``repro.refsim``:

    - queued demand is the node-request sum over WAITING jobs (this
      event's arrivals have NOT happened yet — capacity ticks run after
      completions and reliability entries, before arrivals);
    - demand >= up_threshold: up to ``step`` nodes come back online,
      never beyond ``max_nodes`` (pre-clamped to the machine size).  In
      machine mode the *lowest-index* offline nodes return;
    - else if demand <= down_threshold: up to ``step`` nodes go offline,
      never below ``min_nodes`` and never more than the free count — a
      busy node is never taken, so a running job is never stranded (drain
      semantics: capacity leaves only as it frees up).  In machine mode
      the *highest-index* free online nodes leave;
    - the online count after the tick is logged to ``cap_online[ptr]``
      (the capacity series goodput-under-autoscaling integrates).
    """
    deadline, tick_time, up_t, down_t, step, min_n, max_n = svc
    T = tick_time.shape[0]
    # same vmap liveness guard as the reliability stream: a finished batch
    # member must not re-drain its leftover tick tail every lockstep
    # iteration (and a finished simulation needs no capacity changes)
    live = jnp.any(state.jstate != DONE)

    def cond(st: SimState):
        p = st.svc.ptr
        return (p < T) & (tick_time[jnp.minimum(p, T - 1)] <= st.clock) & live

    def body(st: SimState) -> SimState:
        s = st.svc
        demand = jnp.sum(jnp.where(st.jstate == WAITING,
                                   _jobs_eff(jobs, st).nodes, 0))
        up = demand >= up_t
        down = ~up & (demand <= down_t)
        k_up = jnp.where(up, jnp.clip(max_n - s.n_online, 0, step), 0)
        k_down = jnp.where(
            down,
            jnp.minimum(jnp.clip(s.n_online - min_n, 0, step),
                        jnp.maximum(st.free, 0)),
            0)
        delta = (k_up - k_down).astype(jnp.int32)
        if ctx is None:
            offline = s.offline               # [0] placeholder
        else:
            # scale-up reactivates the lowest-index offline nodes;
            # scale-down deactivates the highest-index FREE online nodes
            # (cumsum rank masks; k_down <= free so enough candidates)
            on_rank = jnp.cumsum(s.offline.astype(jnp.int32))
            react = s.offline & (on_rank <= k_up)
            free_node = (st.node_owner < 0) & ~s.offline
            down_rank = jnp.cumsum(free_node[::-1].astype(jnp.int32))[::-1]
            deact = free_node & (down_rank <= k_down)
            offline = (s.offline & ~react) | deact
        n_online = s.n_online + delta
        new_svc = dataclasses.replace(
            s, ptr=s.ptr + 1, n_online=n_online, offline=offline,
            cap_online=s.cap_online.at[s.ptr].set(n_online, mode="drop"))
        return dataclasses.replace(st, free=st.free + delta, svc=new_svc)

    return jax.lax.while_loop(cond, body, state)


def _process_mal_ticks(jobs: JobSet, state: SimState,
                       ctx: Optional[AllocCtx], mctx: tuple) -> SimState:
    """Consume every elastic resize tick with time <= clock (DESIGN.md §17).

    Ticks are processed one at a time in stream order (an inner
    ``while_loop`` over the pointer) because each resize changes the
    widths the next tick's demand and candidate rules read.  Semantics,
    pinned identically in ``repro.refsim`` — at most ONE resize action per
    tick:

    - queued demand is the effective-width sum over WAITING jobs (this
      event's arrivals have NOT happened yet — resize ticks run after
      completions, reliability entries and capacity ticks, before
      arrivals);
    - demand >= shrink_threshold: the *widest* running job above
      ``min_width`` (ties to the lowest row) sheds
      ``min(step, width - min_width)`` nodes, freeing room for the queue.
      In machine mode its *highest-index* owned nodes release;
    - else if demand <= grow_threshold: the *narrowest* running job below
      ``max_width`` (ties to the lowest row) grows by ``min(step,
      max_width - width, cap)`` where ``cap`` is the placement-feasibility
      cap (the free counter, or the strategy's placeable cap in machine
      mode; no action when the cap is 0).  In machine mode the new nodes
      place via the active strategy over ``owner_eff``;
    - either action closes the job's node-second segment, re-dilates its
      remaining wall time to the new width (``_ratio_ceil``), restamps its
      finish event, and recomputes its allocation fingerprints.
    """
    dur_t, _, tick_time, wlo, whi, step = mctx[0], mctx[1], mctx[2], \
        mctx[3], mctx[4], mctx[5]
    shrink_t, grow_t = mctx[6], mctx[7]
    T = tick_time.shape[0]
    W = dur_t.shape[1]
    # same vmap liveness guard as the reliability/capacity streams
    live = jnp.any(state.jstate != DONE)

    def cond(st: SimState):
        p = st.mal.ptr
        return (p < T) & (tick_time[jnp.minimum(p, T - 1)] <= st.clock) & live

    def body(st: SimState) -> SimState:
        m = st.mal
        running = st.jstate == RUNNING
        demand = jnp.sum(jnp.where(st.jstate == WAITING, m.width, 0))
        shrink_tick = demand >= shrink_t
        grow_tick = ~shrink_tick & (demand <= grow_t)
        # shrink: widest running above min_width; grow: narrowest running
        # below max_width — both tie to the lowest row (first argext)
        s_cand = running & (m.width > wlo)
        g_cand = running & (m.width < whi)
        s_vic = jnp.argmax(jnp.where(s_cand, m.width, -1)).astype(jnp.int32)
        g_vic = jnp.argmin(jnp.where(g_cand, m.width,
                                     jnp.int32(INF_TIME))).astype(jnp.int32)
        do_shrink = shrink_tick & jnp.any(s_cand)
        vic = jnp.where(do_shrink, s_vic, g_vic)
        w_v = m.width[vic]
        if ctx is None:
            gcap = jnp.maximum(st.free, 0)
        else:
            gcap = _alloc.placeable_cap(ctx[1], _owner_eff(jobs, st))
        d_grow = jnp.minimum(jnp.minimum(step, whi - w_v), gcap)
        do_grow = grow_tick & jnp.any(g_cand) & (d_grow > 0)
        resize = do_shrink | do_grow
        d = jnp.where(do_shrink, jnp.minimum(step, w_v - wlo),
                      jnp.where(do_grow, d_grow, 0))
        new_w = jnp.where(do_shrink, w_v - d, w_v + d)

        # remaining wall time re-dilates to the new width
        k_old = jnp.clip(w_v - wlo, 0, W - 1)
        k_new = jnp.clip(new_w - wlo, 0, W - 1)
        new_r = _ratio_ceil(st.finish[vic] - st.clock,
                            dur_t[vic, k_new], dur_t[vic, k_old])
        finish = st.finish.at[vic].set(jnp.where(
            resize, st.clock + new_r, st.finish[vic]))
        free = st.free + jnp.where(do_shrink, d,
                                   jnp.where(do_grow, -d, 0))

        node_owner = st.node_owner
        alloc_first, alloc_span, alloc_sum = (
            st.alloc_first, st.alloc_span, st.alloc_sum)
        if ctx is not None:
            machine, strategy, _ = ctx
            own_mask = st.node_owner == vic
            # shrink releases the d highest-index owned nodes
            shed_rank = jnp.cumsum(
                own_mask[::-1].astype(jnp.int32))[::-1]
            shed = own_mask & (shed_rank <= jnp.where(do_shrink, d, 0))
            # grow places d new nodes via the strategy over owner_eff
            add = _alloc.place(strategy, machine, _owner_eff(jobs, st),
                               jnp.where(do_grow, d, 0))
            node_owner = jnp.where(shed, jnp.int32(-1), st.node_owner)
            node_owner = jnp.where(add, vic, node_owner)
            mask_new = node_owner == vic
            n_first, n_asum = _alloc.alloc_fingerprint(mask_new)
            n_span = _alloc.group_span(machine, mask_new)
            alloc_first = st.alloc_first.at[vic].set(jnp.where(
                resize, n_first, st.alloc_first[vic]))
            alloc_span = st.alloc_span.at[vic].set(jnp.where(
                resize, n_span, st.alloc_span[vic]))
            alloc_sum = st.alloc_sum.at[vic].set(jnp.where(
                resize, n_asum, st.alloc_sum[vic]))

        closed = jnp.where(resize, w_v * (st.clock - m.seg_start[vic]), 0)
        new_mal = dataclasses.replace(
            m, ptr=m.ptr + 1,
            width=m.width.at[vic].set(jnp.where(resize, new_w, w_v)),
            prev_w=m.prev_w.at[vic].set(jnp.where(
                resize, new_w, m.prev_w[vic])),
            seg_start=m.seg_start.at[vic].set(jnp.where(
                resize, st.clock, m.seg_start[vic])),
            node_s=m.node_s.at[vic].add(closed),
            n_resizes=m.n_resizes.at[vic].add(resize.astype(jnp.int32)))
        return dataclasses.replace(
            st, finish=finish, free=free, node_owner=node_owner,
            alloc_first=alloc_first, alloc_span=alloc_span,
            alloc_sum=alloc_sum, mal=new_mal)

    return jax.lax.while_loop(cond, body, state)


def _event_step(policy: jax.Array, jobs: JobSet, state: SimState,
                ctx: Optional[AllocCtx] = None,
                static_policy: Optional[int] = None,
                fast_order: Optional[jax.Array | str] = None,
                csr: Optional[tuple] = None,
                rel: Optional[tuple] = None,
                svc: Optional[tuple] = None,
                mctx: Optional[tuple] = None) -> SimState:
    with jax.named_scope("advance"):
        pending = state.jstate == PENDING
        running = state.jstate == RUNNING
        has_deps = jobs.dep_dst is not None
        mal_ticks = mctx is not None and mctx[2].shape[0] > 0

        # A PENDING job generates an arrival event only once its dependencies
        # are DONE; unreleased dependents are invisible to the clock (and to
        # backfill's shadow math, which never sees them as WAITING).  The
        # pre-completion release mask is the standing counter — no recompute.
        arrivable = pending & (state.n_unmet == 0) if has_deps else pending
        t_arr = jnp.min(jnp.where(arrivable, jobs.submit, INF_TIME))
        t_fin = jnp.min(jnp.where(running, state.finish, INF_TIME))
        clock = jnp.minimum(t_arr, t_fin)
        if rel is not None:
            K = rel[0].shape[0]
            p = state.rel.ptr
            t_rel = jnp.where(p < K, rel[0][jnp.minimum(p, K - 1)],
                              jnp.int32(INF_TIME))
            clock = jnp.minimum(clock, t_rel)
        if svc is not None and svc[1].shape[0] > 0:
            # T == 0 (no autoscaler) statically elides the tick clock source
            T = svc[1].shape[0]
            p = state.svc.ptr
            t_svc = jnp.where(p < T, svc[1][jnp.minimum(p, T - 1)],
                              jnp.int32(INF_TIME))
            clock = jnp.minimum(clock, t_svc)
        if mal_ticks:
            # T == 0 (moldable mode) statically elides the resize clock source
            Tm = mctx[2].shape[0]
            p = state.mal.ptr
            t_mal = jnp.where(p < Tm, mctx[2][jnp.minimum(p, Tm - 1)],
                              jnp.int32(INF_TIME))
            clock = jnp.minimum(clock, t_mal)

        # completions first (frees nodes for arrivals at the same timestamp);
        # with malleability a completing job frees its current width and closes
        # its node-second segment
        completed = running & (state.finish <= clock)
        eff_nodes = jobs.nodes if mctx is None else state.mal.width
        freed = jnp.sum(jnp.where(completed, eff_nodes, 0)).astype(jnp.int32)
        jstate = jnp.where(completed, DONE, state.jstate)
        node_owner = (state.node_owner if ctx is None
                      else _release_nodes(state.node_owner, completed,
                                          jobs.capacity))
        mal_after = state.mal
        if mctx is not None:
            closed = jnp.where(
                completed, state.mal.width * (clock - state.mal.seg_start), 0)
            mal_after = dataclasses.replace(
                state.mal, node_s=state.mal.node_s + closed)

        # arrivals — dependents of this event's completions release *now*
        # (paper §3 release rule): each RUNNING->DONE transition happens
        # exactly once, so decrementing n_unmet along the completing jobs'
        # out-edges keeps the counters exact; a job whose last dependency
        # just finished joins the wait queue in the same event, with
        # ready_time = max(submit, last dep finish).  Padding edges scatter
        # out of range and drop.
        # (named scope "release": this update and the arrival mask's
        # dependency test, present only when the table carries edges)
        n_unmet = state.n_unmet
        if has_deps:
            with jax.named_scope("release"):
                J = jobs.capacity
                dec = completed[jnp.clip(jobs.dep_src, 0, J - 1)].astype(
                    jnp.int32)
                if csr is not None:
                    row_start, row_end = csr
                    c = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                         jnp.cumsum(dec)])
                    n_unmet = n_unmet - (c[row_end] - c[row_start])
                else:
                    n_unmet = n_unmet.at[jobs.dep_dst].add(-dec, mode="drop")
        if rel is not None or svc is not None or mal_ticks:
            # stream events run after completions (a job finishing at the
            # failure/tick instant has completed) and before arrivals (a job
            # whose last dependency aborts still releases within this same
            # event; autoscale and resize ticks read queued demand *before*
            # this event's arrivals join the queue) — order: completions,
            # reliability, capacity ticks, resize ticks, arrivals
            state = dataclasses.replace(
                state, clock=clock, jstate=jstate, n_unmet=n_unmet,
                free=state.free + freed, node_owner=node_owner, mal=mal_after)
            if rel is not None:
                with jax.named_scope("failures"):
                    state = _process_rel_events(jobs, state, ctx, rel, mctx)
            if svc is not None and svc[1].shape[0] > 0:
                state = _process_capacity_ticks(jobs, state, ctx, svc)
            if mal_ticks:
                state = _process_mal_ticks(jobs, state, ctx, mctx)
            jstate, n_unmet = state.jstate, state.n_unmet
            arrived = (jstate == PENDING) & (jobs.submit <= clock)
            if has_deps:
                with jax.named_scope("release"):
                    arrived = arrived & (n_unmet == 0)
            jstate = jnp.where(arrived, WAITING, jstate)
            state = dataclasses.replace(
                state, jstate=jstate, n_events=state.n_events + 1)
        else:
            arrived = (jstate == PENDING) & (jobs.submit <= clock)
            if has_deps:
                with jax.named_scope("release"):
                    arrived = arrived & (n_unmet == 0)
            jstate = jnp.where(arrived, WAITING, jstate)

            state = dataclasses.replace(
                state,
                clock=clock,
                jstate=jstate,
                n_unmet=n_unmet,
                free=state.free + freed,
                n_events=state.n_events + 1,
                node_owner=node_owner,
                mal=mal_after,
            )
    with jax.named_scope("schedule"):
        state = _schedule_pass(policy, jobs, state, ctx, static_policy,
                               fast_order, mctx)
    if ctx is None:
        return state
    # fragmentation log: one (clock, free, largest-free-block) row per event
    with jax.named_scope("log"):
        slot = state.n_events - 1
        return dataclasses.replace(
            state,
            ev_time=state.ev_time.at[slot].set(state.clock, mode="drop"),
            ev_free=state.ev_free.at[slot].set(state.free, mode="drop"),
            ev_lfb=state.ev_lfb.at[slot].set(
                _alloc.largest_free_run(_owner_eff(jobs, state)),
                mode="drop"),
        )


def make_alloc_ctx(machine, strategy, contention,
                   total_nodes=None) -> Optional[AllocCtx]:
    """Canonicalize user-facing allocation arguments into an ``AllocCtx``.

    Raises when allocation arguments are inconsistent: ``alloc``/
    ``contention`` without a ``machine`` would be silently ignored, and a
    ``machine`` whose size disagrees with a *concrete* ``total_nodes`` would
    corrupt the occupancy map (a traced ``total_nodes`` skips that check —
    the caller owns it in sweep code).
    """
    if machine is None:
        if strategy is not None or contention is not None:
            raise ValueError(
                "alloc/contention require machine=; without a Machine the "
                "simulation runs in scalar-counter mode and would silently "
                "ignore them")
        return None
    if total_nodes is not None:
        try:
            concrete = int(total_nodes)
        except (TypeError, jax.errors.TracerArrayConversionError,
                jax.errors.ConcretizationTypeError):
            concrete = None
        if concrete is not None and concrete != machine.n_nodes:
            raise ValueError(
                f"machine has {machine.n_nodes} nodes but "
                f"total_nodes={concrete}")
    strategy = jnp.asarray(_alloc.canonical_id(strategy), dtype=jnp.int32)
    return (machine, strategy, _alloc.Contention.canonical(contention))


def simulate(
    jobs: JobSet,
    policy: jax.Array | int,
    total_nodes: jax.Array | int,
    *,
    machine=None,
    alloc: jax.Array | int | str | None = None,
    contention=None,
    failures=None,
    service=None,
    malleable=None,
    max_events: Optional[int] = None,
) -> SimResult:
    """Run the full job-scheduling simulation for one cluster.

    This is the low-level engine call; the declarative front door is
    ``repro.api.run(Scenario(...))``, which builds the job table, machine
    and contention from one spec and returns a unified ``Result``
    (DESIGN.md §12).  Kept stable for callers that already hold a
    ``JobSet``.

    Pure function of its inputs (``policy``, ``total_nodes``, the allocation
    ``alloc`` strategy id and ``contention`` parameters are traced, so the
    same executable serves every policy/machine-size/allocator combination);
    ``vmap``-able over ``jobs`` leaves, ``policy``, ``total_nodes``,
    ``alloc`` and/or ``contention`` for ensemble simulation (see
    ``repro.core.parallel``).

    Without ``machine`` the engine runs in the seed scalar-counter mode.
    With ``machine`` (a ``repro.alloc.Machine`` whose ``n_nodes`` must equal
    ``total_nodes``) each start places concrete nodes under the ``alloc``
    strategy and the result carries allocation fingerprints plus the
    per-event fragmentation log.

    When ``policy`` (and, with a machine, ``alloc``) is concrete at call
    time, the executable specializes on it: the policy selector dispatches
    directly instead of through ``lax.switch``, and the blocking policies
    take the batched scheduling pass (DESIGN.md §14).  Each concrete policy
    then compiles its own executable; traced values (vmap axes) keep the
    shared fully-dynamic executable with seed semantics.

    ``failures`` (None, a ``repro.reliability.FailureModel``, a
    ``FailureTrace``, or a prebuilt fail-ctx tuple) switches on the
    reliability subsystem (DESIGN.md §15); ``None`` statically elides it.

    ``service`` (None, a ``repro.serving.ServiceTrace``, a ``ServicePlan``,
    or a prebuilt svc-ctx tuple) switches on the online-serving subsystem
    (DESIGN.md §16): per-job SLO deadlines in the result and a hysteresis
    autoscaler consuming a deterministic capacity-tick stream.  ``None``
    statically elides it to the pre-serving event graph.

    ``malleable`` (None, a ``repro.malleable.MalleablePlan``, or a prebuilt
    mal-ctx tuple) switches on the malleability subsystem (DESIGN.md §17):
    moldable width choice at dispatch, and — in elastic mode — grow/shrink
    resize ticks plus shrink-instead-of-requeue on node failures.
    ``None`` statically elides it to the rigid event graph.
    """
    ctx = make_alloc_ctx(machine, alloc, contention, total_nodes)
    fctx = make_fail_ctx(failures, n_nodes=_concrete_int(total_nodes))
    sctx = make_svc_ctx(service, n_nodes=_concrete_int(total_nodes))
    mctx = make_mal_ctx(malleable)
    if (ctx is not None and fctx is not None and sctx is not None
            and sctx[1].shape[-1] > 0):
        # the autoscaler's offline mask and the reliability down mask would
        # double-count the shared free counter (a node can be failed and
        # drained at once); scalar-counter mode composes fine
        raise ValueError(
            "machine-mode failures cannot be combined with an active "
            "autoscaler; drop machine=, failures=, or autoscale")
    static_policy = _static_policy_hint(policy)
    if mctx is not None:
        if contention is not None:
            # the speedup curve already maps width to runtime; span-based
            # contention would dilate the dilated value a second time
            raise ValueError(
                "malleable jobs cannot be combined with contention "
                "dilation; the speedup curve owns the width->runtime map")
        if static_policy == PREEMPT:
            raise ValueError(
                "malleable jobs cannot be combined with the preempt "
                "policy; a suspended job's width bookkeeping is undefined")
        if mctx[0].ndim == 2 and mctx[0].shape[0] != jobs.capacity:
            raise ValueError(
                f"malleable plan rows ({mctx[0].shape[0]}) do not match "
                f"the job-table capacity ({jobs.capacity}); materialize "
                "the plan with capacity == the padded job capacity")
    static_strategy = _concrete_int(ctx[1]) if ctx is not None else None
    if mctx is None:
        _count_pass_kind(_batched_pass_kind(jobs, ctx, static_policy,
                                            static_strategy))
    return _simulate_jit(
        jobs, jnp.asarray(policy, dtype=jnp.int32),
        jnp.asarray(total_nodes, dtype=jnp.int32), ctx, fctx=fctx,
        sctx=sctx, mctx=mctx, max_events=max_events,
        static_policy=static_policy, static_strategy=static_strategy,
    )


@functools.partial(
    jax.jit,
    static_argnames=("max_events", "static_policy", "static_strategy"))
def _simulate_jit(
    jobs: JobSet,
    policy: jax.Array,
    total_nodes: jax.Array,
    ctx: Optional[AllocCtx],
    fctx: Optional[tuple] = None,
    sctx: Optional[tuple] = None,
    mctx: Optional[tuple] = None,
    *,
    max_events: Optional[int] = None,
    static_policy: Optional[int] = None,
    static_strategy: Optional[int] = None,
) -> SimResult:
    if fctx is None:
        base_cap = 6 * jobs.capacity + 8
        rel = None
    else:
        # every failure adds at most one kill (an extra start + completion
        # cycle) and two stream entries, so the event bound grows with the
        # padded failure capacity F — a static shape, known at trace time
        F = fctx[0].shape[-1]
        base_cap = 6 * jobs.capacity + 6 * F + 8
        # one loop-invariant stable merge of the failure + repair streams,
        # pinned identically (host-side) in repro.reliability.merge_stream
        times = jnp.concatenate([fctx[0], fctx[2]])
        nodes = jnp.concatenate([fctx[1], fctx[1]])
        kind = jnp.concatenate([jnp.zeros_like(fctx[1]),
                                jnp.ones_like(fctx[1])])
        order = jnp.argsort(times, stable=True)
        rel = (times[order], nodes[order], kind[order],
               fctx[3], fctx[4], fctx[5])
    if sctx is None:
        svc = None
        svc_T = None
    else:
        # each capacity tick consumes exactly one event; T is static
        svc_T = sctx[1].shape[-1]
        base_cap = base_cap + svc_T
        # clamp max_nodes to the actual cluster size here (total_nodes may
        # be traced, so the spec layer cannot always do it)
        svc = sctx[:6] + (
            jnp.minimum(sctx[6], jnp.asarray(total_nodes, jnp.int32)),)
    if mctx is not None:
        # each elastic resize tick consumes exactly one event; the tick
        # capacity is a static shape (0 in moldable mode)
        base_cap = base_cap + mctx[2].shape[-1]
    cap = max_events if max_events is not None else base_cap
    machine = ctx[0] if ctx is not None else None
    state = SimState.init(jobs, total_nodes, machine=machine, event_log=cap,
                          failures=fctx is not None, service=svc_T,
                          malleable=None if mctx is None
                          else (mctx[3], mctx[2].shape[-1]))
    # the batched prefix pass assumes rigid node requests; malleable runs
    # keep the per-start selector loop (widths change under its feet)
    fast_order = (None if mctx is not None
                  else _fast_order(jobs, ctx, static_policy, static_strategy))
    csr = dep_csr(jobs)   # jobs are immutable here, dst order guaranteed

    def cond(st: SimState):
        unfinished = jnp.any((st.jstate != DONE))
        return unfinished & (st.n_events < cap)

    state = jax.lax.while_loop(
        cond,
        lambda st: _event_step(policy, jobs, st, ctx, static_policy,
                               fast_order, csr, rel, svc, mctx),
        state,
    )
    return result_from_state(
        jobs, state, deadline=None if sctx is None else sctx[0],
        nref=None if mctx is None else mctx[1])


def _batched_pass_kind(jobs: JobSet, ctx: Optional[AllocCtx],
                       static_policy: Optional[int],
                       static_strategy: Optional[int]) -> Optional[str]:
    """How the scheduling pass runs, from static facts alone: ``ROW_ORDER``
    (batched, queue read in row order), ``PERMUTED`` (batched, queue read
    through ``blocking_order``) or ``None`` (per-start selector loop).

    Row order needs scalar-counter mode (placement in machine mode walks
    the starts one by one anyway), a submit-keyed blocking order (FCFS, or
    EASY's phase A) and a table whose ``submit_sorted`` says that order is
    the identity.  See ``_fast_order`` for the batched-pass eligibility.
    """
    if not (ctx is None or static_strategy in _COUNT_CAPPED):
        return None
    if static_policy != BACKFILL and not (
            jobs.dep_dst is not None and static_policy in _BLOCKING_POLICIES):
        return None
    if ctx is None and jobs.submit_sorted and static_policy in (FCFS,
                                                                BACKFILL):
        return ROW_ORDER
    return PERMUTED


def _count_pass_kind(kind: Optional[str]) -> None:
    """Counters ``engine.batched.row_order`` / ``engine.batched.permuted``
    (DESIGN.md §21.3): one per host call (per trace under a caller's
    jit)."""
    if kind is not None:
        obs.count("engine.batched." + kind)


def _fast_order(jobs: JobSet, ctx: Optional[AllocCtx],
                static_policy: Optional[int],
                static_strategy: Optional[int]) -> Optional[jax.Array | str]:
    """The loop-invariant batched-pass permutation, ``ROW_ORDER`` when that
    permutation is the identity, or ``None`` when the combination keeps the
    per-start selector loop (DESIGN.md §14 eligibility table).

    The batched pass needs a blocking policy and a free-counter feasibility
    cap, and it only *pays* on workloads whose events start many jobs at
    once — which is the dependency-carrying tables (DAG stage fronts
    release whole waves into one event; measured 7-90x there).  Dependency-
    free traces trickle arrivals in, so their typical event starts 0-1
    jobs and the per-event selection prefix would tax every event; they
    keep the selector loop (measured at or above seed throughput with the
    static selector dispatch).  All three paths are bit-identical — this
    is purely a trace-time cost model.

    Backfill is the exception to the deps-only rule: its seed loop paid a
    shadow recomputation over the running set on EVERY blocked select, so
    the batched pass — one shadow per event instead of one per select
    (DESIGN.md §18) — wins on dependency-free traces too (measured
    2.0k -> 28.8k ev/s on the congested no-deps SDSC-like case, where
    nearly every event has a blocked head).
    """
    kind = _batched_pass_kind(jobs, ctx, static_policy, static_strategy)
    if kind == PERMUTED:
        return blocking_order(jobs, static_policy)
    return kind


def next_event_time(jobs: JobSet, state: SimState) -> jax.Array:
    pending = state.jstate == PENDING
    running = state.jstate == RUNNING
    arrivable = (pending & (state.n_unmet == 0)
                 if jobs.dep_dst is not None else pending)
    t_arr = jnp.min(jnp.where(arrivable, jobs.submit, INF_TIME))
    t_fin = jnp.min(jnp.where(running, state.finish, INF_TIME))
    return jnp.minimum(t_arr, t_fin)


def simulate_window(
    policy: jax.Array,
    jobs: JobSet,
    state: SimState,
    t_hi: jax.Array,
    max_events: jax.Array | int,
    ctx: Optional[AllocCtx] = None,
    rel: Optional[tuple] = None,
) -> tuple[SimState, jax.Array]:
    """Process every event with timestamp <= ``t_hi`` (conservative window).

    The multi-cluster engine (``repro.core.parallel``) calls this once per
    synchronization round — the JAX analogue of SST's conservative
    per-lookahead-window execution (DESIGN.md §2) — and the streaming
    trace-replay runner (``repro.replay``) once per refill round.
    ``policy`` is usually a closed-over concrete array here, so the
    fast-path specialization resolves at trace time exactly as in
    ``simulate``.

    Returns ``(state, saturated)``.  ``saturated`` is a bool scalar set
    when the loop stopped at ``max_events`` with events still due at or
    below ``t_hi`` — the window's answer is then a *truncated prefix* of
    the round, which used to be silent.  Callers must either re-enter with
    a higher cap (the state is a valid prefix; replay doubles the cap and
    continues) or surface the flag (``MulticlusterResult.saturated``).

    ``rel`` is the merged failure/repair stream 6-tuple of ``simulate``'s
    reliability path (``state.rel`` must then be initialized); ``None``
    statically elides it, keeping the existing callers' lowering
    byte-identical.
    """
    static_policy = _static_policy_hint(policy)
    static_strategy = _concrete_int(ctx[1]) if ctx is not None else None
    _count_pass_kind(_batched_pass_kind(jobs, ctx, static_policy,
                                        static_strategy))
    fast_order = _fast_order(jobs, ctx, static_policy, static_strategy)

    def next_due(st: SimState):
        # the failure/repair stream is a clock source in _event_step, so it
        # must also be one here: a round whose only upcoming event is a
        # repair (jobs queued behind down nodes) would otherwise never fire.
        # Gated on the same any-job-unfinished guard as simulate's cond —
        # a finished table never needs its remaining stream entries.
        nxt = next_event_time(jobs, st)
        if rel is not None:
            K = rel[0].shape[0]
            p = st.rel.ptr
            t_rel = jnp.where(p < K, rel[0][jnp.minimum(p, K - 1)],
                              jnp.int32(INF_TIME))
            live = jnp.any(st.jstate != DONE)
            nxt = jnp.minimum(nxt, jnp.where(live, t_rel,
                                             jnp.int32(INF_TIME)))
        return nxt

    def cond(st: SimState):
        # INF_TIME is the nothing-is-due sentinel (padding rows, drained
        # streams), never a real instant: without the strict bound a drain
        # round at t_hi = INF_TIME would spin no-op events into the cap —
        # and then read as saturated
        due = next_due(st)
        return (due <= t_hi) & (due < INF_TIME) & (st.n_events < max_events)

    state = jax.lax.while_loop(
        cond,
        lambda st: _event_step(policy, jobs, st, ctx, static_policy,
                               fast_order, None, rel),
        state,
    )
    due = next_due(state)
    saturated = (due <= t_hi) & (due < INF_TIME) \
        & (state.n_events >= max_events)
    return state, saturated


def simulate_np(trace, policy, *, total_nodes: int, capacity: int | None = None,
                machine=None, alloc: int | str | None = None, contention=None):
    """Host convenience shim: dict-of-numpy trace -> numpy result dict.

    Equivalent to ``repro.api.run(Scenario(trace=trace, ...)).to_np()``;
    kept as the minimal-dependency one-call path (and as the schema
    reference for ``repro.api.Result.to_np``).
    """
    import numpy as np
    from repro.core.jobs import make_jobset

    jobs = make_jobset(
        trace["submit"], trace["runtime"], trace["nodes"],
        trace.get("estimate"), trace.get("priority"),
        deps=trace.get("deps"),
        capacity=capacity, total_nodes=total_nodes,
    )
    pol = policies_id(policy)
    res = simulate(jobs, pol, total_nodes, machine=machine, alloc=alloc,
                   contention=contention)
    ok = np.asarray(res.done)
    out = {
        "submit": np.asarray(jobs.submit),
        "nodes": np.asarray(jobs.nodes),
        "runtime": np.asarray(jobs.runtime),
        "start": np.asarray(res.start),
        "finish": np.asarray(res.finish),
        "ready": np.asarray(res.ready),
        "wait": np.asarray(res.wait),
        "makespan": int(res.makespan),
        "n_events": int(res.n_events),
        "done": ok,
        "valid": np.asarray(jobs.valid),
    }
    if machine is not None:
        n_ev = out["n_events"]
        out["alloc_first"] = np.asarray(res.alloc_first)
        out["alloc_span"] = np.asarray(res.alloc_span)
        out["alloc_sum"] = np.asarray(res.alloc_sum)
        out["ev_time"] = np.asarray(res.ev_time)[:n_ev]
        out["ev_free"] = np.asarray(res.ev_free)[:n_ev]
        out["ev_lfb"] = np.asarray(res.ev_lfb)[:n_ev]
    return out


def policies_id(policy) -> int:
    from repro.core.jobs import POLICY_IDS
    if isinstance(policy, str):
        return POLICY_IDS[policy.lower()]
    return int(policy)
