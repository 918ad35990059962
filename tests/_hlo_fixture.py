"""HLO fingerprints of the no-failure engine executables (ISSUE 5).

The reliability subsystem must *statically elide* to nothing: a
``failures=None`` simulation has to lower to the exact HLO module the
pre-reliability engine produced — not just the same results, the same
compiled program.  This module pins that: ``fingerprints()`` lowers the
engine across the existing policy × alloc × DAG differential grid and
hashes the StableHLO text; ``tests/data/hlo_nofail.json`` holds the pinned
hashes, and ``tests/test_engine_fastpath.py`` asserts today's lowering
still matches.

Regenerate (only when an *intentional* engine-graph change lands)::

    PYTHONPATH=src:tests python tests/_hlo_fixture.py --write

Hashes are stable across processes for a fixed jax version; the fixture
records the jax version it was built with, and a toolchain bump fails the
comparison with the command above, so the pin never lapses in silence.
"""

from __future__ import annotations

import hashlib
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import engine
from repro.core.jobs import POLICY_IDS, make_jobset
from repro.traces import sdsc_sp2_like
from repro.traces.workflows import galactic_like, montage_like, workflow_to_trace

FIXTURE = os.path.join(os.path.dirname(__file__), "data", "hlo_nofail.json")

ALL_POLICIES = ("fcfs", "sjf", "ljf", "bestfit", "backfill", "preempt")


def _dag_jobs(total_nodes: int):
    trace = workflow_to_trace(galactic_like(tiles=2, width=5, seed=0))
    return make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace["deps"],
                       total_nodes=total_nodes)


def _montage_jobs(total_nodes: int):
    trace = workflow_to_trace(montage_like(6, seed=2))
    return make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], deps=trace["deps"],
                       total_nodes=total_nodes)


def _plain_jobs(total_nodes: int):
    trace = sdsc_sp2_like(80, seed=11)
    return make_jobset(trace["submit"], trace["runtime"], trace["nodes"],
                       trace["estimate"], total_nodes=total_nodes)


def configs():
    """(name, jobs, policy_name, total_nodes, topology_or_None, alloc) grid.

    Mirrors the differential grid the fast-path identity tests run: every
    policy on a DAG and on a plain trace in scalar-counter mode, plus the
    machine modes (count-capped and geometry-capped strategies).
    """
    from repro.api import Topology

    out = []
    for pol in ALL_POLICIES:
        out.append((f"dag_scalar_{pol}", _dag_jobs(8), pol, 8, None, None))
        out.append((f"plain_scalar_{pol}", _plain_jobs(16), pol, 16, None, None))
    mesh = Topology.mesh2d(4, 4)
    for pol in ("fcfs", "backfill"):
        for alloc in ("simple", "contiguous"):
            out.append((f"dag_mesh_{pol}_{alloc}", _montage_jobs(16), pol, 16,
                        mesh, alloc))
    # the fully-dynamic executable (traced policy — the vmap-sweep path)
    out.append(("plain_dynamic", _plain_jobs(16), None, 16, None, None))
    return out


def _lower(jobs, policy_name, total_nodes, topology, alloc):
    if topology is not None:
        machine = topology.build()
        ctx = engine.make_alloc_ctx(machine, alloc, None)
    else:
        ctx = None
    if policy_name is None:
        pol_id, static_policy, static_strategy = 0, None, None
    else:
        pol_id = POLICY_IDS[policy_name]
        static_policy = engine._static_policy_hint(pol_id)
        static_strategy = (engine._concrete_int(ctx[1])
                           if ctx is not None else None)
    kwargs = dict(max_events=None, static_policy=static_policy,
                  static_strategy=static_strategy)
    args = (jobs, jnp.asarray(pol_id, jnp.int32),
            jnp.asarray(total_nodes, jnp.int32), ctx)
    try:
        # post-reliability signature: the elided failure context is explicit
        return engine._simulate_jit.lower(*args, fctx=None, **kwargs)
    except TypeError:
        # pre-reliability signature (fixture generation at the seed commit)
        return engine._simulate_jit.lower(*args, **kwargs)


def fingerprints() -> dict:
    out = {}
    for name, jobs, pol, tn, topo, alloc in configs():
        txt = _lower(jobs, pol, tn, topo, alloc).as_text()
        out[name] = hashlib.sha256(txt.encode()).hexdigest()
    return out


def load_fixture() -> dict:
    with open(FIXTURE) as f:
        return json.load(f)


def write_fixture() -> dict:
    fp = {"jax_version": jax.__version__, "hashes": fingerprints()}
    os.makedirs(os.path.dirname(FIXTURE), exist_ok=True)
    with open(FIXTURE, "w") as f:
        json.dump(fp, f, indent=1, sort_keys=True)
        f.write("\n")
    return fp


if __name__ == "__main__":
    import sys

    if "--write" in sys.argv:
        fp = write_fixture()
        print(f"wrote {FIXTURE} ({len(fp['hashes'])} configs, "
              f"jax {fp['jax_version']})")
    else:
        want = load_fixture()["hashes"]
        got = fingerprints()
        bad = {k for k in want if want[k] != got.get(k)}
        print("MATCH" if not bad else f"MISMATCH: {sorted(bad)}")
        sys.exit(1 if bad else 0)
