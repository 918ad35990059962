"""The main path's programs compile for a TPU v5e chip, from shapes alone.

Nothing here runs on a chip: the TPU compiler is asked to compile for a
described ``v5e:2x2`` topology and raises what the chip's compiler would
raise (an unaligned slice, a scalar stored to VMEM, a program too large for
the device).  The sizes are those ``chip_smoke.py`` runs.  The topology is
described inside a fixture, never at import, so every test worker collects
the same tests and only the worker that runs this file loads the TPU
library.
"""

import dataclasses
import itertools
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import chip_smoke  # noqa: E402
from repro.api import (  # noqa: E402
    Scenario, SyntheticTrace, WorkflowTrace, build_jobset,
)
from repro.api.sweep import _bucket_fn, _bucket_program  # noqa: E402
from repro.core import engine  # noqa: E402
from repro.kernels.queue_select.kernel import queue_select_tiled  # noqa: E402
from repro.reliability import make_fail_ctx  # noqa: E402

FULL = chip_smoke.SIZES["full"]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shapes(tree, sharding):
    """Array leaves -> ShapeDtypeStructs on ``sharding``; Python scalars
    and static leaves stay as they are."""
    def leaf(x):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)
        return x
    return jax.tree.map(leaf, tree)


def _compile_simulate(scn: Scenario, sharding):
    """Compile ``engine._simulate_jit`` the way ``run(scn)`` calls it."""
    jobs = build_jobset(scn)
    tn = int(scn.total_nodes)
    machine = scn.topology.build() if scn.topology is not None else None
    ctx = engine.make_alloc_ctx(machine, scn.alloc, scn.contention, tn)
    fctx = make_fail_ctx(scn.failures, n_nodes=tn)
    policy = engine.policies_id(scn.policy)
    args = _shapes((jobs, jnp.asarray(policy, jnp.int32),
                    jnp.asarray(tn, jnp.int32), ctx), sharding)
    return engine._simulate_jit.lower(
        *args, fctx=_shapes(fctx, sharding),
        static_policy=engine._static_policy_hint(policy),
        static_strategy=(engine._concrete_int(ctx[1])
                         if ctx is not None else None),
    ).compile()


def test_simulate_compiles_sdsc_backfill_16k(one_chip):
    scn = Scenario(trace=SyntheticTrace(n_jobs=FULL.run_jobs, seed=0,
                                        kind="sdsc_sp2"),
                   total_nodes=128, policy="backfill")
    compiled = _compile_simulate(scn, one_chip)
    assert compiled.memory_analysis() is not None


def test_simulate_compiles_dragonfly_topo_failures(one_chip):
    scn = chip_smoke.machine_scenario(FULL, FULL.machine_jobs, FULL.mtbf[0])
    assert scn.total_nodes >= 1024
    compiled = _compile_simulate(scn, one_chip)
    assert compiled.memory_analysis() is not None


def test_sweep_bucket_compiles_48_lanes(one_chip):
    base = chip_smoke.machine_scenario(FULL, FULL.sweep_jobs, FULL.mtbf[0])
    axes = {**chip_smoke.SWEEP_AXES, "failures.mtbf": FULL.mtbf}
    bucket = [base.with_(**dict(zip(axes, combo)))
              for combo in itertools.product(*axes.values())]
    assert len(bucket) >= 48
    key, args, machine, _ = _bucket_program(bucket, None)
    fn = _bucket_fn(*key)
    compiled = fn.lower(*_shapes(args, one_chip),
                        _shapes(machine, one_chip)).compile()
    assert compiled.memory_analysis() is not None


def test_queue_select_kernel_compiles_1m(one_chip):
    n = 1 << 20
    x = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda s, f: queue_select_tiled(
        s, f, tile=8192, interpret=False)).lower(x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


# an HLO instruction: its opcode, and the op_name of its metadata
_HLO_OP = re.compile(
    r'^\s*(?:ROOT )?\S+ = (\S+|\(.*?\)) ([a-z][a-z0-9-]*)\(.*'
    r'op_name="([^"]*)"')


def _schedule_ops(scn, sharding, row_order):
    """(opcode, shape, op_name) of every compiled instruction whose
    op_name lies under the engine's ``schedule`` scope."""
    jobs = build_jobset(scn)
    if not row_order:
        jobs = dataclasses.replace(jobs, submit_sorted=False)
    policy = engine.policies_id(scn.policy)
    args = _shapes((jobs, jnp.asarray(policy, jnp.int32),
                    jnp.asarray(int(scn.total_nodes), jnp.int32), None),
                   sharding)
    text = engine._simulate_jit.lower(
        *args, fctx=None, static_policy=engine._static_policy_hint(policy),
        static_strategy=None).compile().as_text()
    ops = [m.groups() for m in map(_HLO_OP.match, text.splitlines()) if m]
    assert ops
    return jobs.capacity, [(op, shape, name) for shape, op, name in ops
                           if "/schedule/" in name]


@pytest.mark.parametrize("row_order", (True, False),
                         ids=("row_order", "gathered"))
def test_fcfs_campaign_schedule_has_no_gather_or_loop(one_chip, row_order):
    """On a make_jobset table the FCFS campaign's scheduling pass compiles
    to neither a gather nor a while loop; the same table without
    ``submit_sorted`` keeps both, which shows the match finds them."""
    scn = Scenario(trace=WorkflowTrace(
        kind="montage_campaign", seed=0,
        params=(("images", 40), ("mosaics", 2), ("pairs", 110),
                ("tiles", 4))), total_nodes=32, policy="fcfs")
    _, ops = _schedule_ops(scn, one_chip, row_order)
    found = {op for op, _, _ in ops} & {"gather", "while"}
    assert found == (set() if row_order else {"gather", "while"})


@pytest.mark.parametrize("row_order", (True, False),
                         ids=("row_order", "gathered"))
def test_easy_phase_a_has_no_table_sized_gather(one_chip, row_order):
    """EASY's phase A on a make_jobset table reads the queue without a
    J-sized gather (the backfill window never had one)."""
    scn = Scenario(trace=SyntheticTrace(n_jobs=256, seed=0, kind="sdsc_sp2"),
                   total_nodes=128, policy="backfill")
    J, ops = _schedule_ops(scn, one_chip, row_order)
    sized = [name for op, shape, name in ops
             if op == "gather" and re.match(rf"\w+\[{J}\]", shape)]
    assert bool(sized) != row_order, sized
