"""Multi-cluster DES under a real device mesh (subprocess, 4 host devices):
the shard_map + all_gather migration path must match the single-device
vmapped path bit-for-bit (conservative-sync correctness on actual SPMD).
A ``sweep(mesh=)`` bucket, sharded over the same mesh, gives the
single-device answers."""

import os
import subprocess
import sys
import textwrap

import pytest

_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.core.jobs import POLICY_IDS, make_jobset
    from repro.core.parallel import (multicluster_result_np,
                                     simulate_multicluster, stack_jobsets)
    from repro.traces import das2_like

    C, J = 4, 120
    trs = [das2_like(J, seed=50 + s) for s in range(C)]
    jsets = [make_jobset(t["submit"], t["runtime"], t["nodes"], t["estimate"],
                         capacity=J + 32, total_nodes=96) for t in trs]
    jc = stack_jobsets(jsets)
    horizon = int(max(t["submit"].max() for t in trs) + 50_000)
    kw = dict(window=4000, horizon=horizon, migrate=True, max_export=4)

    mesh = Mesh(np.array(jax.devices()), ("sim",))
    a = simulate_multicluster(jc, POLICY_IDS["backfill"], [96] * C,
                              mesh=mesh, **kw)
    b = simulate_multicluster(jc, POLICY_IDS["backfill"], [96] * C,
                              mesh=None, **kw)
    for x, y in zip(jax.tree.leaves(a.state), jax.tree.leaves(b.state)):
        assert np.array_equal(np.asarray(x), np.asarray(y)), "sharded != vmapped"
    out = multicluster_result_np(a)
    assert out["dropped"] == 0 and out["done"].sum() == C * J
    assert not out["saturated"]
    print("SHARDED_OK migrated=", out["migrated"])
""")


@pytest.mark.timeout(600)
def test_multicluster_sharded_matches_single_device(tmp_path):
    # the child rehearses on virtual CPU devices and must never take a chip
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", _CHILD], env=env,
                       capture_output=True, text=True, timeout=540)
    assert "SHARDED_OK" in p.stdout, (p.stdout[-400:], p.stderr[-800:])


_SWEEP_CHILD = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro import obs
    from repro.api import Scenario, SyntheticTrace, sweep

    scn = Scenario(trace=SyntheticTrace(n_jobs=30, seed=1, kind="sdsc_sp2"),
                   total_nodes=32, policy="backfill")
    axes = {"policy": ("fcfs", "sjf", "backfill")}   # 3 lanes, 1 padded
    mesh = Mesh(np.array(jax.devices()), ("sim",))
    key = "sweep.split.unstacked"
    c0 = obs.counters().get(key, 0)
    a = sweep(scn, axes, mesh=mesh)
    c1 = obs.counters().get(key, 0)
    b = sweep(scn, axes)
    leaves = len(jax.tree.leaves(b.results[0].raw))
    assert c1 - c0 == leaves, (c1 - c0, leaves)
    for x, y in zip(a.results, b.results):
        p, q = x.to_np(), y.to_np()
        assert set(p) == set(q)
        for k in p:
            assert np.array_equal(p[k], q[k]), k
    print("SWEEP_SHARDED_OK lanes=", len(a.results))
""")


@pytest.mark.timeout(600)
def test_sharded_sweep_matches_one_device(tmp_path):
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    p = subprocess.run([sys.executable, "-c", _SWEEP_CHILD], env=env,
                       capture_output=True, text=True, timeout=540)
    assert "SWEEP_SHARDED_OK" in p.stdout, (p.stdout[-400:], p.stderr[-800:])
