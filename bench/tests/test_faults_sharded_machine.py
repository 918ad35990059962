"""Faults of the two paths that only the four-chip sweep and the machine-mode
``run()`` cells drive (the latter kept out of ``BENCHMARK.json``, see
``conftest.KEPT``): each run with the timed path broken underneath must
read ``correct`` false, and the unbroken run true."""

import json
import os
import subprocess
import sys
import textwrap

import pytest

from conftest import BENCH, small
from lib import harness

# Four virtual CPU devices in a child (the device count is fixed when JAX
# starts): the four-chip cell on its mesh, sound, then under each fault of
# the sharded bucket: the second chip's shard of lanes replaced by the
# first chip's, as if the lanes never left chip 0 (the exchange left out);
# one job's finish altered in every lane; half of the bucket run and its
# answers copied into the other half.
_SHARDED_CHILD = textwrap.dedent("""
    import dataclasses, importlib, json, os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [os.path.join(sys.argv[1], "tests"), sys.argv[1]]
    import jax
    from conftest import small
    from lib import harness

    bench, cell, cfg, trf = small("dragonfly_1024.sweep48.x4")
    assert cell["chips"] == 4 and len(jax.devices()) == 4
    sweep_mod = importlib.import_module("repro.api.sweep")
    bucket_fn, run_bucket = sweep_mod._bucket_fn, sweep_mod._run_bucket

    def run():
        return harness.run_cell(
            bench, cell, seed=2**32 + 3, seconds=1.0, trace=False,
            t_start=0.0, device=harness.device_info(4), workdir=sys.argv[2],
            config=cfg, traffic=trf)["correct"]

    def exchange(*key):
        fn = bucket_fn(*key)
        assert key[5] is not None, "the bucket is not sharded"

        def broken(*args):
            def swap(x):
                n = x.shape[0] // 4
                return x.at[n:2 * n].set(x[:n])
            return jax.tree.map(swap, fn(*args))
        return broken
    exchange.cache_clear = bucket_fn.cache_clear

    def altered(bucket, mesh):
        assert mesh is not None, "the bucket is not sharded"
        out = run_bucket(bucket, mesh)
        for r in out:
            r.raw = dataclasses.replace(r.raw, finish=r.raw.finish.at[0].add(1))
        return out

    def half(bucket, mesh):
        assert mesh is not None, "the bucket is not sharded"
        n = len(bucket) // 2
        out = run_bucket(bucket[:n], mesh)
        return out + [dataclasses.replace(out[i % n], scenario=scn)
                      for i, scn in enumerate(bucket[n:])]

    got = {"sound": run()}
    sweep_mod._bucket_fn = exchange
    got["exchange"] = run()
    sweep_mod._bucket_fn = bucket_fn
    for name, fault in (("altered", altered), ("half", half)):
        sweep_mod._run_bucket = fault
        got[name] = run()
    sweep_mod._run_bucket = run_bucket
    print(json.dumps(got))
""")


@pytest.fixture(scope="module")
def sharded_runs(tmp_path_factory):
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    work = tmp_path_factory.mktemp("sharded") / "work"
    p = subprocess.run([sys.executable, "-c", _SHARDED_CHILD, BENCH,
                        str(work)], env=env, capture_output=True, text=True,
                       timeout=840)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.timeout(900)
def test_sharded_sweep_unbroken_is_correct(sharded_runs):
    assert sharded_runs["sound"] is True


@pytest.mark.timeout(900)
@pytest.mark.parametrize("fault", ["exchange", "altered", "half"])
def test_sharded_sweep_fault_reads_false(sharded_runs, fault):
    """``exchange`` is a shard of another chip in place of this one's."""
    assert sharded_runs[fault] is False


def _machine_run(workdir):
    bench, cell, cfg, trf = small("dragonfly_1024.run4k")
    assert cfg["machine"]["topology"] and cfg["failures"]
    return harness.run_cell(
        bench, cell, seed=2**31 + 29, seconds=1.0, trace=False, t_start=0.0,
        device=harness.device_info(1), workdir=workdir, config=cfg,
        traffic=trf)


def test_machine_run_unbroken_is_correct(workdir):
    res = _machine_run(workdir)
    assert res["correct"] is True, res["limits"]
    assert res["limits"]["compared"] > 0


def test_machine_run_answer_altered(monkeypatch, workdir):
    import dataclasses

    from repro.core import engine

    orig = engine.simulate

    def broken(*a, **k):
        res = orig(*a, **k)
        return dataclasses.replace(res, finish=res.finish.at[0].add(1))

    monkeypatch.setattr(engine, "simulate", broken)
    assert _machine_run(workdir)["correct"] is False
