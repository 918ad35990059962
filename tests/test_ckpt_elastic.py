"""Elastic restore across device-count changes (subprocess: 4 -> 2 devices).

The checkpoint stores unsharded global arrays; restore re-device_puts onto
whatever mesh the restarted job has — the core of elastic scaling.

Each subprocess pays a full JAX cold start; on slow single-core containers
that can exceed any fixed limit, so the per-subprocess timeout is tunable
via ``REPRO_ELASTIC_TIMEOUT`` (seconds, default 240) and a timeout SKIPS
with a reason instead of hanging or failing tier-1.
"""

import os
import subprocess
import sys
import textwrap

import pytest

# wall-clock budget per subprocess; the pytest.mark.timeout below (enforced
# by pytest-timeout when installed, registered in pytest.ini either way)
# adds headroom for both subprocesses plus parent overhead
SUBPROC_TIMEOUT = int(os.environ.get("REPRO_ELASTIC_TIMEOUT", "240"))

_SAVE = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.ckpt.store import save_checkpoint
    mesh = Mesh(np.array(jax.devices()).reshape(4), ("data",))
    tree = {
        "w": jax.device_put(jnp.arange(64, dtype=jnp.float32).reshape(8, 8),
                            NamedSharding(mesh, P("data", None))),
        "b": jnp.float32(7.0),
    }
    save_checkpoint(sys.argv[1], 5, tree, extra={"devices": 4})
    print("SAVED", len(jax.devices()))
""")

_LOAD = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.ckpt.store import load_checkpoint
    mesh = Mesh(np.array(jax.devices()).reshape(2), ("data",))
    template = {"w": jnp.zeros((8, 8), jnp.float32), "b": jnp.float32(0)}
    shardings = {"w": NamedSharding(mesh, P("data", None)),
                 "b": NamedSharding(mesh, P())}
    tree, step, extra = load_checkpoint(sys.argv[1], template,
                                        shardings=shardings)
    assert step == 5 and extra["devices"] == 4
    assert np.array_equal(np.asarray(tree["w"]),
                          np.arange(64, dtype=np.float32).reshape(8, 8))
    assert len(tree["w"].sharding.device_set) == 2
    print("RESTORED", len(jax.devices()))
""")


def _run_step(argv, env, step: str) -> subprocess.CompletedProcess:
    try:
        return subprocess.run(argv, env=env, capture_output=True, text=True,
                              timeout=SUBPROC_TIMEOUT)
    except subprocess.TimeoutExpired:
        pytest.skip(
            f"elastic-restore {step} subprocess exceeded {SUBPROC_TIMEOUT}s "
            "(slow container; raise REPRO_ELASTIC_TIMEOUT to run it)")


@pytest.mark.timeout(2 * SUBPROC_TIMEOUT + 60)
def test_elastic_restore_across_device_counts(tmp_path):
    # the child rehearses on virtual CPU devices and must never take a chip
    env = {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"}
    ck = str(tmp_path / "ck")
    p1 = _run_step([sys.executable, "-c", _SAVE, ck], env, "save")
    assert "SAVED 4" in p1.stdout, p1.stderr[-800:]
    p2 = _run_step([sys.executable, "-c", _LOAD, ck], env, "load")
    assert "RESTORED 2" in p2.stdout, p2.stderr[-800:]
