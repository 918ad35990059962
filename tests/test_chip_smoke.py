"""``chip_smoke.py`` rehearsed on the CPU, and the compile-cache helper.

The script itself refuses to run without a TPU, so the rehearsal drives
its phases directly at ``SIZES["tiny"]``: the same scenarios, checks and
server, on toy sizes.  The four-chip phases run in a child process on four
virtual CPU devices; the child is pinned to the CPU so that it can never
take a chip.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import pytest

ROOT = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro import compile_cache  # noqa: E402

TINY = chip_smoke.SIZES["tiny"]


def _cpu_env(**extra):
    return {**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu",
            **extra}


def _phase_lines(text: str) -> dict:
    lines = [json.loads(ln) for ln in text.splitlines() if ln.startswith("{")]
    return {ln["phase"]: ln for ln in lines if "phase" in ln}


@pytest.mark.parametrize("phase", ["run", "sweep", "replay", "whatif"])
def test_phase_passes_on_cpu_at_tiny_size(phase, tmp_path, capsys):
    fn = getattr(chip_smoke, f"phase_{phase}")
    fn(TINY, str(tmp_path)) if phase == "replay" else fn(TINY)
    line = _phase_lines(capsys.readouterr().out)[phase]
    assert line["wall_s"] > 0 and line["compile_s"] >= 0
    if phase == "sweep":
        assert line["lanes"] == 48 and line["sweep_compiles"] == 1
    if phase == "whatif":
        for family in ("placement", "capacity", "reliability"):
            assert line[family]["warm_compiles"] == 0


@pytest.mark.timeout(600)
def test_four_device_phases_on_virtual_cpus(tmp_path):
    child = ("import sys; sys.path.insert(0, '.'); import chip_smoke as c; "
             f"c.run_phases(c.SIZES['tiny'], 4, {str(tmp_path)!r})")
    p = subprocess.run(
        [sys.executable, "-c", child], cwd=ROOT, capture_output=True,
        text=True, timeout=540,
        env=_cpu_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert p.returncode == 0, p.stderr[-2000:]
    lines = _phase_lines(p.stdout)
    assert lines["sharded_sweep"]["devices"] == [0, 1, 2, 3]
    assert lines["sharded_multicluster"]["devices"] == [0, 1, 2, 3]


def _no_ok_line(out: str) -> bool:
    return not any('"ok"' in ln for ln in out.splitlines())


@pytest.mark.timeout(300)
def test_refuses_without_tpu():
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                       capture_output=True, text=True, timeout=240,
                       env=_cpu_env())
    assert p.returncode != 0
    assert _no_ok_line(p.stdout)
    assert '"platform": "cpu"' in p.stdout


@pytest.mark.timeout(300)
def test_fails_alone_outside_the_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=240,
                       env={**_cpu_env(), "PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_ok_line(p.stdout)


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_leaves_the_env_setting_alone(monkeypatch, tmp_path,
                                                    restore_cache_dir):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


_LM_MODULES = ("repro.models", "repro.launch", "repro.kernels.flash_attention",
               "repro.kernels.linattn_scan")


@pytest.mark.timeout(300)
def test_imports_leave_the_cache_off_and_the_lm_code_out():
    env = _cpu_env()
    env.pop(compile_cache.ENV_VAR, None)
    child = ("import sys, jax, chip_smoke, repro.refsim, repro.replay, "
             "repro.service; print(jax.config.jax_compilation_cache_dir); "
             "print(sorted(m for m in sys.modules "
             f"if m.startswith({_LM_MODULES!r})))")
    p = subprocess.run([sys.executable, "-c", child], cwd=ROOT,
                       capture_output=True, text=True, timeout=240, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.strip().splitlines()[-2:] == ["None", "[]"]
