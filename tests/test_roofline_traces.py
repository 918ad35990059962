"""HLO analyzer correctness (trip counts, dot flops, collectives), trace
loaders, and sharding-rule repair."""

import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch.hlo_analysis import analyze_hlo_text
from repro.launch.roofline import analytic_hbm_bytes, roofline_terms
from repro.sharding.rules import repair_pspec
from repro.traces.swf import load_swf


def test_analyzer_counts_loop_trips_for_flops():
    """L-layer scanned matmul: flops must be ~ 2*M*K*N*L, not /L."""
    M = K = N = 64
    L = 7

    def f(ws, x):
        def body(h, w):
            return h @ w, 0
        h, _ = jax.lax.scan(body, x, ws)
        return h

    ws = jnp.zeros((L, K, N))
    x = jnp.zeros((M, K))
    compiled = jax.jit(f).lower(ws, x).compile()
    stats = analyze_hlo_text(compiled.as_text())
    expect = 2 * M * K * N * L
    assert stats.flops == pytest.approx(expect, rel=0.05), (
        stats.flops, expect, stats.while_loops)
    # XLA's own cost_analysis undercounts by ~L (the bug we correct);
    # jax 0.4.x returns a one-dict-per-device list, newer jax a plain dict
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    xla = float(ca.get("flops", 0))
    assert xla < stats.flops


def test_analyzer_parses_collectives_with_trip_counts():
    hlo = textwrap.dedent("""\
    HloModule m

    %body (p: (s32[], f32[16,8])) -> (s32[], f32[16,8]) {
      %p = (s32[], f32[16,8]) parameter(0)
      %i = s32[] get-tuple-element(%p), index=0
      %x = f32[16,8]{1,0} get-tuple-element(%p), index=1
      %ag = f32[32,8]{1,0} all-gather(%x), dimensions={0}
      %rs = f32[16,8]{1,0} reduce-scatter(%ag), dimensions={0}, to_apply=%add
      ROOT %t = (s32[], f32[16,8]) tuple(%i, %rs)
    }

    %cond (p: (s32[], f32[16,8])) -> pred[] {
      %p = (s32[], f32[16,8]) parameter(0)
      ROOT %c = pred[] constant(true)
    }

    ENTRY %main (a: f32[16,8]) -> f32[16,8] {
      %a = f32[16,8]{1,0} parameter(0)
      %ar = f32[16,8]{1,0} all-reduce(%a), to_apply=%add
      %t0 = (s32[], f32[16,8]) tuple(%ar, %ar)
      %w = (s32[], f32[16,8]) while(%t0), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
      ROOT %o = f32[16,8]{1,0} get-tuple-element(%w), index=1
    }
    """)
    stats = analyze_hlo_text(hlo)
    assert stats.collective_bytes["all-reduce"] == 16 * 8 * 4
    assert stats.collective_bytes["all-gather"] == 5 * 32 * 8 * 4
    assert stats.collective_bytes["reduce-scatter"] == 5 * 32 * 8 * 4  # max(in,out)
    assert stats.while_loops == {"body": 5}


def test_roofline_terms_pick_dominant():
    t = roofline_terms(flops_per_device=197e12, bytes_per_device=1.0,
                       coll_bytes_per_device=1.0)
    assert t["bottleneck"] == "compute" and t["t_compute_s"] == pytest.approx(1.0)
    t = roofline_terms(flops_per_device=1.0, bytes_per_device=819e9 * 2,
                       coll_bytes_per_device=1.0)
    assert t["bottleneck"] == "memory" and t["t_memory_s"] == pytest.approx(2.0)


def test_analytic_bytes_monotone_in_params():
    from repro.configs.base import SHAPES, get_config
    mesh = {"data": 16, "model": 16}
    small = analytic_hbm_bytes(get_config("llama3.2-3b"), SHAPES["train_4k"],
                               mesh, int(3.2e9), "train_fsdp_tp")
    big = analytic_hbm_bytes(get_config("qwen2-vl-72b"), SHAPES["train_4k"],
                             mesh, int(72e9), "train_fsdp_tp")
    assert big > small > 0


def test_repair_pspec_moves_uneven_axis():
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)

    class FakeMesh:
        shape = {"data": 16, "model": 16}
    fm = FakeMesh()
    # kv=8 not divisible by 16 -> "model" moves to head_dim (128)
    spec = repair_pspec((32, 4096, 8, 128), P(None, "data", "model", None), fm)
    assert spec == P(None, "data", None, "model")
    # nothing fits -> axis dropped entirely
    spec = repair_pspec((3, 5), P("data", "model"), fm)
    assert spec == P(None, None)
    # already fine -> untouched
    spec = repair_pspec((64, 32), P("data", "model"), fm)
    assert spec == P("data", "model")


def test_swf_parser(tmp_path):
    swf = textwrap.dedent("""\
    ; SWF header comment
    ; MaxNodes: 128
    1 0 -1 120 16 -1 -1 16 300 -1 1 1 1 1 1 -1 -1 -1
    2 30 -1 60 8 -1 -1 8 100 -1 1 1 1 1 1 -1 -1 -1
    3 60 -1 0 4 -1 -1 4 50 -1 0 1 1 1 1 -1 -1 -1
    """)
    p = tmp_path / "log.swf"
    p.write_text(swf)
    tr, rep = load_swf(str(p))
    assert len(tr["submit"]) == 2  # zero-runtime row dropped
    assert rep.n_jobs == 2 and rep.n_skipped == 1
    np.testing.assert_array_equal(tr["nodes"], [16, 8])
    np.testing.assert_array_equal(tr["estimate"], [300, 100])


def test_synthetic_traces_shape_and_determinism():
    from repro.traces import das2_like, sdsc_sp2_like
    a = das2_like(500, seed=3)
    b = das2_like(500, seed=3)
    np.testing.assert_array_equal(a["submit"], b["submit"])
    assert (a["nodes"] >= 1).all() and (a["nodes"] <= 400).all()
    assert (a["estimate"] >= a["runtime"]).all()
    c = sdsc_sp2_like(200, seed=1)
    assert (c["nodes"] <= 128).all()
    assert (np.diff(c["submit"]) >= 0).all()
