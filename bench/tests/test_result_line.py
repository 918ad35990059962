"""A whole run of every cell at a small size, the chip check skipped."""

import json

import pytest

from conftest import CELLS, small
from lib import harness

KEYS = ("correct", "attempted", "failed", "metrics", "device")


def _run(cell_name, workdir, *, trace=False):
    bench, cell, cfg, trf = small(cell_name)
    return bench, harness.run_cell(
        bench, cell, seed=2**33 + 17, seconds=1.0, trace=trace, t_start=0.0,
        device=harness.device_info(cell["chips"]), workdir=workdir,
        config=cfg, traffic=trf)


@pytest.mark.parametrize("cell_name", CELLS)
def test_untraced_run_prints_the_end_to_end_metrics(cell_name, workdir):
    bench, res = _run(cell_name, workdir)
    assert all(k in res for k in KEYS)
    assert list(res)[-1] == "limits"
    assert res["correct"] is True, res["limits"]
    assert res["failed"] == 0 and res["attempted"] >= 1
    want = {m["name"] for m in harness.cell_metrics(bench, cell_name,
                                                      "end_to_end")}
    assert set(res["metrics"]) == want
    for m in res["metrics"].values():
        assert m["value"] > 0 and m["unit"]
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        res["device"])
    assert res["limits"]["compared"] > 0
    json.dumps(res)


def test_traced_run_reads_per_layer_metrics(workdir):
    bench, res = _run("sdsc_sp2_128.run8k", workdir, trace=True)
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    allowed = {m["name"] for m in harness.cell_metrics(
        bench, "sdsc_sp2_128.run8k", "per_layer")}
    assert set(res["metrics"]) <= allowed
    assert list(res)[-1] == "limits"


def test_command_refuses_without_a_tpu(capsys):
    rc = harness.main(["--workload", "sdsc_sp2_128.run8k", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert out.out == ""
    assert "no TPU" in out.err
