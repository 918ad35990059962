"""Schema regression tests for the engine perf artifact (ISSUE 5, ISSUE 8).

``benchmarks/des_throughput.py`` emits ``results/BENCH_engine.json`` — the
machine-readable perf trajectory future PRs regress against.  A benchmark
refactor that silently changes keys or units would corrupt that trajectory
without failing anything; these tests pin the schema:

- every report names the device that took its numbers (``platform``,
  ``device_kind``, ``count``), so a CPU number is never read as a chip's;
- every case carries a positive ``run_s``; engine cases carry ``n_events``
  / ``events_per_s`` / ``compile_s`` that are mutually consistent;
- wall-clock stamps are present and monotonic (schema >= 2);
- kernel cases are timed *compiled* and carry bytes/tile so GB/s figures
  are comparable across cases (schema >= 3 — ISSUE 8: the old artifact
  timed the Pallas interpreter and hardcoded the element size);
- the checked-in artifact (if present) parses under the same validator and
  holds the ISSUE-8 throughput floors: backfill within 3x of FCFS on the
  2k no-deps case, no >10x GB/s cliff between queue_select sizes;
- the smoke variant produces the identical shape (slow lane: it runs the
  real benchmark at tiny sizes).
"""

import json
import os

import pytest

RESULTS_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                            "BENCH_engine.json")


def validate_bench_report(report: dict) -> None:
    assert isinstance(report.get("schema"), int) and report["schema"] >= 1
    assert isinstance(report.get("smoke"), bool)
    dev = report.get("device")
    assert isinstance(dev, dict) and dev.get("platform") and \
        dev.get("device_kind") and dev.get("count", 0) >= 1, \
        "report does not name the device that took its numbers"
    cases = report.get("cases")
    assert isinstance(cases, dict) and cases, "report carries no cases"
    for name, case in cases.items():
        assert isinstance(case, dict), name
        assert case.get("run_s", 0) > 0, f"{name}: run_s must be positive"
        if "n_events" in case:  # engine throughput case
            assert case["n_events"] > 0, name
            assert case.get("events_per_s", 0) > 0, name
            assert case.get("compile_s", -1) >= 0, name
            # events/s == n_events / run_s (same units: events, seconds)
            want = case["n_events"] / case["run_s"]
            assert abs(case["events_per_s"] - want) <= 1e-6 * max(want, 1), \
                f"{name}: events_per_s inconsistent with n_events/run_s"
        if "GBps" in case:      # kernel bandwidth case
            assert case["GBps"] > 0, name
            if report["schema"] >= 3:
                # compiled timing with auditable units: GB/s must derive
                # from the actual argument bytes, not a hardcoded width
                assert case.get("mode") == "compiled", \
                    f"{name}: kernel case must be timed compiled"
                assert case.get("tile", 0) > 0, name
                assert case.get("bytes", 0) > 0, name
                want = (case["bytes"] / case["run_s"]) / 1e9
                assert abs(case["GBps"] - want) <= 1e-6 * max(want, 1e-9), \
                    f"{name}: GBps inconsistent with bytes/run_s"
    if report["schema"] >= 2:
        t0, t1 = report["generated_unix"], report["finished_unix"]
        assert t0 > 1e9, "generated_unix is not an epoch timestamp"
        assert t1 >= t0, "timestamps must be monotonic"


def _load_artifact() -> dict:
    if not os.path.exists(RESULTS_JSON):
        pytest.skip("no committed BENCH_engine.json")
    with open(RESULTS_JSON) as f:
        return json.load(f)


def test_checked_in_artifact_parses():
    """The committed perf artifact stays machine-readable."""
    report = _load_artifact()
    validate_bench_report(report)
    # the perf trajectory needs the headline cases to exist under stable
    # names; renaming them silently orphans every historical comparison
    full_run_cases = {"nodeps_fcfs", "nodeps_backfill", "moldable_backfill",
                      "galactic8k_backfill", "trace_replay",
                      "queue_select_N65536", "queue_select_N1048576"}
    smoke_cases = {"nodeps_fcfs", "nodeps_backfill", "galactic_smoke_fcfs",
                   "moldable_backfill", "trace_replay", "queue_select_N65536"}
    have = set(report["cases"])
    assert (full_run_cases <= have) or (smoke_cases <= have), sorted(have)
    # the malleable width-choice case (DESIGN.md §17) carries its static
    # dur-table width so trajectory tooling can match like against like
    assert report["cases"]["moldable_backfill"].get("n_widths", 0) >= 2


def test_checked_in_artifact_is_schema3_compiled():
    """ISSUE 8 regression gate: the committed artifact must be schema >= 3,
    i.e. queue_select timed on the compiled lowering with auditable units —
    an ``interpret_mode`` artifact can never be checked in again."""
    report = _load_artifact()
    assert report["schema"] >= 3
    ks = [c for n, c in report["cases"].items() if n.startswith("queue_select")]
    assert ks, "artifact lost its queue_select cases"
    for case in ks:
        assert case.get("mode") == "compiled"


@pytest.mark.slow
def test_checked_in_artifact_throughput_floors():
    """ISSUE 8/9 acceptance floors on the committed full-run artifact:

    - batched backfill (DESIGN.md §18) holds >= 1/3 of FCFS events/s on
      the 2k no-deps case;
    - compiled queue_select has no >10x GB/s cliff going 64k -> 1M;
    - streaming replay sustains >= 1000 jobs/s on a >= 200k-job archive
      with bounded window occupancy.
    """
    report = _load_artifact()
    if report.get("smoke"):
        pytest.skip("floors are pinned on the full-run artifact")
    cases = report["cases"]
    bf = cases["nodeps_backfill"]["events_per_s"]
    fcfs = cases["nodeps_fcfs"]["events_per_s"]
    assert bf >= fcfs / 3, (
        f"backfill {bf:.0f} ev/s fell below 1/3 of FCFS {fcfs:.0f} ev/s — "
        "the batched backfill pass regressed")
    small = cases["queue_select_N65536"]["GBps"]
    big = cases["queue_select_N1048576"]["GBps"]
    assert big >= small / 10, (
        f"queue_select GB/s cliff: {small:.2f} at 64k vs {big:.2f} at 1M")
    # ISSUE 9 floors: the streaming replay runner (DESIGN.md §19) holds
    # archive scale — >= 200k jobs at >= 1000 jobs/s with the active window
    # bounded by the configured W (no silent whole-trace materialization)
    tr = cases["trace_replay"]
    assert tr["n_jobs"] >= 200_000, tr["n_jobs"]
    assert tr["jobs_per_s"] >= 1000, (
        f"trace_replay fell to {tr['jobs_per_s']:.0f} jobs/s — the windowed "
        "runner regressed")
    assert tr["peak_live"] <= tr["window"], (
        f"peak_live {tr['peak_live']} exceeds window {tr['window']} — replay "
        "memory is no longer bounded")


@pytest.mark.slow
def test_smoke_run_emits_valid_schema(tmp_path):
    """`--smoke` produces the same artifact shape the full run does (CI
    uploads it), validated end-to-end."""
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.des_throughput import run_bench

    report = run_bench(str(tmp_path), smoke=True)
    validate_bench_report(report)
    assert report["smoke"] is True
    assert report["schema"] >= 3
    with open(tmp_path / "BENCH_engine.json") as f:
        on_disk = json.load(f)
    validate_bench_report(on_disk)
    assert on_disk["cases"].keys() == report["cases"].keys()


# -- what-if service benchmark (ISSUE 10) -----------------------------------

WHATIF_JSON = os.path.join(os.path.dirname(__file__), "..", "results",
                           "fig_whatif.json")

WHATIF_FAMILIES = ("placement", "capacity", "reliability")


def validate_whatif_report(report: dict) -> None:
    """The cold/warm amortization contract, pinned on the artifact:
    every family carries both paths, the cold path compiled at least
    once, the warm path compiled exactly ZERO times and was no slower
    than cold — a static-key regression that re-compiles per query can
    never check in a passing artifact."""
    validate_bench_report(report)
    assert report["generated_unix"] > 1e9
    assert report["finished_unix"] >= report["generated_unix"]
    for family in WHATIF_FAMILIES:
        cold = report["cases"][f"{family}_cold"]
        warm = report["cases"][f"{family}_warm"]
        assert cold["compiles"] >= 1, family
        assert warm["compiles"] == 0, (
            f"{family}: warm queries recompiled — the persistent "
            "executable cache regressed")
        assert warm["hits"] >= 1, family
        assert warm["run_s"] <= cold["run_s"], (
            f"{family}: warm {warm['run_s']:.3f}s slower than cold "
            f"{cold['run_s']:.3f}s")
        assert warm["n_queries"] == cold["n_queries"] > 0, family


def test_checked_in_whatif_artifact():
    if not os.path.exists(WHATIF_JSON):
        pytest.skip("no committed fig_whatif.json")
    with open(WHATIF_JSON) as f:
        report = json.load(f)
    validate_whatif_report(report)


@pytest.mark.slow
def test_whatif_smoke_run_emits_valid_schema(tmp_path):
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.fig_whatif import _run

    _run(smoke=True, outdir=str(tmp_path))
    with open(tmp_path / "fig_whatif.json") as f:
        on_disk = json.load(f)
    validate_whatif_report(on_disk)
    assert on_disk["smoke"] is True
