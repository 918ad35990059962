"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness is driven by data.  The cell names a configuration, found as
``bench/configs/<config>.json``, and a traffic mix, found as
``bench/traffic/<traffic>.json``; the mix names the entry point it drives,
found as ``bench/lib/entries/<entry>.py``; each per-layer metric is read by
``bench/metrics/<metric>.py``, or by ``bench/metrics/<stem>.py`` for a
``<stem>.<variant>`` without a file of its own.  A new cell, mix or metric
is new files and new entries, never an edit here.

A run: refuse without a TPU (or with fewer chips than the cell asks for);
hand the entry the cell's chips; switch the persistent compile cache on;
warm up the cell's shapes (all of this is ``setup_s``); ask back-to-back
questions for ``--seconds``, the last one finishing past the mark (with
``--trace 1`` the first of them, for about ``TRACE_SECONDS``, under the
profiler); read the peak device memory of the fullest chip; drop the
program's state; compare a seeded sample of the answers with the plain
reference; print the result.
"""

from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
import types
from typing import Dict, List, Optional

from lib import meter as meter_mod
from lib import trace as trace_mod

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)
WORK_DIR = os.path.join(BENCH_DIR, ".work")
# --trace 1 profiles the window's first questions until this many seconds
# have passed (whole questions), then runs the rest of the window untraced
TRACE_SECONDS = 2.0


class BenchError(RuntimeError):
    """The run cannot be made as asked (no chip, unknown cell, bad file)."""


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def find(items: List[dict], name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise BenchError(f"no {what} named {name!r}")


def load_json(kind: str, name: str) -> dict:
    path = os.path.join(BENCH_DIR, kind, f"{name}.json")
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _load_module(path: str, label: str):
    if not os.path.isfile(path):
        raise BenchError(f"no file {os.path.relpath(path, ROOT)}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def entry_class(entry: str):
    path = os.path.join(BENCH_DIR, "lib", "entries", f"{entry}.py")
    return _load_module(path, f"bench_entry_{entry}").ENTRY


def metric_reader(name: str):
    """The reader of per-layer metric ``name``: ``metrics/<name>.py`` or,
    where that is missing and the name is ``<stem>.<variant>``,
    ``metrics/<stem>.py``, which reads the variant as ``view.variant``."""
    stem, _, variant = name.partition(".")
    path = os.path.join(BENCH_DIR, "metrics", f"{name}.py")
    if not os.path.isfile(path) and variant:
        path = os.path.join(BENCH_DIR, "metrics", f"{stem}.py")
    read = _load_module(path, "bench_metric_" + name.replace(".", "_")).read

    def reader(view):
        view.variant = variant
        return read(view)
    return reader


def cell_metrics(bench: dict, cell: str, section: str) -> List[dict]:
    """The metrics of ``section`` this cell reports: those that list it,
    and those without a list whose end-to-end metric the cell reports."""
    reported = {m["name"] for m in bench["end_to_end"]
                if cell in m.get("workloads", [cell])}
    out = []
    for m in bench[section]:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif section == "end_to_end" or m["moves"] in reported:
            out.append(m)
    return out


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


class RunView:
    """What a per-layer metric reader may read of one traced run."""

    def __init__(self, cell, records, counters, traced, reduced, raw_trace):
        self.cell = cell
        self.records = records      # every question of the window
        self.counters = counters    # over the whole window
        self.traced = traced        # over the questions the trace covers
        self.trace = reduced        # trace_mod.reduce()
        self.raw_trace = raw_trace  # trace_mod.extract()
        self.variant = ""           # set by metric_reader


def import_program():
    src = os.path.join(ROOT, "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    import repro.api as api
    from repro.compile_cache import enable_compile_cache

    return types.SimpleNamespace(api=api, enable_compile_cache=enable_compile_cache)


def device_info(chips: int) -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": min(len(devs), chips)}


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, t_start: float, device: dict,
             workdir: str = WORK_DIR, config: Optional[dict] = None,
             traffic: Optional[dict] = None, log=sys.stderr) -> dict:
    """One run of ``cell``; returns the result dict (the last line).
    ``config``/``traffic`` stand in for the cell's files (tests use them to
    run a cell at a small size)."""
    import jax

    config = config or load_json("configs", cell["config"])
    traffic = traffic or load_json("traffic", cell["traffic"])
    cls = entry_class(traffic["entry"])
    if cell["chips"] > 1 and not cls.SHARDS:
        raise BenchError(f"cell {cell['name']} asks for {cell['chips']} "
                         f"chips, but the {traffic['entry']} entry runs on "
                         f"one")
    program = import_program()
    cache_dir = program.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    meter = meter_mod.CompileMeter()
    os.makedirs(workdir, exist_ok=True)

    devices = jax.devices()[:cell["chips"]]
    entry = cls(program, config, traffic, seed, workdir, devices=devices)
    records: List[dict] = []
    failed = attempted = 0
    raw_trace = reduced = None
    n_traced = 0
    trace_dir = os.path.join(workdir, "trace")
    try:
        entry.warm_up()
        setup_s = time.perf_counter() - t_start
        before = meter.snapshot()
        shutil.rmtree(trace_dir, ignore_errors=True)
        tracing = trace
        if tracing:
            trace_mod.start(trace_dir)
        t0 = time.perf_counter()
        ends = []               # seconds into the window each question ended
        q = 0
        while True:
            attempted += 1
            try:
                with jax.profiler.TraceAnnotation("question.prepare"):
                    inp = entry.prepare(q)
                with jax.profiler.TraceAnnotation("question.call"):
                    out = entry.call(inp)
                with jax.profiler.TraceAnnotation("question.collect"):
                    records.append(entry.collect(q, inp, out))
            except Exception as e:  # noqa: BLE001 — counted, run goes on
                failed += 1
                print(f"question {q} failed: {type(e).__name__}: {e}",
                      file=log)
                if failed > 3 and not records:
                    break       # nothing answers: stop asking
            q += 1
            elapsed = time.perf_counter() - t0
            ends.append(elapsed)
            if tracing and elapsed >= TRACE_SECONDS:
                trace_mod.stop()
                tracing = False
                n_traced = len(records)
            if elapsed >= seconds:
                break
        window_s = time.perf_counter() - t0
        if tracing:
            trace_mod.stop()
            n_traced = len(records)
        in_window = meter_mod.CompileMeter.delta(before, meter.snapshot())
        mem = _peak_bytes(devices)
        if trace:
            path = trace_mod.find_xplane(trace_dir)
            if path is None:
                raise BenchError("the profiler wrote no trace")
            raw_trace = trace_mod.extract(path)
            shutil.rmtree(trace_dir, ignore_errors=True)
            reduced = trace_mod.reduce(raw_trace)
    finally:
        entry.close()
    e2e = entry.end_to_end(records, window_s) if records else {}
    counters = entry.counters(records) if records else {}
    print(json.dumps({"window": {
        "seconds": window_s, "questions": len(records), "ends_s": ends,
        "compiles_in_window": in_window, "compile_cache": cache_dir,
        "counters": counters}}), flush=True)
    gc.collect()

    t_check = time.perf_counter()
    limits, compared = entry.check(records) if records else ([], 0)
    check_s = time.perf_counter() - t_check

    metrics: Dict[str, dict] = {}
    if not trace:
        values = dict(e2e, setup_s=setup_s)
        for m in cell_metrics(bench, cell["name"], "end_to_end"):
            if m["name"] in values:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        view = RunView(cell, records, counters,
                       entry.counters(records[:n_traced]), reduced, raw_trace)
        for m in cell_metrics(bench, cell["name"], "per_layer"):
            value = metric_reader(m["name"])(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    ok = (bool(records) and failed == 0 and compared > 0
          and all(v <= lim for _, v, lim in limits))
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": dict(device,
                                                 memory_peak_bytes=mem)}
    if trace:
        result["device"]["busy_s"] = reduced["busy_s"]
        result["device"]["window_s"] = reduced["window_s"]
        result["breakdown"] = {"device_ops": reduced["device_ops"],
                               "idle_gaps": reduced["idle_gaps"]}
    result["limits"] = dict(
        {name: {"value": v, "limit": lim} for name, v, lim in limits},
        compared=compared, check_s=check_s)
    return result


def _peak_bytes(devices) -> Optional[int]:
    """The peak bytes in use on the fullest of ``devices``."""
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in devices]
    peaks = [int(p) for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the command
# ---------------------------------------------------------------------------


def parse_args(argv=None):
    ap = argparse.ArgumentParser(prog="bench/run.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")
    return args


def main(argv=None, t_start: Optional[float] = None) -> int:
    t_start = time.perf_counter() if t_start is None else t_start
    args = parse_args(argv)
    try:
        bench = load_benchmark()
        cell = find(bench["workloads"], args.workload, "workload")
        import jax

        devs = jax.devices()
        if devs[0].platform != "tpu":
            raise BenchError(f"no TPU: JAX found {devs[0].platform}")
        if len(devs) < cell["chips"]:
            raise BenchError(f"cell needs {cell['chips']} chips, JAX found "
                             f"{len(devs)}")
        result = run_cell(bench, cell, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), t_start=t_start,
                          device=device_info(cell["chips"]))
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, entry in result["limits"].items():
        if isinstance(entry, dict):
            print(f"check {name}: {entry['value']} (limit {entry['limit']})",
                  file=sys.stderr)
    print(f"check compared: {result['limits']['compared']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0
