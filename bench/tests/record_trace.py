#!/usr/bin/env python3
"""Record ``data/trace_obs_v5e.json`` on a TPU v5e: one 64-job ``run()``
and one 4-lane sweep bucket under the profiler, reduced to the plain form
of ``lib.trace.extract`` with the keys ``lib.program_trace.extract`` adds
(``program_spans`` and ``op_scopes``).

    python3 bench/tests/record_trace.py [--xplane-dir DIR]
    python3 bench/tests/record_trace.py --xplane FILE

Each question runs once untraced (its compile) and once traced, inside the
benchmark's ``question.*`` spans, through the benchmark's own entries.
``--xplane-dir`` keeps the raw ``.xplane.pb`` there as well; ``--xplane``
reduces such a file, recorded earlier, on any machine.  The fixture keeps
operation names without their instruction text (``%while.81``), which is
all the reduction reads of them.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import shutil
import sys
import tempfile

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

from lib import harness, program_trace, trace  # noqa: E402

OUT = os.path.join(BENCH, "tests", "data", "trace_obs_v5e.json")
SEED = 3


def small_entries(workdir: str):
    """The run entry at 64 jobs and the sweep entry at 4 lanes of 16 jobs
    on a 64-node dragonfly with failures."""
    program = harness.import_program()
    sdsc = harness.load_json("configs", "sdsc_sp2_128")
    run_trf = dict(harness.load_json("traffic", "run8k"), jobs=64)
    fly = copy.deepcopy(harness.load_json("configs", "dragonfly_1024"))
    fly["machine"] = {"nodes": 64,
                      "topology": {"kind": "dragonfly", "shape": [4, 16]}}
    fly["workload"].update(total_nodes=64, node_pow2_max=6)
    fly["failures"].update(mtbf=4e5, horizon=1 << 20)
    sweep_trf = copy.deepcopy(harness.load_json("traffic", "sweep48"))
    sweep_trf.update(jobs=16, backlogs=1)
    sweep_trf["axes"] = {"policy": ["fcfs", "backfill"],
                         "alloc": ["simple", "topo"],
                         "failures.mtbf": [4e5]}
    return [harness.entry_class("run")(program, sdsc, run_trf, SEED, workdir),
            harness.entry_class("sweep")(program, fly, sweep_trf, SEED,
                                         workdir)]


def write_fixture(xplane: str) -> int:
    import jax

    tr = trace.extract(xplane)
    tr.update(program_trace.extract(xplane, tr))
    for lines in tr["devices"].values():
        lines["ops"] = [[trace.op_name(n), s, d] for n, s, d in lines["ops"]]
    tr["recorded"] = (f"TPU v5e, jax {jax.__version__}: "
                      "bench/tests/record_trace.py, one 64-job run() then "
                      "one 4-lane sweep bucket")
    with open(OUT, "w", encoding="utf-8") as f:
        json.dump(tr, f, separators=(",", ":"))
        f.write("\n")
    return os.path.getsize(OUT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--xplane-dir", default=None)
    ap.add_argument("--xplane", default=None)
    args = ap.parse_args(argv)
    if args.xplane:
        print(json.dumps({"out": OUT, "bytes": write_fixture(args.xplane)}))
        return 0
    import jax

    if jax.devices()[0].platform != "tpu":
        print("record_trace: needs a TPU", file=sys.stderr)
        return 2
    work = tempfile.mkdtemp(prefix="record_trace_")
    entries = small_entries(work)
    for e in entries:
        e.warm_up()
        inp = e.prepare(0)
        e.collect(0, inp, e.call(inp))
    tdir = os.path.join(work, "trace")
    trace.start(tdir)
    for e in entries:
        with jax.profiler.TraceAnnotation("question.prepare"):
            inp = e.prepare(0)
        with jax.profiler.TraceAnnotation("question.call"):
            out = e.call(inp)
        with jax.profiler.TraceAnnotation("question.collect"):
            e.collect(0, inp, out)
    trace.stop()
    path = trace.find_xplane(tdir)
    if args.xplane_dir:
        os.makedirs(args.xplane_dir, exist_ok=True)
        shutil.copy(path, os.path.join(args.xplane_dir, "trace.xplane.pb"))
    size = write_fixture(path)
    shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"out": OUT, "bytes": size}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
