"""The control of ``correct``: the plain reference with one stated
guarantee broken, put where the program's answers go.

Both configurations state EASY backfill: a backfilled job never delays the
reserved start of the job at the head of the queue.  The control drops that
reservation (any waiting job that fits may start), answers each question
of a cell with that schedule, and runs the cell's own check on those
answers.  The check has to come out as not correct: at least one of its
numbers over its limit.

    python3 bench/control.py --workload <cell> --seeds 11,12,13

prints, per seed, every number the cell's check compares beside its limit.
It runs on the host alone, at the cell's own sizes.
"""

from __future__ import annotations

from typing import List

from lib import harness


def control_readings(cell: dict, seed: int, questions: int = 1, *,
                     config=None, traffic=None) -> List[tuple]:
    """``[(name, value, limit), ...]`` of the check on ``questions`` control
    answers of ``cell`` for ``seed``."""
    config = config or harness.load_json("configs", cell["config"])
    traffic = traffic or harness.load_json("traffic", cell["traffic"])
    entry = harness.entry_class(traffic["entry"])(None, config, traffic, seed,
                                                  harness.WORK_DIR)
    records = [entry.control_record(q) for q in range(questions)]
    limits, compared = entry.check(records)
    return limits + [("compared", compared, None)]


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(prog="bench/control.py")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds, one reading each")
    ap.add_argument("--questions", type=int, default=4)
    args = ap.parse_args(argv)
    cell = harness.find(harness.load_benchmark()["workloads"], args.workload,
                        "workload")
    for seed in (int(s) for s in args.seeds.split(",")):
        readings = control_readings(cell, seed, args.questions)
        failed = any(lim is not None and v > lim for _, v, lim in readings)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_fails_check": failed,
                          "readings": {n: {"value": v, "limit": lim}
                                       for n, v, lim in readings}}),
              flush=True)
    return 0
