"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows; artifacts land in results/.

    PYTHONPATH=src python -m benchmarks.run            # all
    PYTHONPATH=src python -m benchmarks.run fig5       # substring filter
    PYTHONPATH=src python -m benchmarks.run --smoke    # CI dry pass: run the
                                                       # tiny smoke() variant
                                                       # of benches that have
                                                       # one, skip the rest
"""

from __future__ import annotations

import sys
import time
import traceback

from benchmarks import (
    des_throughput, fig3_occupancy, fig4_policies, fig4_wait, fig5_scaling,
    fig6_workflow_scaling, fig7_workflow_wait, fig_alloc, fig_malleable,
    fig_reliability, fig_serving, fig_whatif, fig_workflow_cluster,
    roofline_table,
)
from repro.compile_cache import enable_compile_cache

BENCHES = [
    ("fig3_occupancy", fig3_occupancy),
    ("fig4_wait", fig4_wait),
    ("fig4_policies", fig4_policies),
    ("fig5_scaling", fig5_scaling),
    ("fig6_workflow_scaling", fig6_workflow_scaling),
    ("fig7_workflow_wait", fig7_workflow_wait),
    ("fig_workflow_cluster", fig_workflow_cluster),
    ("fig_alloc", fig_alloc),
    ("fig_reliability", fig_reliability),
    ("fig_serving", fig_serving),
    ("fig_malleable", fig_malleable),
    ("fig_whatif", fig_whatif),
    ("des_throughput", des_throughput),
    ("roofline_table", roofline_table),
]


def main() -> int:
    args = sys.argv[1:]
    smoke = "--smoke" in args
    args = [a for a in args if a != "--smoke"]
    pattern = args[0] if args else ""
    enable_compile_cache()
    print("name,us_per_call,derived")
    failed = []
    for name, mod in BENCHES:
        if pattern and pattern not in name:
            continue
        fn = getattr(mod, "smoke", None) if smoke else mod.main
        if fn is None:
            print(f"# {name} skipped (no smoke variant)", flush=True)
            continue
        t0 = time.time()
        try:
            fn()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:
            failed.append(name)
            traceback.print_exc()
            print(f"# {name} FAILED: {e}", flush=True)
    if failed:
        print(f"# FAILED benches: {failed}")
        return 1
    print("# all benches passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
