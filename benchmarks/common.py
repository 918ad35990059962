"""Shared benchmark utilities: timing + CSV emission."""

from __future__ import annotations

import time
from typing import Callable, Iterable

import jax
import numpy as np


def time_call(fn: Callable, *, warmup: int = 1, iters: int = 3) -> float:
    """Median wall seconds per call (after warmup, block_until_ready-safe)."""
    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append(time.perf_counter() - t0)
    return float(np.median(times))


def device_info() -> dict:
    """The device a benchmark's numbers come from, as JAX reports it."""
    dev = jax.devices()[0]
    return {"platform": dev.platform, "device_kind": dev.device_kind,
            "count": len(jax.devices())}


def emit(name: str, seconds: float, derived: str = "") -> str:
    """`name,us_per_call,derived` CSV row (scaffold contract)."""
    row = f"{name},{seconds * 1e6:.1f},{derived}"
    print(row, flush=True)
    return row


def series_to_csv(path: str, header: Iterable[str], rows):
    import csv
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(list(header))
        for r in rows:
            w.writerow(list(r))


def sweep_to_csv(path: str, grid, fields: Iterable[str]):
    """Write a ``repro.api.SweepResult`` to CSV: one row per grid point,
    axis values first, then the requested ``Result.summary()`` fields."""
    axis_names = list(grid.axes)
    fields = list(fields)
    rows = [
        [summary[a] for a in axis_names] + [summary[f] for f in fields]
        for summary in grid.summaries()
    ]
    series_to_csv(path, axis_names + fields, rows)
    return rows
