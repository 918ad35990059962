"""From a profiler trace to device busy time, idle gaps and executable time.

The window is traced with ``jax.profiler.trace`` (Python tracer off), and
:func:`extract` reads the ``.xplane.pb`` it writes into a small plain form:

    {"devices": {plane: {"modules": [[name, start_ns, dur_ns], ...],
                         "ops": [[name, start_ns, dur_ns], ...]}},
     "spans": [[name, start_ns, dur_ns], ...]}

``modules`` are the executions of whole XLA programs (the device line
``XLA Modules``), ``ops`` the operations inside them (``XLA Ops``), and
``spans`` the benchmark's own host spans (``jax.profiler.TraceAnnotation``
around each step of a question).  :func:`reduce` works on that form only,
so it is tested on a recorded fixture without a chip.

The programs of the main path, by the name their jitted function gives the
XLA module (``jit_<function name>``):

- ``jit_fn``: the vmapped sweep bucket, ``repro.api.sweep._bucket_fn``;
- ``jit__simulate_jit``: the one-shot engine, ``repro.core.engine._simulate_jit``;
- ``jit_step``: one replay round, the ``step`` that
  ``repro.replay.runner.StreamingReplay._build_step`` wraps around
  ``repro.core.engine.simulate_window``.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional

import numpy as np

EXECUTABLES = {
    "sweep_bucket": "jit_fn",
    "engine_run": "jit__simulate_jit",
    "engine_replay": "jit_step",
}
SPANS = ("question.prepare", "question.call", "question.collect",
         "query.http")
_MODULE_LINE = "XLA Modules"
_OP_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)$")


def op_name(name: str) -> str:
    """``%while.81 = (s32[8192]...) while(...)`` -> ``%while.81``: an
    operation's HLO name without its instruction text."""
    return name.split(" = ", 1)[0]


def module_base(name: str) -> str:
    """``jit_fn(12)`` -> ``jit_fn``: the program's name without the id the
    runtime appends."""
    return _SUFFIX.sub("", name.strip())


def start(out_dir: str) -> None:
    """Start tracing host and device into ``out_dir`` (no Python function
    tracer).  The device side records every operation, about a million
    events per second of engine time on a TPU v5e, and the profiler drops
    what passes its buffer (a few million events): trace a few seconds."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def stop() -> None:
    import jax

    jax.profiler.stop_trace()


def find_xplane(out_dir: str) -> Optional[str]:
    paths = sorted(glob.glob(os.path.join(out_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def extract(path: str) -> dict:
    """The plain form of one ``.xplane.pb`` (module docstring)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, dict] = {}
    spans: List[list] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            lines = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {_MODULE_LINE: "modules", _OP_LINE: "ops"}.get(line.name)
                if key is not None:
                    lines[key].extend([e.name, e.start_ns, e.duration_ns]
                                      for e in line.events)
            if lines["modules"] or lines["ops"]:
                devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend([e.name, e.start_ns, e.duration_ns]
                             for e in line.events if e.name in SPANS)
    return {"devices": devices, "spans": spans}


def _union(events, lo: float, hi: float) -> np.ndarray:
    """Merged [start, end] intervals of ``events`` clipped to [lo, hi]."""
    if not events:
        return np.zeros((0, 2))
    a = np.asarray([[e[1], e[1] + e[2]] for e in events], dtype=np.float64)
    a = a[np.argsort(a[:, 0], kind="stable")]
    a[:, 0] = np.clip(a[:, 0], lo, hi)
    a[:, 1] = np.clip(a[:, 1], lo, hi)
    a = a[a[:, 1] > a[:, 0]]
    if len(a) == 0:
        return np.zeros((0, 2))
    ends = np.maximum.accumulate(a[:, 1])
    new = np.r_[True, a[1:, 0] > ends[:-1]]
    starts = a[new, 0]
    group = np.cumsum(new) - 1
    merged_end = np.zeros(len(starts))
    np.maximum.at(merged_end, group, ends)
    return np.stack([starts, merged_end], axis=1)


def _covering_span(spans, t: float) -> str:
    """The innermost benchmark span that holds instant ``t``."""
    best, best_len = "outside_spans", None
    for name, s, d in spans:
        if s <= t <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


def reduce(tr: dict) -> dict:
    """Busy and window seconds, per-program device seconds, the longest
    device operations and the longest idle gaps of one traced window.

    The window runs from the first benchmark span's start to the last
    one's end.  Busy time is the union of the intervals in which an
    operation ran on a device (``ops``, or ``modules`` where a trace has no
    operation line), averaged over the devices that ran anything."""
    spans = tr["spans"]
    if not spans:
        raise ValueError("trace holds no benchmark span")
    lo = min(s for _, s, _ in spans)
    hi = max(s + d for _, s, d in spans)
    busy, gaps = [], []
    per_module: Dict[str, float] = {}
    runs: Dict[str, int] = {}
    per_op: Dict[str, float] = {}
    for lines in tr["devices"].values():
        evs = lines["ops"] or lines["modules"]
        u = _union(evs, lo, hi)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0)
        edges = np.r_[lo, u.ravel(), hi].reshape(-1, 2)
        for a, b in edges:
            if b > a:
                gaps.append((float(b - a), _covering_span(spans, (a + b) / 2)))
        for name, s, d in lines["modules"]:
            if lo <= s <= hi:
                base = module_base(name)
                per_module[base] = per_module.get(base, 0.0) + d
                runs[base] = runs.get(base, 0) + 1
        short = op_name if lines["ops"] else module_base
        for name, s, d in evs:
            if lo <= s <= hi:
                name = short(name)
                per_op[name] = per_op.get(name, 0.0) + d
    n_dev = max(len(busy), 1)
    gaps.sort(key=lambda g: -g[0])
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev * 1e-9,
        "module_s": {k: v / n_dev * 1e-9 for k, v in per_module.items()},
        "module_runs": {k: v // n_dev for k, v in runs.items()},
        "device_ops": [[name, d / n_dev * 1e-9] for name, d in top],
        "idle_gaps": [[name, d * 1e-9] for d, name in gaps[:10]],
        "n_devices": len(busy),
    }


def busy_within(tr: dict, start_ns: float, end_ns: float) -> float:
    """Device busy seconds inside one host interval, averaged over the
    devices that ran anything."""
    out = []
    for lines in tr["devices"].values():
        u = _union(lines["ops"] or lines["modules"], start_ns, end_ns)
        out.append(float((u[:, 1] - u[:, 0]).sum()) if len(u) else 0.0)
    return (sum(out) / len(out)) * 1e-9 if out else 0.0
