"""The program's own spans and named scopes in a profiler trace.

:func:`extract` adds two keys to the plain form of ``lib.trace.extract``:

    {"program_spans": [[name, start_ns, dur_ns, thread, args], ...],
     "op_scopes": {program: {op: op_name path}}}

``program_spans`` are the program's host spans (``repro.obs.span``: names
``repro.*``), each with the host line it sits on (one per thread) and its
arguments.  ``op_scopes`` gives each device operation's ``op_name`` path
(``jit(_simulate_jit)/while/body/schedule/...``), keyed by the base name
of the program whose execution holds the operation in time and by the
operation's name; the engine's named scopes (``SCOPES``) are read from
it.  The readers of ``service_host_ms_p50``, ``host_idle_ms.*``,
``scope_us_per_event.*`` and ``scope_ms_per_iter.*`` work on those keys,
and :func:`idle_gaps` names each long idle gap by the program span over
it as well.  With a trace of a program that has no span or scope, every
reader reads ``None``.

The TPU's operation events carry no ``op_name`` stat.  The trace's
``/host:metadata`` plane holds one event metadata per program, named as
its executions are on the ``XLA Modules`` line, with its ``HloProto`` in a
bytes stat; :func:`hlo_op_names` reads the paths from there, with a
reader of the protobuf wire format that decodes only the fields on the
way.  The profiler's clock is not the host's ``time`` clock: program
spans are matched with device events inside the trace only.
"""

from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Tuple

import numpy as np

from lib import trace

PROGRAM_PREFIX = "repro."
# the engine's named scopes (repro.core.engine); an operation belongs to
# the innermost of them on its op_name path
SCOPES = ("advance", "failures", "schedule", "place", "log")

# field numbers of xplane.proto and hlo.proto on the way to op_name
_XSPACE_PLANES = 1
_XPLANE_NAME, _XPLANE_EVENT_METADATA = 2, 4
_MAP_VALUE = 2
_XEVENT_METADATA_NAME, _XEVENT_METADATA_STATS = 2, 5
_XSTAT_BYTES = 6
_HLO_PROTO_MODULE = 1
_HLO_MODULE_COMPUTATIONS = 3
_HLO_COMPUTATION_INSTRUCTIONS = 2
_HLO_INSTRUCTION_NAME, _HLO_INSTRUCTION_METADATA = 1, 7
_OP_METADATA_OP_NAME = 2


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, wanted: int):
    """The length-delimited fields numbered ``wanted`` of one message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            size, i = _varint(buf, i)
            if key >> 3 == wanted:
                yield buf[i:i + size]
            i += size
        else:
            raise ValueError(f"protobuf wire type {wire} in a trace")


def _text(buf, field: int) -> Optional[str]:
    for v in _fields(buf, field):
        return bytes(v).decode("utf-8", "replace")
    return None


def hlo_op_names(path: str) -> Dict[str, Dict[str, str]]:
    """``{module name: {"%<instruction>": op_name path}}`` from the HLO
    that the trace's ``/host:metadata`` plane carries."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for plane in _fields(buf, _XSPACE_PLANES):
        if _text(plane, _XPLANE_NAME) != "/host:metadata":
            continue
        for entry in _fields(plane, _XPLANE_EVENT_METADATA):
            for meta in _fields(entry, _MAP_VALUE):
                module = _text(meta, _XEVENT_METADATA_NAME)
                for stat in _fields(meta, _XEVENT_METADATA_STATS):
                    for proto in _fields(stat, _XSTAT_BYTES):
                        out[module] = _instruction_op_names(proto)
    return out


def _instruction_op_names(hlo_proto) -> Dict[str, str]:
    names: Dict[str, str] = {}
    for module in _fields(hlo_proto, _HLO_PROTO_MODULE):
        for comp in _fields(module, _HLO_MODULE_COMPUTATIONS):
            for ins in _fields(comp, _HLO_COMPUTATION_INSTRUCTIONS):
                for meta in _fields(ins, _HLO_INSTRUCTION_METADATA):
                    path = _text(meta, _OP_METADATA_OP_NAME)
                    if path:
                        names["%" + _text(ins, _HLO_INSTRUCTION_NAME)] = path
    return names


def _op_scopes(devices: dict, hlo: Dict[str, Dict[str, str]]
               ) -> Dict[str, Dict[str, str]]:
    out: Dict[str, Dict[str, str]] = {}
    for lines in devices.values():
        mods = sorted((s, s + d, n) for n, s, d in lines["modules"])
        starts = [m[0] for m in mods]
        seen = set()
        for name, start, _ in lines["ops"]:
            i = bisect.bisect_right(starts, start) - 1
            if i < 0 or start > mods[i][1] or (i, name) in seen:
                continue
            seen.add((i, name))
            op = trace.op_name(name)
            path_ = hlo.get(mods[i][2], {}).get(op)
            if path_ is not None:
                out.setdefault(trace.module_base(mods[i][2]), {}).setdefault(
                    op, path_)
    return out


def extract(path: str, tr: dict) -> dict:
    """``program_spans`` and ``op_scopes`` of one ``.xplane.pb``, whose
    ``lib.trace.extract`` form is ``tr`` (module docstring)."""
    from jax.profiler import ProfileData

    spans: List[list] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans.extend([e.name, e.start_ns, e.duration_ns,
                              f"{line.name}#{i}", dict(e.stats)]
                             for e in line.events
                             if e.name.startswith(PROGRAM_PREFIX))
    return {"program_spans": spans,
            "op_scopes": _op_scopes(tr["devices"], hlo_op_names(path))}


# ---------------------------------------------------------------------------
# what the readers read
# ---------------------------------------------------------------------------


def _window(tr: dict) -> Tuple[float, float]:
    """First benchmark span's start to the last one's end, in ns (the
    window of ``lib.trace.reduce``)."""
    spans = tr["spans"]
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def idle_gaps(tr: dict, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of ``lib.trace.reduce``, the same
    seconds in the same order, each named by the innermost benchmark span
    over it and then by the innermost program span over it, where one is
    (``question.call/repro.sweep.prepare``)."""
    lo, hi = _window(tr)
    gaps = []
    for lines in tr["devices"].values():
        u = trace._union(lines["ops"] or lines["modules"], lo, hi)
        for a, b in np.r_[lo, u.ravel(), hi].reshape(-1, 2):
            if b > a:
                gaps.append((float(b - a), (a + b) / 2))
    gaps.sort(key=lambda g: -g[0])
    out = []
    for d, t in gaps[:n]:
        name = trace._covering_span(tr["spans"], t)
        inner = _innermost_span(tr.get("program_spans") or [], t)
        out.append([name if inner is None else f"{name}/{inner}", d * 1e-9])
    return out


def _innermost_span(spans, t: float) -> Optional[str]:
    best, best_len = None, None
    for name, s, d, _, _ in spans:
        if s <= t <= s + d and (best_len is None or d < best_len):
            best, best_len = name, d
    return best


# results derived from the last trace a reader was given: every reader of
# one run asks for the same union and the same scope split of that trace
_LAST: Dict[str, tuple] = {}


def _per_trace(tr: dict, key: str, fn):
    last = _LAST.get(key)
    if last is None or last[0] is not tr:
        last = _LAST[key] = (tr, fn())
    return last[1]


def _busy_unions(tr: dict) -> list:
    """Per device: the merged busy intervals and the busy time before
    each (``lib.trace._union`` of its operations)."""
    out = []
    for lines in tr["devices"].values():
        u = trace._union(lines["ops"] or lines["modules"], -np.inf, np.inf)
        lengths = u[:, 1] - u[:, 0]
        out.append((u[:, 0], u[:, 1], np.r_[0.0, np.cumsum(lengths)]))
    return out


def _busy_before(starts, ends, before, t: np.ndarray) -> np.ndarray:
    """Busy ns of one device's merged intervals before each instant."""
    k = np.searchsorted(starts, t, side="right") - 1
    kk = np.maximum(k, 0)
    inside = np.clip(t - starts[kk], 0.0, ends[kk] - starts[kk])
    return np.where(k >= 0, before[kk] + inside, 0.0)


def busy_seconds(tr: dict, intervals) -> np.ndarray:
    """Device busy seconds inside each ``(start_ns, end_ns)`` host
    interval, averaged over the devices: ``lib.trace.busy_within`` of
    each, from one union per device however many intervals there are."""
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    unions = _per_trace(tr, "busy", lambda: _busy_unions(tr))
    total = np.zeros(len(iv))
    for starts, ends, before in unions:
        if len(starts):
            total += (_busy_before(starts, ends, before, iv[:, 1])
                      - _busy_before(starts, ends, before, iv[:, 0]))
    return total / max(len(unions), 1) * 1e-9


def idle_seconds(tr: dict, intervals) -> float:
    """Device idle seconds summed over host intervals."""
    iv = np.asarray(intervals, dtype=np.float64).reshape(-1, 2)
    return float(((iv[:, 1] - iv[:, 0]) * 1e-9
                  - busy_seconds(tr, iv)).sum())


def questions(tr: dict) -> int:
    """Traced questions: the benchmark's ``question.call`` spans."""
    return sum(1 for name, _, _ in tr["spans"] if name == "question.call")


def self_intervals(program_spans, prefixes: Tuple[str, ...]
                   ) -> List[Tuple[float, float]]:
    """The self time of every program span whose name starts with one of
    ``prefixes``: the span less the program spans of the same thread
    inside it, as ``(start_ns, end_ns)`` pieces."""
    by_thread: Dict[str, list] = {}
    for name, s, d, thread, _ in program_spans:
        by_thread.setdefault(thread, []).append((s, s + d, name))
    out = []
    for evs in by_thread.values():
        for s, e, name in evs:
            if not name.startswith(prefixes):
                continue
            kids = sorted((cs, ce) for cs, ce, _ in evs
                          if s <= cs and ce <= e and (cs, ce) != (s, e))
            t = s
            for cs, ce in kids:
                if cs > t:
                    out.append((t, cs))
                t = max(t, ce)
            if e > t:
                out.append((t, e))
    return out


def innermost_scope(path: str) -> Optional[str]:
    """The innermost of ``SCOPES`` on an ``op_name`` path (its last part is
    the operation itself, so a ``log`` primitive is no scope)."""
    for part in reversed(path.split("/")[:-1]):
        if part in SCOPES:
            return part
    return None


def scope_seconds(tr: dict, program: str) -> Optional[Dict[str, float]]:
    """Device seconds of the leaf operations of ``program`` in the window,
    by innermost scope (``""`` for none), averaged over the devices that
    ran anything; ``None`` where no operation of it carries a scope.

    A leaf operation's interval holds no other operation of its line, so
    the ``while`` and ``cond`` operations around a loop body drop out and
    no time counts twice.  An operation belongs to the program whose
    execution holds it in time."""
    return _per_trace(tr, "scopes." + program,
                      lambda: _scope_split(tr, program))


def _scope_split(tr: dict, program: str) -> Optional[Dict[str, float]]:
    paths = (tr.get("op_scopes") or {}).get(program)
    if not paths:
        return None
    scope_of = {op: innermost_scope(p) or "" for op, p in paths.items()}
    if not any(scope_of.values()):
        return None
    lo, hi = _window(tr)
    out: Dict[str, float] = {}
    n_dev = 0
    for lines in tr["devices"].values():
        ops = lines["ops"]
        if not ops:
            continue
        n_dev += 1
        mods = sorted((s, s + d) for n, s, d in lines["modules"]
                      if trace.module_base(n) == program)
        if not mods:
            continue
        m_start = np.asarray([m[0] for m in mods])
        m_end = np.asarray([m[1] for m in mods])
        start = np.asarray([e[1] for e in ops], dtype=np.float64)
        end = start + np.asarray([e[2] for e in ops], dtype=np.float64)
        order = np.lexsort((-end, start))
        start, end = start[order], end[order]
        leaf = np.r_[start[1:] >= end[:-1], True]
        i = np.searchsorted(m_start, start, side="right") - 1
        mine = ((i >= 0) & (end <= m_end[np.maximum(i, 0)])
                & (start >= lo) & (start <= hi) & leaf)
        for j in np.nonzero(mine)[0]:
            scope = scope_of.get(trace.op_name(ops[order[j]][0]), "")
            out[scope] = out.get(scope, 0.0) + (end[j] - start[j]) * 1e-9
    n_dev = max(n_dev, 1)
    return {k: v / n_dev for k, v in out.items()}
