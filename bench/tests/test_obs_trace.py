"""The program's own spans and named scopes in a trace
(``lib.program_trace``): the two keys its ``extract`` adds, the named idle
gaps, and the readers of ``service_host_ms_p50``, ``host_idle_ms.*``,
``scope_us_per_event.*`` and ``scope_ms_per_iter.*``; on hand-built traces
and on one recorded on a TPU v5e (``data/trace_obs_v5e.json``,
``tests/record_trace.py``)."""

import copy
import json
import os
import types

import numpy as np
import pytest

from lib import harness, program_trace, trace

NS = 1_000_000  # one millisecond in ns
SIM = "jit__simulate_jit"
PATH = "jit(_simulate_jit)/while/body/"

# one question.call of 20 ms on the client's line; on the server's line a
# request span (1-19 ms) holds decode (1-2), dispatch (2-4), a summary
# (4-18) with its fetch (5-17) and encode (18-19).  The device runs the
# engine from 3 to 12 ms: a while op (3-12) around three leaves (advance
# 3-5, schedule 5-9, place under schedule 9-10) and an unscoped leaf
# (10-11); 11-12 is inside the while op only.
HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [[f"{SIM}(5)", 3 * NS, 9 * NS]],
        "ops": [["%while.1", 3 * NS, 9 * NS],
                ["%fusion.1", 3 * NS, 2 * NS],
                ["%fusion.2", 5 * NS, 4 * NS],
                ["%fusion.3", 9 * NS, 1 * NS],
                ["%copy.4", 10 * NS, 1 * NS]]}},
    "spans": [["question.call", 0, 20 * NS], ["query.http", 0, 20 * NS]],
    "program_spans": [
        ["repro.service.request", 1 * NS, 18 * NS, "python#1",
         {"id": 0, "kind": "capacity"}],
        ["repro.service.decode", 1 * NS, 1 * NS, "python#1", {}],
        ["repro.sweep.dispatch", 2 * NS, 2 * NS, "python#1",
         {"lanes": 4, "cold": 0}],
        ["repro.result.summary", 4 * NS, 14 * NS, "python#1", {}],
        ["repro.result.fetch", 5 * NS, 12 * NS, "python#1", {}],
        ["repro.service.encode", 18 * NS, 1 * NS, "python#1", {}],
    ],
    "op_scopes": {SIM: {"%while.1": "jit(_simulate_jit)/while",
                        "%fusion.1": PATH + "advance/reduce_min",
                        "%fusion.2": PATH + "schedule/cumsum",
                        "%fusion.3": PATH + "schedule/place/log",
                        "%copy.4": "jit(_simulate_jit)/copy"}},
}


def view(tr, variant="", **traced):
    return types.SimpleNamespace(raw_trace=tr, variant=variant,
                                 traced=traced)


def read(name, tr, **traced):
    return harness.metric_reader(name)(view(tr, **traced))


# ---------------------------------------------------------------------------
# reduction
# ---------------------------------------------------------------------------


def test_idle_gaps_name_the_program_span_over_them():
    # idle 0-3 ms (its middle in decode) and 12-20 ms (in the fetch)
    assert program_trace.idle_gaps(HAND) == [
        ["question.call/repro.result.fetch", pytest.approx(0.008)],
        ["question.call/repro.service.decode", pytest.approx(0.003)]]
    # a gap no program span covers keeps the benchmark span's name
    tr = dict(HAND, program_spans=HAND["program_spans"][4:5])
    assert [n for n, _ in program_trace.idle_gaps(tr)] == [
        "question.call/repro.result.fetch", "question.call"]


FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.json")
RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "trace_obs_v5e.json")


@pytest.mark.parametrize("path", [None, FIXTURE, RECORDED])
def test_named_gaps_are_the_reductions_gaps(path):
    """The same seconds in the same order as ``lib.trace.reduce``, each
    name led by the name ``reduce`` gives it."""
    if path is None:
        tr = HAND
    else:
        with open(path) as f:
            tr = json.load(f)
    named = program_trace.idle_gaps(tr)
    plain = trace.reduce(tr)["idle_gaps"]
    assert [s for _, s in named] == [s for _, s in plain]
    for (a, _), (b, _) in zip(named, plain):
        assert a == b or a.startswith(b + "/repro.")


def test_self_time_leaves_out_the_children_of_its_thread():
    spans = HAND["program_spans"] + [
        ["repro.service.decode", 6 * NS, 1 * NS, "python#2", {}]]
    got = program_trace.self_intervals(spans, ("repro.service.",))
    # request 1-19 less decode, dispatch, summary, encode: nothing left;
    # decode 1-2, encode 18-19, the other thread's decode 6-7
    assert sorted(got) == [(1 * NS, 2 * NS), (6 * NS, 7 * NS),
                           (18 * NS, 19 * NS)]
    got = program_trace.self_intervals(spans, ("repro.result.",))
    assert sorted(got) == [(4 * NS, 5 * NS), (5 * NS, 17 * NS),
                           (17 * NS, 18 * NS)]


@pytest.mark.parametrize("path", [None, FIXTURE, RECORDED])
def test_busy_seconds_is_busy_within_of_each_interval(path):
    if path is None:
        tr = HAND
    else:
        with open(path) as f:
            tr = json.load(f)
    lo, hi = program_trace._window(tr)
    edges = np.linspace(lo - 1e6, hi + 1e6, 41)
    intervals = list(zip(edges[:-1], edges[1:])) + [(lo, hi), (hi, hi)]
    got = program_trace.busy_seconds(tr, intervals)
    want = [trace.busy_within(tr, s, e) for s, e in intervals]
    assert got == pytest.approx(want, abs=1e-12)
    idle = sum(e - s for s, e in intervals) * 1e-9 - sum(want)
    assert program_trace.idle_seconds(tr, intervals) == pytest.approx(idle)


@pytest.mark.parametrize("path,scope", [
    (PATH + "advance/reduce_min", "advance"),
    (PATH + "schedule/place/add", "place"),
    (PATH + "advance/failures/cond/scatter", "failures"),
    (PATH + "advance/log", "advance"),          # the log primitive
    (PATH + "log/dynamic_update_slice", "log"),
    ("jit(_simulate_jit)/while", None),
])
def test_innermost_scope(path, scope):
    assert program_trace.innermost_scope(path) == scope


def test_scope_time_counts_leaf_operations_only():
    by = program_trace.scope_seconds(HAND, SIM)
    assert by == pytest.approx({"advance": 0.002, "schedule": 0.004,
                                "place": 0.001, "": 0.001})
    assert program_trace.scope_seconds(HAND, "jit_fn") is None


def test_scope_time_is_none_without_scopes():
    tr = copy.deepcopy(HAND)
    tr["op_scopes"][SIM] = {op: "jit(_simulate_jit)/while/body/add"
                            for op in tr["op_scopes"][SIM]}
    assert program_trace.scope_seconds(tr, SIM) is None


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------


def test_service_host_ms_is_the_request_less_device_time():
    # request 1-19 ms, the device busy 3-12 ms inside it
    assert read("service_host_ms_p50", HAND) == pytest.approx(9.0)


def test_host_idle_ms_reads_the_self_time_of_a_layer():
    # service: decode 1-2 (idle), encode 18-19 (idle); one question
    assert read("host_idle_ms.service", HAND) == pytest.approx(2.0)
    # api: dispatch 2-4 (idle 2-3), summary self 4-5 and 17-18 (idle
    # 17-18), fetch 5-17 (idle 12-17)
    assert read("host_idle_ms.api.query", HAND) == pytest.approx(7.0)
    assert read("host_idle_ms.api.sweep", HAND) == pytest.approx(7.0)


def test_scope_readers_divide_by_the_traced_work():
    assert read("scope_us_per_event.advance", HAND,
                engine_events=100) == pytest.approx(20.0)
    assert read("scope_us_per_event.schedule", HAND,
                engine_events=100) == pytest.approx(40.0)
    tr = copy.deepcopy(HAND)
    tr["devices"]["/device:TPU:0"]["modules"][0][0] = "jit_fn(9)"
    tr["op_scopes"] = {"jit_fn": tr["op_scopes"][SIM]}
    assert read("scope_ms_per_iter.place", tr,
                lockstep_iters=4) == pytest.approx(0.25)
    assert read("scope_ms_per_iter.failures", tr, lockstep_iters=4) is None


NEW = ("service_host_ms_p50", "host_idle_ms.service",
       "host_idle_ms.api.query", "host_idle_ms.api.sweep",
       "scope_us_per_event.advance", "scope_us_per_event.schedule",
       "scope_ms_per_iter.advance", "scope_ms_per_iter.failures",
       "scope_ms_per_iter.schedule", "scope_ms_per_iter.place",
       "scope_ms_per_iter.log")


@pytest.mark.parametrize("name", NEW)
def test_a_program_without_spans_or_scopes_reads_nothing(name):
    """A trace of a program that has no span or scope (the form
    ``extract`` gives it) reads ``None``, and no reader raises."""
    with open(FIXTURE) as f:
        tr = json.load(f)
    tr.update(program_spans=[], op_scopes={})
    assert read(name, tr, engine_events=1e4, lockstep_iters=100) is None
    assert read(name, None) is None


def test_the_readers_wait_outside_the_benchmark():
    """Their metrics need ``lib.trace.extract`` to add the keys of
    ``lib.program_trace.extract`` (PERF.md, Open questions): until then no
    cell lists them."""
    bench = harness.load_benchmark()
    assert not set(NEW) & {m["name"] for m in bench["per_layer"]}
    for name in NEW:
        assert callable(harness.metric_reader(name))


# ---------------------------------------------------------------------------
# the recorded chip trace
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(RECORDED) as f:
        return json.load(f)


def test_recorded_trace_shares_one_clock(recorded):
    """Each engine and bucket execution starts after its question's
    dispatch span starts and ends before its last fetch ends."""
    slack = 50_000
    calls = [(s, s + d) for n, s, d in recorded["spans"]
             if n == "question.call"]
    checked = 0
    for name, s, d in recorded["devices"]["/device:TPU:0"]["modules"]:
        if trace.module_base(name) not in (SIM, "jit_fn"):
            continue
        qa, qb = next(c for c in calls if c[0] <= s <= c[1])
        mine = [p for p in recorded["program_spans"] if qa <= p[1] <= qb]
        dispatch = min(p[1] for p in mine if p[0].endswith(".dispatch"))
        fetched = max(p[1] + p[2] for p in mine
                      if p[0] == "repro.result.fetch")
        assert dispatch - slack <= s
        assert s + d <= fetched + slack
        checked += 1
    assert checked == 2


def test_recorded_trace_holds_every_span_and_scope(recorded):
    names = {p[0] for p in recorded["program_spans"]}
    assert {"repro.run.prepare", "repro.run.dispatch",
            "repro.sweep.prepare", "repro.sweep.dispatch",
            "repro.sweep.split", "repro.result.summary",
            "repro.result.fetch"} <= names
    disp = next(p for p in recorded["program_spans"]
                if p[0] == "repro.sweep.dispatch")
    assert disp[4] == {"lanes": 4, "cold": 0}
    one = program_trace.scope_seconds(recorded, SIM)
    many = program_trace.scope_seconds(recorded, "jit_fn")
    assert set(one) == {"", "advance", "schedule"}
    assert set(many) == {"", "advance", "failures", "schedule", "place",
                         "log"}
    r = trace.reduce(recorded)
    for prog, by in ((SIM, one), ("jit_fn", many)):
        assert sum(by.values()) <= r["module_s"][prog]
    assert sum(many.values()) >= 0.9 * r["module_s"]["jit_fn"]


def test_recorded_trace_names_every_long_gap(recorded):
    gaps = program_trace.idle_gaps(recorded)
    assert len(gaps) == 10
    for name, _ in gaps:
        assert name.startswith("question.call/repro."), name


def test_extract_reads_program_spans_with_their_line_and_args(tmp_path):
    import jax

    trace.start(str(tmp_path))
    with jax.profiler.TraceAnnotation("question.call"):
        with jax.profiler.TraceAnnotation("repro.sweep.dispatch", lanes=48,
                                          cold=1):
            pass
    trace.stop()
    path = trace.find_xplane(str(tmp_path))
    tr = trace.extract(path)
    assert [s[0] for s in tr["spans"]] == ["question.call"]
    tr.update(program_trace.extract(path, tr))
    (name, start, dur, line, args), = tr["program_spans"]
    assert (name, args) == ("repro.sweep.dispatch", {"lanes": 48, "cold": 1})
    assert line.startswith("python") and dur >= 0
    assert tr["op_scopes"] == {}
