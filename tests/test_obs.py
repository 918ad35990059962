"""Program spans, counters and the engine's named scopes (DESIGN.md §21).

- the counter table, and ``cache_stats()`` reading it;
- a CPU profiler trace of a small ``run()``, ``sweep()`` and what-if
  request holds every host span, nested by time on its thread, with its
  arguments;
- ``GET /stats`` serves the table;
- every named scope reaches the ``op_name`` metadata of the compiled
  one-shot engine and sweep bucket program.
"""

import contextlib
import json
import re
import threading
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.api import (
    FailureModel, Scenario, SyntheticTrace, Topology, build_jobset,
    cache_stats, reset_cache_stats, run, sweep,
)
from repro.api.sweep import _bucket_fn, _bucket_program
from repro.core import engine
from repro.service import make_server


def scalar_scenario(n_jobs=40, policy="backfill"):
    return Scenario(trace=SyntheticTrace(n_jobs=n_jobs, seed=1,
                                         kind="sdsc_sp2"),
                    total_nodes=32, policy=policy)


def machine_scenario(n_jobs=24):
    return Scenario(trace=SyntheticTrace(n_jobs=n_jobs, seed=2,
                                         kind="sdsc_sp2"),
                    topology=Topology.dragonfly(4, 8), policy="backfill",
                    alloc="topo",
                    failures=FailureModel(mtbf=400_000.0, seed=5,
                                          max_failures=64))


MIXED_AXES = {"policy": ("fcfs", "backfill"), "alloc": ("simple", "topo")}


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_counter_table_counts_and_resets():
    obs.reset_counters("t.a", "t.b")
    obs.count("t.a")
    obs.count("t.a", 4)
    obs.count("t.b", 2)
    c = obs.counters()
    assert (c["t.a"], c["t.b"]) == (5, 2)
    c["t.a"] = 99                       # a copy, not the table
    assert obs.counters()["t.a"] == 5
    obs.reset_counters("t.a")
    c = obs.counters()
    assert (c["t.a"], c["t.b"]) == (0, 2)
    obs.reset_counters()
    assert not any(obs.counters().values())


def test_cache_stats_read_the_sweep_counters():
    scn = scalar_scenario(n_jobs=20)
    reset_cache_stats()
    before = obs.counters()
    sweep(scn, axes={"policy": ("fcfs", "sjf")})
    sweep(scn, axes={"policy": ("fcfs", "sjf")})
    c, s = obs.counters(), cache_stats()
    assert (s.compiles, s.hits) == (c["sweep.compiles"], c["sweep.hits"])
    assert s.compiles + s.hits == 2 and s.hits >= 1
    assert c["sweep.buckets"] - before.get("sweep.buckets", 0) == 2
    assert c["sweep.lanes"] - before.get("sweep.lanes", 0) == 4
    reset_cache_stats()
    s = cache_stats()
    assert (s.compiles, s.hits) == (0, 0) and s.entries >= 1
    assert obs.counters()["sweep.lanes"] == c["sweep.lanes"]


def test_xla_compiles_count_backend_compiles():
    obs.counters()                      # the listener is registered
    before = obs.counters().get("xla.compiles", 0)
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(7)).block_until_ready()
    assert obs.counters().get("xla.compiles", 0) >= before + 1


# ---------------------------------------------------------------------------
# host spans in a profiler trace
# ---------------------------------------------------------------------------


def program_spans(trace_dir):
    """``[(name, start_ns, end_ns, line, args)]`` of the ``repro.*`` host
    spans of the trace under ``trace_dir``."""
    import glob
    import os

    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                         "*.xplane.pb"), recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith("repro."):
                    out.append((e.name, e.start_ns, e.start_ns
                                + e.duration_ns, i, dict(e.stats)))
    return out


def inside(inner, outer):
    return (inner[3] == outer[3] and outer[1] <= inner[1]
            and inner[2] <= outer[2])


@pytest.fixture(scope="module")
def api_spans(tmp_path_factory):
    run_scn = scalar_scenario()
    sweep_scn = machine_scenario()
    run(run_scn).summary()              # compile outside the trace
    sweep(sweep_scn, MIXED_AXES).summaries()
    d = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(d)):
        run(run_scn).summary()
        sweep(sweep_scn, MIXED_AXES).summaries()
    return program_spans(d)


def test_run_spans(api_spans):
    names = [s[0] for s in api_spans]
    for name in ("repro.run.prepare", "repro.run.dispatch",
                 "repro.result.summary", "repro.result.fetch"):
        assert name in names
    prep = next(s for s in api_spans if s[0] == "repro.run.prepare")
    disp = next(s for s in api_spans if s[0] == "repro.run.dispatch")
    summ = next(s for s in api_spans if s[0] == "repro.result.summary")
    assert prep[2] <= disp[1] and disp[2] <= summ[1]
    fetch = next(s for s in api_spans if s[0] == "repro.result.fetch")
    assert inside(fetch, summ)


def test_sweep_spans_carry_lanes_and_cold(api_spans):
    by = {}
    for s in api_spans:
        by.setdefault(s[0], []).append(s)
    prep, = by["repro.sweep.prepare"]
    disp, = by["repro.sweep.dispatch"]
    split, = by["repro.sweep.split"]
    assert prep[4] == {"lanes": 4} and split[4] == {"lanes": 4}
    assert disp[4] == {"lanes": 4, "cold": 0}
    assert prep[2] <= disp[1] and disp[2] <= split[1]
    # one summary per lane after the split, each with its fetch inside
    lane_summaries = [s for s in by["repro.result.summary"]
                      if s[1] >= split[2]]
    assert len(lane_summaries) == 4
    for summ in lane_summaries:
        assert any(inside(f, summ) for f in by["repro.result.fetch"])


@pytest.fixture(scope="module")
def server():
    srv = make_server({"q": scalar_scenario(n_jobs=30, policy="fcfs")})
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    yield srv
    srv.shutdown()
    srv.server_close()
    t.join(timeout=30)


def post(url, doc):
    req = urllib.request.Request(url, data=json.dumps(doc).encode(),
                                 headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=300) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def get(url):
    with urllib.request.urlopen(url, timeout=300) as r:
        return json.loads(r.read())


CAPACITY = {"version": 1, "kind": "capacity", "queue": "q",
            "deltas": [{"add_nodes": 0}, {"add_nodes": 16}]}


def handlers_done(timeout=60):
    """Wait until the server's request handler threads have ended: a
    handler leaves its spans only after the client has read the answer,
    and a span still open when the profiler stops is not recorded."""
    deadline = time.monotonic() + timeout
    while (any("process_request_thread" in t.name
               for t in threading.enumerate())
           and time.monotonic() < deadline):
        time.sleep(0.01)


def test_service_request_spans_nest_and_share_an_id(server, tmp_path):
    post(server.url + "/query", CAPACITY)     # warm
    handlers_done()
    with jax.profiler.trace(str(tmp_path)):
        status, _ = post(server.url + "/query", CAPACITY)
        handlers_done()
    assert status == 200
    spans = program_spans(tmp_path)
    req, = [s for s in spans if s[0] == "repro.service.request"]
    assert req[4]["kind"] == "capacity" and isinstance(req[4]["id"], int)
    for name in ("repro.service.decode", "repro.planner.lower",
                 "repro.planner.rank", "repro.service.encode",
                 "repro.sweep.dispatch", "repro.result.fetch"):
        assert any(s[0] == name and inside(s, req) for s in spans), name
    lower = next(s for s in spans if s[0] == "repro.planner.lower")
    assert lower[4] == {"points": 2}
    order = [next(s for s in spans if s[0] == n)[1] for n in
             ("repro.service.decode", "repro.planner.lower",
              "repro.planner.rank")]
    assert order == sorted(order)


def test_stats_route_serves_the_counter_table(server):
    before = get(server.url + "/stats")
    assert post(server.url + "/query", CAPACITY)[0] == 200
    status, body = post(server.url + "/query", {"version": 1, "kind": "??"})
    assert status == 400
    after = get(server.url + "/stats")
    assert set(after) <= set(obs.counters())
    assert after["service.queries.capacity"] == \
        before.get("service.queries.capacity", 0) + 1
    assert after["service.errors"] == before.get("service.errors", 0) + 1
    assert after["sweep.buckets"] > before.get("sweep.buckets", 0)
    cache = get(server.url + "/cache")
    assert set(cache) == {"compiles", "hits", "entries"}


# ---------------------------------------------------------------------------
# named scopes in the compiled programs
# ---------------------------------------------------------------------------


def op_name_scopes(hlo_text):
    """Every path component of the op_name metadata of ``hlo_text``."""
    parts = set()
    for path in re.findall(r'op_name="([^"]*)"', hlo_text):
        parts.update(path.split("/")[:-1])
    return parts


def compiled_programs():
    """Optimized HLO text of the one-shot engine (scalar backfill) and of
    a bucket program (machine, failures, mixed policy), small shapes."""
    scn = scalar_scenario(n_jobs=32)
    jobs = build_jobset(scn)
    pol = engine.policies_id("backfill")
    one = engine._simulate_jit.lower(
        jobs, jnp.asarray(pol, jnp.int32), jnp.asarray(32, jnp.int32),
        None, fctx=None, static_policy=engine._static_policy_hint(pol),
        static_strategy=None).compile().as_text()
    base = machine_scenario(n_jobs=16)
    bucket = [base.with_(policy=p, alloc=a) for p in MIXED_AXES["policy"]
              for a in MIXED_AXES["alloc"]]
    key, args, machine, _ = _bucket_program(bucket, None)
    assert key[1] and key[7] is None        # failures, traced policy
    many = _bucket_fn(*key).lower(*args, machine).compile().as_text()
    return {"simulate": one, "bucket": many}


def strip_metadata(hlo_text):
    """The program without op metadata and the source-location tables it
    points into."""
    text = re.sub(r", metadata=\{[^{}]*\}", "", hlo_text)
    text = re.sub(r"(?ms)^(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n(^\d+ .*?\n)*", "", text)
    return text


@pytest.fixture(scope="module")
def compiled():
    return compiled_programs()


@pytest.fixture(scope="module")
def compiled_scopes(compiled):
    return {k: op_name_scopes(v) for k, v in compiled.items()}


@pytest.mark.parametrize("program,scope", [
    ("simulate", "advance"), ("simulate", "schedule"),
    ("bucket", "advance"), ("bucket", "failures"), ("bucket", "schedule"),
    ("bucket", "place"), ("bucket", "log"),
])
def test_named_scope_reaches_the_compiled_program(compiled_scopes, program,
                                                  scope):
    assert scope in compiled_scopes[program]


def test_scalar_engine_has_no_allocator_failure_or_log_scope(
        compiled_scopes):
    assert not {"failures", "place", "log"} & compiled_scopes["simulate"]


@pytest.mark.parametrize("program", ["simulate", "bucket"])
def test_named_scopes_change_only_metadata(compiled, monkeypatch, program):
    """With every scope a no-op the compiled program is the same text,
    metadata aside."""
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        bare = compiled_programs()[program]
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert "schedule" not in op_name_scopes(bare)
    assert bare != compiled[program]
    assert strip_metadata(bare) == strip_metadata(compiled[program])
