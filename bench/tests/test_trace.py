"""The reduction from a profiler trace to busy time, idle gaps and
per-program device time."""

import json
import os

import pytest

from lib import trace

NS = 1_000_000  # one millisecond in ns

# two question spans of 10 ms each; the device runs jit_fn twice, with one
# overlap between its operations, and stays idle through the collect span
HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit_fn(7)", 1 * NS, 4 * NS], ["jit_fn(7)", 12 * NS,
                                                     3 * NS],
                    ["jit_concatenate.3", 16 * NS, 1 * NS]],
        "ops": [["fusion.1", 1 * NS, 2 * NS], ["while.2", 2 * NS, 3 * NS],
                ["fusion.1", 12 * NS, 3 * NS], ["copy", 16 * NS, 1 * NS]]}},
    "spans": [["question.call", 0, 6 * NS],
              ["question.collect", 6 * NS, 4 * NS],
              ["question.call", 10 * NS, 10 * NS]],
}


def test_busy_is_the_union_of_operation_intervals():
    r = trace.reduce(HAND)
    assert r["window_s"] == pytest.approx(0.020)
    # [1, 5] + [12, 15] + [16, 17] ms
    assert r["busy_s"] == pytest.approx(0.008)
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.6)


def test_program_time_is_keyed_on_the_jitted_name():
    r = trace.reduce(HAND)
    assert r["module_s"]["jit_fn"] == pytest.approx(0.007)
    assert r["module_runs"]["jit_fn"] == 2
    assert trace.module_base("jit__simulate_jit(123)") == "jit__simulate_jit"
    assert trace.module_base("jit_concatenate.3") == "jit_concatenate"


def test_idle_gaps_are_named_by_the_host_span_over_them():
    r = trace.reduce(HAND)
    longest_name, longest_s = r["idle_gaps"][0]
    assert longest_name == "question.collect"   # 5 ms .. 12 ms
    assert longest_s == pytest.approx(0.007)
    assert sum(s for _, s in r["idle_gaps"]) == pytest.approx(0.012)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(0.005)]


def test_busy_within_one_host_interval():
    assert trace.busy_within(HAND, 0, 6 * NS) == pytest.approx(0.004)
    assert trace.busy_within(HAND, 6 * NS, 10 * NS) == 0.0


def test_a_trace_without_spans_is_refused():
    with pytest.raises(ValueError):
        trace.reduce({"devices": {}, "spans": []})


FIXTURE = os.path.join(os.path.dirname(__file__), "data", "trace_v5e.json")


def test_recorded_chip_trace():
    """Two ``run()`` questions recorded on one TPU v5e: the module line
    and the benchmark's spans, reduced to the numbers read off the trace by
    hand (two 0.80 s and 0.79 s engine runs in a 1.613 s window)."""
    with open(FIXTURE) as f:
        tr = json.load(f)
    r = trace.reduce(tr)
    assert r["n_devices"] == 1
    assert r["window_s"] == pytest.approx(1.613220169)
    assert r["busy_s"] == pytest.approx(1.590966332)
    engine = trace.EXECUTABLES["engine_run"]
    assert r["module_runs"] == {engine: 2, "jit_convert_element_type": 4}
    assert r["module_s"][engine] == pytest.approx(0.803342775 + 0.787621186)
    assert r["device_ops"][0][0] == engine
    assert r["idle_gaps"][0] == ["question.call", pytest.approx(0.009255154)]
