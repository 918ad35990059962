"""Device-idle milliseconds per traced question inside the self time of a
layer's program spans (a span less the program spans of its thread inside
it): the host work of that layer that holds the device back.  The variant
names the layer, then the end-to-end metric it moves:

- ``host_idle_ms.service``: ``repro.service.*`` and ``repro.planner.*``;
- ``host_idle_ms.api.query``, ``.api.sweep``: ``repro.sweep.*``,
  ``repro.run.*`` and ``repro.result.*``.

Traced questions are the trace's ``question.call`` spans."""

from lib import program_trace

PREFIXES = {
    "service": ("repro.service.", "repro.planner."),
    "api": ("repro.sweep.", "repro.run.", "repro.result."),
}


def read(view):
    tr = view.raw_trace
    if tr is None:
        return None
    prefixes = PREFIXES[view.variant.split(".")[0]]
    spans = tr.get("program_spans") or []
    if not any(s[0].startswith(prefixes) for s in spans):
        return None
    n = program_trace.questions(tr)
    if not n:
        return None
    idle = program_trace.idle_seconds(
        tr, program_trace.self_intervals(spans, prefixes))
    return idle / n * 1e3
