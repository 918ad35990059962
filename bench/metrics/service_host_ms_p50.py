"""Median host milliseconds of a what-if query inside the server: its
``repro.service.request`` span less the device busy time inside it (the
server's share of ``query_host_ms_p50``, which reads the client's span)."""

from lib import program_trace, stats


def read(view):
    if view.raw_trace is None:
        return None
    spans = sorted((s, d) for name, s, d, *_ in
                   view.raw_trace.get("program_spans") or []
                   if name == "repro.service.request")
    if not spans:
        return None
    busy = program_trace.busy_seconds(view.raw_trace,
                                      [(s, s + d) for s, d in spans])
    host = [d * 1e-9 - b for (_, d), b in zip(spans, busy)]
    return stats.percentile(host, 50) * 1e3
