"""Median host milliseconds of a what-if query: its client span
(``query.http``) less the device busy time inside it."""

from lib import stats
from lib.trace import busy_within


def read(view):
    if view.raw_trace is None:
        return None
    spans = sorted((s, d) for name, s, d in view.raw_trace["spans"]
                   if name == "query.http")
    if not spans:
        return None
    host = [d * 1e-9 - busy_within(view.raw_trace, s, s + d)
            for s, d in spans]
    return stats.percentile(host, 50) * 1e3
