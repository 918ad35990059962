"""Device milliseconds of the cross-chip all-reduces of a sharded sweep
bucket per lockstep iteration: the operations named ``all-reduce*`` (an
async ``-start``/``-done`` pair counted by both halves) in the traced
window, averaged over the chips that ran, over the buckets' most lane
events.  The lockstep loop's conditions hold these all-reduces, so their
time includes each chip's wait for the slowest.  One chip has none, and
reads nothing."""

from lib import trace


def read(view):
    tr = view.raw_trace
    iters = view.traced.get("lockstep_iters")
    if tr is None or not tr["devices"] or not iters:
        return None
    lo = min(s for _, s, _ in tr["spans"])
    hi = max(s + d for _, s, d in tr["spans"])
    ns = sum(d for lines in tr["devices"].values()
             for name, s, d in lines["ops"]
             if lo <= s <= hi
             and trace.op_name(name).lstrip("%").startswith("all-reduce"))
    if not ns:
        return None
    return ns / len(tr["devices"]) * 1e-6 / iters
